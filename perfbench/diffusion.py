#!/usr/bin/env python3
"""``blocks.py`` for a decoder trained under the block-diffusion objective:
the same traced run and ``blocks {...}`` line, its ``metrics`` holding the
mask's attention roofline and the share of tokens that carried loss beside
the five decoder readers (all but ``loss_weighted_share`` are
``BENCHMARK.json`` entries since PR 37), a ``counters {...}`` line with the
program's two counters of the objective, and a ``mask_probe {...}`` line.

    python3 perfbench/diffusion.py --workload <cell> --seed <n> --seconds <s>

The probe reads what the cell's comparison cannot (PERF.md section 2): a
mask that is wrong inside the walk's diagonal tiles alone. Under weights
drawn N(0, 0.02) attention is close to a mean over the keys a row sees, and
4 keys more among thousands move no gradient's norm; so the probe runs the
program's attention alone, forward and backward as a step runs them, on
seeded q, k, v whose scores are sharp (a row's softmax sits on a few keys),
against the model file's plain reference under the same mask, and against
that reference with the planted ``leak``.
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import blocks, scopes  # noqa: E402

DIFFUSION_METRICS = ("block_diffusion_attention_roofline",
                     "loss_weighted_share")
# trace-time, so 0 after a warm load from the executable store; the window's
COUNTERS = ("attention.block_diffusion_layers", "loss.weighted_tokens")

# q is drawn SHARP times N(0, 1), so the scores are N(0, SHARP^2) and a row's
# softmax sits on its few largest keys. On the chip at 16,384 rows 4, 8 and
# 16 read alike; at 1 (a mean over thousands of keys, as under the cell's
# N(0, 0.02) weights) the leak's deepest tiles read like the kernel's own
# rounding (PERF.md section 2)
SHARP = 8.0
# the probe's limit on a row's gap: between the largest that the compiled
# kernel read (0.039) and the least that the leak read in a tile it cuts
# (0.22), over five seeds on the chip (PERF.md section 2; the lines in
# tests/perfbench/data/readings/sdar-30b-a3b.mask_probe.jsonl)
ROW_GAP_LIMIT = 0.1


def _row_gaps(got, want, tile):
    """(tiles,): in each tile of ``tile`` rows the worst row's ``|got -
    want|`` over the root mean square of ``want``'s rows, over every head.
    ``got`` and ``want`` are (heads, rows, d)."""
    import jax.numpy as jnp
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    size = jnp.sqrt(jnp.mean(jnp.sum(jnp.square(want), -1)))
    gap = jnp.sqrt(jnp.sum(jnp.square(got - want), -1)) / size
    return jnp.max(gap.reshape(gap.shape[0], -1, tile), axis=(0, 2))


def _probe_programs(cfg, length, tile, force_pallas=False):
    """``(program, plain)``: the program's attention and the model file's
    reference (``plain(fault)``), each a jitted ``(q, k, v, g) -> (out, dq,
    dk, dv)`` over bfloat16 (heads, 2 x length, d)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas.attention import grouped_query_attention
    from perfbench import run as harness
    model = harness.load_module("models", "sdar-30b-a3b")
    H, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    B, S = cfg["block_length"], 2 * length

    @jax.jit
    def program(q, k, v, g):
        out, back = jax.vjp(
            lambda *qkv: grouped_query_attention(
                *(x[None] for x in qkv), causal=True, block=tile,
                block_length=B, force_pallas=force_pallas)[0], q, k, v)
        return (out,) + back(g)

    def plain(fault):
        def attend(q, k, v):
            # the reference's layout: rows first, query head = kv x group
            rows = model._attend(
                q.reshape(kv, H // kv, S, d).transpose(2, 0, 1, 3),
                k.transpose(1, 0, 2), v.transpose(1, 0, 2), length, B,
                lambda x: x, tile, fault)
            return rows.transpose(1, 2, 0, 3).reshape(H, S, d)

        @jax.jit
        def run(q, k, v, g):
            out, back = jax.vjp(attend, *(x.astype(jnp.float32)
                                          for x in (q, k, v)))
            return (out,) + back(g.astype(jnp.float32))
        return run

    return program, plain


def mask_probe(cfg, length, seed, tile=512, force_pallas=False):
    """One document of ``length`` tokens (2 x ``length`` rows) at the
    configuration's heads: the program's ``grouped_query_attention(...,
    block_length=B)`` and its backward on bfloat16 q, k, v from the seed, q
    scaled ``SHARP``-fold, against the model file's float32 reference (its
    own three-line mask) on the same values. A row's gap is the norm of its
    difference over the root mean square row of the reference; a tile reads
    its worst row over every head. ``sound`` is the largest tile of output,
    dq, dk and dv; ``leak`` is, against the reference with the fault
    planted, the LEAST over the tiles the fault touches (the noisy half's
    query tiles for output and dq, the clean half's key tiles for dk and
    dv, which the noisy rows of their own blocks now reach): every diagonal
    tile of the walk has to show it. The clean half's
    queries see what they saw (``clean_queries``: the largest tile of
    their output and dq, which reads like ``sound``). ``held``: the kernel
    under the limit and the leak over it in every tile it touches.
    ``force_pallas`` runs the kernel through the interpreter (tests)."""
    import jax
    import jax.numpy as jnp
    from perfbench.seeded import seed_key

    H, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    B, S, tile = cfg["block_length"], 2 * length, min(tile, length)
    program, plain = _probe_programs(cfg, length, tile, force_pallas)
    keys = jax.random.split(jax.random.fold_in(seed_key(seed), 0xB10C), 4)
    q, k, v, g = (scale * jax.random.normal(key, (heads, S, d), jnp.float32)
                  for key, heads, scale in zip(
                      keys, (H, kv, kv, H), (SHARP, 1.0, 1.0, 1.0)))
    q, k, v, g = (x.astype(jnp.bfloat16) for x in (q, k, v, g))

    got = program(q, k, v, g)
    want, leaky = plain(None)(q, k, v, g), plain("leak")(q, k, v, g)
    n, names = length // tile, ("out", "dq", "dk", "dv")
    sound = {name: float(jnp.max(_row_gaps(a, b, tile)))
             for name, a, b in zip(names, got, want)}
    gaps = {name: _row_gaps(a, b, tile)
            for name, a, b in zip(names, got, leaky)}
    leak = {"out": float(jnp.min(gaps["out"][:n])),
            "dq": float(jnp.min(gaps["dq"][:n])),
            "dk": float(jnp.min(gaps["dk"][n:])),
            "dv": float(jnp.min(gaps["dv"][n:])),
            "clean_queries": float(jnp.maximum(jnp.max(gaps["out"][n:]),
                                               jnp.max(gaps["dq"][n:])))}
    held = max(sound.values()) <= ROW_GAP_LIMIT \
        and min(leak[name] for name in names) > ROW_GAP_LIMIT
    return {"rows": S, "heads": [H, kv, d], "block_length": B, "tile": tile,
            "sharp": SHARP, "seed": int(seed), "limit": ROW_GAP_LIMIT,
            "sound": sound, "leak": leak, "held": bool(held),
            "device": jax.devices()[0].device_kind}


def main():
    import argparse
    from perfbench import run as harness
    blocks.DECODER_METRICS = blocks.DECODER_METRICS + DIFFUSION_METRICS
    code = blocks.main()
    profiler = scopes.program_profiler({})
    found = profiler.counters() if hasattr(profiler, "counters") else {}
    print("counters " + json.dumps({k: found.get(k) for k in COUNTERS}),
          flush=True)
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_known_args()[0]
    cell = harness.load_cell(args.workload, args.rehearse)
    probe = mask_probe(cell["cfg"], cell["traffic_params"]["seq_len"],
                       args.seed, force_pallas=args.rehearse)
    print("mask_probe " + json.dumps(probe), flush=True)
    return code if probe["held"] else code or 1


if __name__ == "__main__":
    sys.exit(main())
