#!/usr/bin/env python
"""Where does the Gluon-LSTM bench step time go?

Op-level attribution of the EXACT `bench_lstm.py` training step (same
model build, same optimizer), with the same dispatch-amortized timing
discipline as `profile_resnet.py` (N async dispatches per measurement,
closed by ``block_until_ready`` — one synchronous dispatch is mostly
launch overhead).

Measured rows:

* end-to-end: fused runtime step (bf16 + fp32), the pre-round-6
  classic step (fwd program + fwd/bwd program + per-param optimizer
  dispatches), and the isolated fwd / fwd+bwd programs;
* components of one step, each as its own jitted program: embedding
  gather (fp32-table vs cast-table-first — the bf16 ordering fix),
  whole-sequence input projection, the sequential scan cells, the FC
  head, the softmax/loss tail (fwd+bwd), the SGD update, and the packed
  parameter unpack/repack pair the piece layout removed from the step;
* `--xplane DIR` additionally wraps the fused-step loop in
  ``jax.profiler.trace(DIR)`` for device-side XPlane inspection.

Prints a table (ms, share of the fused step) plus one JSON line for
machine consumption. Component shares are attribution estimates: XLA
fuses across component boundaries inside the real step, so they bound
rather than partition the step time (the same caveat as the r5 ResNet
profile's fusion parsing).
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp
from jax import lax

from _device import require_chip
from profile_resnet import _sync, timeit  # shared sync discipline


def _stepper_time(mod, batch, stepper, iters):
    """ms/step of the fused runtime step, async-amortized."""
    stepper.step(batch)     # compile + settle
    float(np.asarray(stepper._params["pred_weight"][0:1, 0:1]).ravel()[0])
    t0 = time.perf_counter()
    for _ in range(iters):
        stepper.step(batch)
    float(np.asarray(stepper._params["pred_weight"][0:1, 0:1]).ravel()[0])
    return (time.perf_counter() - t0) / iters


def _classic_time(mod, batch, iters):
    mod.forward(batch, is_train=True)
    mod.backward()
    mod.update()
    w = mod._exec.arg_dict["pred_weight"]
    float(w[0:1, 0:1].asnumpy()[0, 0])
    t0 = time.perf_counter()
    for _ in range(iters):
        mod.forward(batch, is_train=True)
        mod.backward()
        mod.update()
    float(w[0:1, 0:1].asnumpy()[0, 0])
    return (time.perf_counter() - t0) / iters


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch-size", type=int,
                    default=int(os.environ.get("PROFILE_BATCH", "64")))
    ap.add_argument("--seq-len", type=int,
                    default=int(os.environ.get("PROFILE_SEQ", "256")))
    ap.add_argument("--num-hidden", type=int,
                    default=int(os.environ.get("PROFILE_HIDDEN", "1024")))
    ap.add_argument("--num-layers", type=int, default=2)
    ap.add_argument("--vocab", type=int,
                    default=int(os.environ.get("PROFILE_VOCAB", "10000")))
    ap.add_argument("--iters", type=int,
                    default=int(os.environ.get("PROFILE_ITERS", "10")))
    ap.add_argument("--xplane", default=None,
                    help="directory for a jax.profiler XPlane trace of "
                         "the fused-step loop")
    args = ap.parse_args()
    N, T, H, L, V = (args.batch_size, args.seq_len, args.num_hidden,
                     args.num_layers, args.vocab)
    iters = args.iters

    import bench_lstm
    from mxnet_tpu import perf
    from mxnet_tpu.ops.pallas.lstm import lstm_cell_fused
    from mxnet_tpu.ops.nn_ops import _softmax_output_core
    from mxnet_tpu.ops.rnn_ops import _unpack

    print(f"device: {require_chip()}  config: {L}x{H} bs{N} T={T} V={V}",
          flush=True)
    rows = []

    def row(name, ms, note=""):
        rows.append((name, ms, note))
        print(f"{name:<34} {ms * 1e3:9.2f} ms  {note}", flush=True)

    # ---- end-to-end steps -------------------------------------------------
    mod, batch = bench_lstm.build(N, T, H, L, V)
    stepper = perf.module_stepper(mod, compute_dtype="bfloat16")
    dt_fused = _stepper_time(mod, batch, stepper, iters)
    row("step fused bf16 (bench path)", dt_fused,
        f"{N * T / dt_fused:,.0f} tok/s")
    if args.xplane:
        with jax.profiler.trace(args.xplane):
            for _ in range(3):
                stepper.step(batch)
            float(np.asarray(
                stepper._params["pred_weight"][0:1, 0:1]).ravel()[0])
        print(f"xplane trace written to {args.xplane}", flush=True)

    mod32, batch32 = bench_lstm.build(N, T, H, L, V)
    st32 = perf.module_stepper(mod32, compute_dtype=None)
    dt_f32 = _stepper_time(mod32, batch32, st32, iters)
    row("step fused fp32", dt_f32, f"{N * T / dt_f32:,.0f} tok/s")

    modc, batchc = bench_lstm.build(N, T, H, L, V)
    dt_classic = _classic_time(modc, batchc, iters)
    row("step classic fwd/bwd/update", dt_classic,
        f"{N * T / dt_classic:,.0f} tok/s")

    # ---- components (each its own program, bf16 like the bench step) -----
    share = lambda dt: f"{dt / dt_fused * 100:5.1f}% of fused step"  # noqa

    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, V, (N, T)).astype(np.int32))
    table32 = jnp.asarray(rng.rand(V, H).astype(np.float32))

    emb_fp32 = jax.jit(lambda t, i: jnp.take(t, i, axis=0))
    dt = timeit(emb_fp32, table32, ids, iters=iters)
    row("embedding gather fp32-table", dt, share(dt))
    emb_cast = jax.jit(
        lambda t, i: jnp.take(t.astype(jnp.bfloat16), i, axis=0))
    dt = timeit(emb_cast, table32, ids, iters=iters)
    row("embedding gather cast-first", dt, share(dt))

    x = jnp.asarray(rng.rand(T * N, H), jnp.bfloat16)
    w_i2h = jnp.asarray(rng.rand(4 * H, H), jnp.bfloat16)
    xproj_fn = jax.jit(lambda x, w: x @ w.T)
    dt = timeit(xproj_fn, x, w_i2h, iters=iters)
    row("input projection (1 layer)", dt, share(dt) + "  x2 layers")

    xproj = jnp.asarray(rng.rand(T, N, 4 * H), jnp.bfloat16)
    h0 = jnp.zeros((N, H), jnp.bfloat16)
    c0 = jnp.zeros((N, H), jnp.bfloat16)
    w_h2h = jnp.asarray(rng.rand(4 * H, H), jnp.bfloat16)

    @jax.jit
    def scan_cells(xproj, h0, c0, w_h2h):
        def body(carry, xp):
            h, c = carry
            h2, c2 = lstm_cell_fused(xp, h, c, w_h2h)
            return (h2, c2), h2
        return lax.scan(body, (h0, c0), xproj)

    dt = timeit(scan_cells, xproj, h0, c0, w_h2h, iters=iters)
    row("scan cells (1 layer, T steps)", dt, share(dt) + "  x2 layers")

    act = jnp.asarray(rng.rand(N * T, H), jnp.bfloat16)
    w_pred = jnp.asarray(rng.rand(V, H), jnp.bfloat16)
    head = jax.jit(lambda a, w: a @ w.T)
    dt = timeit(head, act, w_pred, iters=iters)
    row("FC head (N*T,H)@(H,V)", dt, share(dt))

    logits = jnp.asarray(rng.rand(N * T, V).astype(np.float32))
    labels = jnp.asarray(rng.randint(0, V, (N * T,)).astype(np.float32))

    @jax.jit
    def softmax_tail(logits, labels):
        def f(lg):
            return _softmax_output_core(lg, labels, 1.0, -1.0, False,
                                        False, False, "null", False)
        out, vjp = jax.vjp(f, logits)
        (dlg,) = vjp(jnp.ones_like(out))
        return out, dlg

    dt = timeit(softmax_tail, logits, labels, iters=iters)
    row("softmax/loss tail fwd+bwd", dt, share(dt))

    mod32._sync_fused()     # stepper donated the executor buffers
    params = {n: mod32._exec.arg_dict[n]._data
              for n in mod32._param_names}
    grads = {n: jnp.ones_like(v) for n, v in params.items()}

    @jax.jit
    def sgd_all(params, grads):
        from mxnet_tpu.ops.registry import OP_TABLE
        return {n: OP_TABLE["sgd_update"].fn(
            params[n], grads[n], lr=0.5, wd=0.0, rescale_grad=1.0,
            clip_gradient=-1.0) for n in params}

    dt = timeit(sgd_all, params, grads, iters=iters)
    row("optimizer (SGD, all params)", dt, share(dt))

    packed = params["lstm_parameters"]

    @jax.jit
    def unpack_repack(p):
        pieces = _unpack(p, L, H, H, "lstm", False)
        mats = [w.ravel() for per in pieces for w in per[0][:2]]
        vecs = [b.ravel() for per in pieces for b in per[0][2:]]
        return jnp.concatenate(mats + vecs)

    dt = timeit(unpack_repack, packed, iters=iters)
    row("packed param unpack+repack", dt,
        share(dt) + "  (removed from step by piece layout)")

    rec = {"metric": "lstm_profile",
           "config": f"{L}x{H} bs{N} T={T} V={V}",
           "fused_bf16_ms": round(dt_fused * 1e3, 2),
           "fused_fp32_ms": round(dt_f32 * 1e3, 2),
           "classic_ms": round(dt_classic * 1e3, 2),
           "rows": [{"name": n, "ms": round(ms * 1e3, 3)}
                    for n, ms, _ in rows]}
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
