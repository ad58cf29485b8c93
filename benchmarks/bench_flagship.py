#!/usr/bin/env python
"""Flagship-tier micro-benchmarks: flash attention and MoE dispatch.

bench.py nests both records into the headline JSON line on every
default-config run.

Method: same discipline as the other benches — a warm-up dispatch, then
``iters`` async dispatches amortizing per-dispatch latency, closed by
``block_until_ready``. Every record names the device it ran on.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from _device import device_stamp, require_chip


def bench_flash_attention(batch=4, heads=16, seq=2048, head_dim=64,
                          iters=10, quiet=True):
    """Causal flash attention fwd+bwd; value = achieved TFLOP/s.

    Uses the Pallas kernel on TPU (jnp reference elsewhere) through the
    registered ``flash_attention`` custom-vjp entry, bf16 inputs.
    """
    from mxnet_tpu.ops.pallas.attention import flash_attention

    B, H, S, D = batch, heads, seq, head_dim
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.rand(B, H, S, D), jnp.bfloat16)
    k = jnp.asarray(rng.rand(B, H, S, D), jnp.bfloat16)
    v = jnp.asarray(rng.rand(B, H, S, D), jnp.bfloat16)

    @jax.jit
    def step(q, k, v):
        def f(q, k, v):
            return flash_attention(q, k, v, True)
        out, vjp = jax.vjp(f, q, k, v)
        dq, dk, dv = vjp(jnp.ones_like(out))
        # scalar summary keeps the program's output transfer at 4 bytes
        return (out.astype(jnp.float32).ravel()[0]
                + dq.astype(jnp.float32).ravel()[0]
                + dk.astype(jnp.float32).ravel()[0]
                + dv.astype(jnp.float32).ravel()[0])

    jax.block_until_ready(step(q, k, v))     # compile + settle
    t0 = time.perf_counter()
    for _ in range(iters):
        out = step(q, k, v)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / iters
    # causal fwd: 2 matmuls over the lower triangle = 4*B*H*S^2*D / 2;
    # bwd recomputes scores and needs dq/dk/dv (5 matmuls) ~ 2.5x fwd
    fwd_flops = 4 * B * H * S * S * D / 2
    tflops = fwd_flops * 3.5 / dt / 1e12
    rec = {
        "metric": "flash_attention_train",
        "value": round(tflops, 2),
        "unit": "TFLOP/s",
        "config": f"B{B} H{H} S{S} D{D} causal bf16 fwd+bwd",
        "device": device_stamp(),
        "ms_per_step": round(dt * 1e3, 2),
    }
    if not quiet:
        print(f"flash attention {rec['config']}: {dt * 1e3:.2f} ms, "
              f"{tflops:.1f} TF/s")
    return rec


def bench_moe_dispatch(tokens=8192, d_model=1024, num_experts=8,
                       hidden=4096, iters=10, quiet=True):
    """SwitchFFN route+dispatch+combine fwd+bwd; value = tokens/sec.

    Single-chip dense dispatch path (the expert-parallel all_to_all path
    needs a multi-chip mesh); capacity factor 2.0, top-1 routing.
    """
    from mxnet_tpu.ops.moe_ops import _switch_ffn

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(tokens, d_model), jnp.bfloat16)
    gate = jnp.asarray(rng.rand(d_model, num_experts) * 0.02, jnp.bfloat16)
    w1 = jnp.asarray(rng.rand(num_experts, d_model, hidden) * 0.02,
                     jnp.bfloat16)
    b1 = jnp.zeros((num_experts, hidden), jnp.bfloat16)
    w2 = jnp.asarray(rng.rand(num_experts, hidden, d_model) * 0.02,
                     jnp.bfloat16)
    b2 = jnp.zeros((num_experts, d_model), jnp.bfloat16)

    @jax.jit
    def step(x, gate, w1, b1, w2, b2):
        def f(x, gate, w1, b1, w2, b2):
            out, aux = _switch_ffn(x, gate, w1, b1, w2, b2,
                                   num_experts=num_experts,
                                   hidden_size=hidden)
            return out.astype(jnp.float32).sum() + aux.astype(jnp.float32)
        loss, grads = jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4, 5))(
            x, gate, w1, b1, w2, b2)
        return loss + grads[0].ravel()[0].astype(jnp.float32)

    jax.block_until_ready(step(x, gate, w1, b1, w2, b2))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = step(x, gate, w1, b1, w2, b2)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / iters
    tps = tokens / dt
    rec = {
        "metric": "moe_dispatch_train",
        "value": round(tps, 0),
        "unit": "tokens/sec/chip",
        "config": (f"tok{tokens} d{d_model} E{num_experts} f{hidden} "
                   f"top1 cf2.0 bf16 fwd+bwd"),
        "device": device_stamp(),
        "ms_per_step": round(dt * 1e3, 2),
    }
    if not quiet:
        print(f"moe dispatch {rec['config']}: {dt * 1e3:.2f} ms, "
              f"{tps:,.0f} tok/s")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--small", action="store_true",
                    help="tiny shapes: a control-flow smoke that also "
                         "runs on the CPU (its record says so)")
    args = ap.parse_args()
    if not args.small:
        require_chip()
    if args.small:
        fa = bench_flash_attention(batch=1, heads=2, seq=128, head_dim=32,
                                   iters=args.iters, quiet=False)
        moe = bench_moe_dispatch(tokens=256, d_model=64, num_experts=4,
                                 hidden=128, iters=args.iters, quiet=False)
    else:
        fa = bench_flash_attention(iters=args.iters, quiet=False)
        moe = bench_moe_dispatch(iters=args.iters, quiet=False)
    print(json.dumps({"flash_attention": fa, "moe_dispatch": moe}))


if __name__ == "__main__":
    main()
