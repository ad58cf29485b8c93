#!/usr/bin/env python
"""Dispatch-amortized conv microbenchmarks (in-graph lax.scan loops).

A single dispatch is mostly launch overhead, so single-op timing is useless;
each measurement runs K conv applications inside ONE jitted scan with a
serial data dependency (x += eps*mean(out)) so XLA cannot hoist or batch
them. Prints per-ResNet-50-conv-shape fwd and bwd TF/s plus the expected
total conv time for one fwd pass at batch B.
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax

from _device import require_chip
from profile_resnet import (resnet50_convs, conv_flops,  # noqa: F401
                            _sync, timed)




def conv_loop(h, w, cin, cout, k, s, B, K, bwd=False):
    p = k // 2
    x0 = jnp.asarray(np.random.rand(B, h, w, cin), jnp.bfloat16)
    wt = jnp.asarray(np.random.rand(k, k, cin, cout) * 0.1, jnp.bfloat16)
    dn = lax.conv_dimension_numbers(x0.shape, wt.shape,
                                    ("NHWC", "HWIO", "NHWC"))

    def f(x, wt):
        return lax.conv_general_dilated(
            x, wt, (s, s), [(p, p), (p, p)], dimension_numbers=dn)

    if not bwd:
        def body(x, _):
            out = f(x, wt)
            return x + (1e-30 * jnp.mean(out)).astype(x.dtype), ()
    else:
        ct = jnp.ones((B, h // s, w // s, cout), jnp.bfloat16)

        def body(x, _):
            dx, dw = jax.vjp(f, x, wt)[1](ct)
            return x + (1e-30 * (jnp.mean(dx) + jnp.mean(dw))).astype(
                x.dtype), ()

    @jax.jit
    def run(x):
        xf, _ = lax.scan(body, x, None, length=K)
        return jnp.mean(xf)

    return run, x0


def main():
    B = int(os.environ.get("BENCH_BATCH", "256"))
    print("device:", require_chip(), flush=True)

    uniq = {}
    for shape in resnet50_convs():
        uniq[shape] = uniq.get(shape, 0) + 1

    tot_fwd = tot_bwd = 0.0
    print(f"{'HxW':>9} {'Cin':>4} {'Cout':>4} k s n K | "
          f"{'fwd TF/s':>8} {'bwd TF/s':>8} | fwd-ms bwd-ms")
    for (h, w, cin, cout, k, s), n in sorted(uniq.items()):
        flops = conv_flops(B, h, w, cin, cout, k, s)
        K = int(min(300, max(10, 0.4e12 / flops * 10)))
        run, x0 = conv_loop(h, w, cin, cout, k, s, B, K)
        dt_f = timed(run, x0) / K
        runb, x0 = conv_loop(h, w, cin, cout, k, s, B, max(K // 3, 5),
                             bwd=True)
        dt_b = timed(runb, x0) / max(K // 3, 5)
        tot_fwd += n * dt_f
        tot_bwd += n * dt_b
        print(f"{h:4d}x{w:<4d} {cin:4d} {cout:4d} {k} {s} {n} {K:3d} | "
              f"{flops / dt_f / 1e12:8.1f} {2 * flops / dt_b / 1e12:8.1f} | "
              f"{dt_f * 1e3:6.2f} {dt_b * 1e3:6.2f}", flush=True)
    print(f"\nexpected conv-only: fwd {tot_fwd * 1e3:.1f} ms, "
          f"bwd {tot_bwd * 1e3:.1f} ms per batch-{B} step")


if __name__ == "__main__":
    main()
