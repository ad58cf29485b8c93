#!/usr/bin/env python
"""Would conv-as-matmul beat XLA's conv lowering on this chip?

For each ResNet-50 conv shape, measure (a) the implicit-GEMM matmul of
the same M/K/N, (b) for 3x3: a shift-and-accumulate decomposition (9
matmuls on shifted views), and compare with the conv rates from
profile_convs.py. All dispatch-amortized via in-graph scan.
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
from profile_resnet import resnet50_convs, _sync, timed  # noqa: F401




def mm_loop(M, K, N, Kiters):
    a0 = jnp.asarray(np.random.rand(M, K), jnp.bfloat16)
    b = jnp.asarray(np.random.rand(K, N) * 0.01, jnp.bfloat16)

    def body(a, _):
        out = a @ b
        return a + (1e-30 * jnp.mean(out)).astype(a.dtype), ()

    @jax.jit
    def run(a):
        af, _ = lax.scan(body, a, None, length=Kiters)
        return jnp.mean(af)

    return run, a0


def shift_conv_loop(B, h, w, cin, cout, Kiters):
    """3x3 stride-1 conv as 9 shifted (B*h*w, cin)@(cin, cout) matmuls."""
    x0 = jnp.asarray(np.random.rand(B, h, w, cin), jnp.bfloat16)
    wt = jnp.asarray(np.random.rand(3, 3, cin, cout) * 0.1, jnp.bfloat16)

    def conv(x):
        xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
        out = jnp.zeros((B, h, w, cout), jnp.float32)
        for dy in range(3):
            for dx in range(3):
                xs = lax.dynamic_slice(xp, (0, dy, dx, 0), (B, h, w, cin))
                out = out + jnp.einsum(
                    "bhwc,cd->bhwd", xs, wt[dy, dx],
                    preferred_element_type=jnp.float32)
        return out.astype(jnp.bfloat16)

    def body(x, _):
        out = conv(x)
        return x + (1e-30 * jnp.mean(out)).astype(x.dtype), ()

    @jax.jit
    def run(x):
        xf, _ = lax.scan(body, x, None, length=Kiters)
        return jnp.mean(xf)

    return run, x0


def main():
    B = int(os.environ.get("BENCH_BATCH", "256"))
    print("device:", require_chip(), flush=True)

    uniq = {}
    for shape in resnet50_convs():
        uniq[shape] = uniq.get(shape, 0) + 1

    print(f"{'HxW':>9} {'Cin':>4} {'Cout':>4} k s | {'mm TF/s':>8} "
          f"{'shift TF/s':>10}")
    for (h, w, cin, cout, k, s), _n in sorted(uniq.items()):
        M = B * (h // s) * (w // s)
        Kdim = cin * k * k
        flops = 2 * M * Kdim * cout
        Kit = int(min(300, max(10, 0.4e12 / flops * 10)))
        run, a0 = mm_loop(M, Kdim, cout, Kit)
        dt = timed(run, a0) / Kit
        shift_str = ""
        if k == 3 and s == 1:
            runs, x0 = shift_conv_loop(B, h, w, cin, cout, max(Kit, 10))
            dts = timed(runs, x0) / max(Kit, 10)
            shift_str = f"{flops / dts / 1e12:10.1f}"
        print(f"{h:4d}x{w:<4d} {cin:4d} {cout:4d} {k} {s} | "
              f"{flops / dt / 1e12:8.1f} {shift_str}", flush=True)


if __name__ == "__main__":
    main()
