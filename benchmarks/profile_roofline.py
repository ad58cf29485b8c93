#!/usr/bin/env python
"""Establish this chip's roofline: HBM bandwidth + matmul peak vs K.

Confirms/refutes the hypothesis that ResNet-shaped GEMMs (~200 flops/byte)
are bandwidth-bound on this chip. In-graph scan loops, 4-byte sync.
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
from profile_resnet import _sync, timed  # noqa: F401




def main():
    print("device:", require_chip(), flush=True)

    # HBM bandwidth: elementwise x*1.0000001 over a big array, K iters.
    # Each iter reads + writes the array once: 2*bytes traffic.
    for mb in (64, 256, 512):
        n = mb * 1024 * 1024 // 2  # bf16 elements
        x0 = jnp.ones((n,), jnp.bfloat16)
        K = 40

        def body(x, _):
            return x * jnp.bfloat16(1.0000001), ()

        @jax.jit
        def run(x):
            xf, _ = lax.scan(body, x, None, length=K)
            return jnp.mean(xf)

        dt = timed(run, x0) / K
        print(f"copy-scale {mb:4d} MB: {2 * mb / 1024 / dt:7.1f} GB/s",
              flush=True)

    # matmul peak vs inner dim K (M=N=4096): intensity ~ K flops/byte-ish
    for K in (256, 512, 1024, 2048, 4096, 8192):
        M = N = 4096
        a0 = jnp.asarray(np.random.rand(M, K), jnp.bfloat16)
        b = jnp.asarray(np.random.rand(K, N) * 0.01, jnp.bfloat16)
        it = max(5, int(3e12 / (2 * M * K * N)))

        def body(a, _):
            out = a @ b
            return a + (1e-30 * jnp.mean(out)).astype(a.dtype), ()

        @jax.jit
        def run(a):
            af, _ = lax.scan(body, a, None, length=it)
            return jnp.mean(af)

        dt = timed(run, a0) / it
        flops = 2 * M * K * N
        bytes_ = 2 * (M * K + K * N + M * N)
        print(f"mm {M}x{K}x{N}: {flops / dt / 1e12:6.1f} TF/s  "
              f"(intensity {flops / bytes_:5.0f} f/B, "
              f"implied bw {bytes_ / dt / 1e9:6.1f} GB/s)", flush=True)


if __name__ == "__main__":
    main()
