#!/usr/bin/env python
"""Inference scoring throughput across the model zoo.

Reference analogue: example/image-classification/benchmark_score.py —
img/s for alexnet/vgg/inception/resnet at several batch sizes (the
reference's published K80 numbers live in its README; BASELINE.md). Runs
each zoo model's forward under jit; timings close with block_until_ready.

Usage: python benchmarks/benchmark_score.py [--models resnet18_v1,...]
       [--batch-sizes 1,32] [--image-shape 3,224,224]
"""
import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def score(model_name, batch, image_shape, iters=10):
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision

    c, h, w = image_shape
    net = vision.get_model(model_name, classes=1000)
    net.initialize(mx.init.Xavier())
    net.hybridize()
    x = mx.nd.array(np.random.rand(batch, c, h, w).astype(np.float32))
    # warm (compile)
    jax.block_until_ready(net(x)._data)
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = net(x)
    jax.block_until_ready(out._data)
    dt = time.perf_counter() - t0
    return batch * iters / dt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--models", default="alexnet,resnet18_v1,resnet50_v1,"
                    "vgg11,squeezenet1.1")
    ap.add_argument("--batch-sizes", default="1,32")
    ap.add_argument("--image-shape", default="3,224,224")
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from _device import require_chip
    print("device:", require_chip(), flush=True)

    shape = tuple(int(d) for d in args.image_shape.split(","))
    for name in args.models.split(","):
        for bs in (int(b) for b in args.batch_sizes.split(",")):
            ips = score(name, bs, shape, args.iters)
            print(f"{name:<16} batch {bs:>3}: {ips:10.1f} images/sec")


if __name__ == "__main__":
    main()
