#!/usr/bin/env python
"""Serving-throughput record: continuous batching vs one-at-a-time.

The metric the batching subsystem exists for (ROADMAP item 3): the SAME
open-loop burst of single-row ResNet requests served through the same
`InferenceServer` twice — once with `max_batch=1` (the pre-batching
one-dispatch-per-request path) and once with the coalescer on
(`max_batch=16`) — at the same per-request deadline. Both runs must
finish every request inside that deadline; the record is requests/sec
for each, their ratio (`batched_speedup`, the acceptance gate is >= 3x),
and the measured p99 latency of each path.

The stateful half: an LSTM decode through `Module.as_decode_backend`
drives a full `InflightBatcher` (capacity 8) with a join/leave churn
event mid-stream, reporting decode tokens/sec, bitwise equality of two
churned sequences vs their solo decodes, and the retrace count (the
contract is 0 — one fixed-shape step program for the whole run).

``run()`` returns one nested bench.py record: the value is the batched
requests/sec, with absolute contract flags bench.py enforces: speedup
>= 3, decode bitwise, zero retraces/unwarmed signatures. Both models
bind on the default context (the chip where there is one) and the
record names the device. ``python benchmarks/bench_serving.py`` prints
the record.
"""
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

N_REQUESTS = 64
MAX_BATCH = 16
DEADLINE_S = 120.0          # generous p99 bound both paths must meet
IMAGE_SHAPE = (32, 32, 3)
NUM_CLASSES = 16

DECODE_CAPACITY = 8
DECODE_DIM = 64
DECODE_HIDDEN = 128
DECODE_STEPS = 32


def _resnet_backend():
    """A bound forward-only ResNet-18 Module at the coalescer's max
    batch (warm-up re-traces the smaller buckets)."""
    import mxnet_tpu as mx
    from mxnet_tpu import models

    sym = models.get_symbol("resnet", num_layers=18,
                            num_classes=NUM_CLASSES,
                            image_shape=",".join(map(str, IMAGE_SHAPE)))
    mod = mx.mod.Module(sym, label_names=[])
    mod.bind(data_shapes=[("data", (MAX_BATCH,) + IMAGE_SHAPE)],
             label_shapes=None, for_training=False)
    mx.random.seed(5)
    mod.init_params(mx.init.Xavier())
    return mod.as_serving_backend()


def _serve_burst(backend, max_batch):
    """Open-loop burst: submit all N single-row requests, one worker
    drains (coalescing when max_batch > 1), collect per-request
    latencies in submit order. Returns (requests/sec, p99 seconds)."""
    from mxnet_tpu.serving import InferenceServer

    server = InferenceServer(
        backend, name=f"bench-b{max_batch}", max_batch=max_batch,
        batch_wait=0.002, workers=1, capacity=N_REQUESTS,
        buckets=None if max_batch > 1 else [1],
        default_deadline=DEADLINE_S)
    server.warm_up()
    rng = np.random.RandomState(0)
    rows = [rng.rand(1, *IMAGE_SHAPE).astype(np.float32)
            for _ in range(N_REQUESTS)]

    t0 = time.perf_counter()
    pending = [server.submit({"data": x}) for x in rows]
    latencies = []
    for req in pending:
        server.result(req)
        latencies.append(time.perf_counter() - t0)
    wall = time.perf_counter() - t0
    stats = server.stats()
    server.close()
    assert stats["completed"] == N_REQUESTS, stats
    return {
        "rps": N_REQUESTS / wall,
        "p99_s": float(np.percentile(latencies, 99)),
        "dispatches": stats["dispatches"],
        "unwarmed_signatures":
            stats["batching"]["unwarmed_dispatch_signatures"],
    }


def _lstm_batcher(name):
    """One decode-step LSTM Module, identically initialized per call,
    wrapped as a warm InflightBatcher."""
    import mxnet_tpu as mx
    from mxnet_tpu.serving import InflightBatcher

    x = mx.sym.Variable("data")
    h = mx.sym.Variable("h")
    c = mx.sym.Variable("c")
    cell = mx.rnn.LSTMCell(DECODE_HIDDEN, prefix="dec_")
    out, (nh, nc) = cell(x, [h, c])
    logits = mx.sym.FullyConnected(out, name="proj",
                                   num_hidden=NUM_CLASSES)
    mod = mx.mod.Module(mx.sym.Group([logits, nh, nc]),
                        data_names=["data", "h", "c"],
                        label_names=[])
    mod.bind(data_shapes=[("data", (DECODE_CAPACITY, DECODE_DIM)),
                          ("h", (DECODE_CAPACITY, DECODE_HIDDEN)),
                          ("c", (DECODE_CAPACITY, DECODE_HIDDEN))],
             label_shapes=None, for_training=False)
    mx.random.seed(13)
    mod.init_params(mx.init.Xavier())
    return InflightBatcher(mod.as_decode_backend(["h", "c"]), name=name)


def bench_decode():
    """Full-table decode throughput + the join/leave bitwise contract."""
    rng = np.random.RandomState(7)
    tokens = [[rng.rand(DECODE_DIM).astype(np.float32)
               for _ in range(DECODE_STEPS)]
              for _ in range(DECODE_CAPACITY + 1)]   # +1: the joiner

    b = _lstm_batcher("bench-decode").warm_up()
    slots = [b.join() for _ in range(DECODE_CAPACITY)]
    traced = {0: [], DECODE_CAPACITY: []}   # churned sequences to verify

    # steady state: every slot fed, ONE dispatch per step — the tok/s
    # segment, with a churn event in the middle (sequence 0 leaves,
    # sequence DECODE_CAPACITY joins its recycled slot)
    churn_at = DECODE_STEPS // 2
    seq_for_slot0 = 0
    t0 = time.perf_counter()
    for t in range(DECODE_STEPS):
        if t == churn_at:
            b.leave(slots[0])
            slots[0] = b.join()
            seq_for_slot0 = DECODE_CAPACITY
        feed = {slots[i]: {"data": tokens[i][t]}
                for i in range(1, DECODE_CAPACITY)}
        tok = tokens[seq_for_slot0][t - churn_at if t >= churn_at else t]
        feed[slots[0]] = {"data": tok}
        outs = b.step(feed)
        traced[seq_for_slot0].append(outs[slots[0]][0])
    wall = time.perf_counter() - t0
    stats = b.stats()

    # bitwise contract: both sequences that churned through slot 0
    # match their solo decode exactly
    bitwise = True
    for seq, n_steps in ((0, churn_at), (DECODE_CAPACITY,
                                         DECODE_STEPS - churn_at)):
        solo = _lstm_batcher(f"bench-decode-ref{seq}").warm_up()
        s = solo.join()
        for t in range(n_steps):
            out = solo.step({s: {"data": tokens[seq][t]}})[s][0]
            bitwise &= bool(np.array_equal(out, traced[seq][t]))

    return {
        "tokens_per_sec": stats["tokens"] / wall,
        "steps": stats["steps"],
        "capacity": DECODE_CAPACITY,
        "bitwise_vs_sequential": bitwise,
        "retraces": int(stats["retraced"]),
    }


def run(quiet=False):
    from _device import device_stamp
    backend = _resnet_backend()
    batched = _serve_burst(backend, MAX_BATCH)
    unbatched = _serve_burst(backend, 1)
    speedup = batched["rps"] / unbatched["rps"]
    decode = bench_decode()
    record = {
        "metric": "serving_throughput",
        "value": round(batched["rps"], 2),
        "unit": "requests/sec",
        "device": device_stamp(),
        "unbatched_rps": round(unbatched["rps"], 2),
        "batched_speedup": round(speedup, 2),
        "p99_bound_s": DEADLINE_S,
        "p99_s": {"batched": round(batched["p99_s"], 4),
                  "unbatched": round(unbatched["p99_s"], 4)},
        "dispatches": {"batched": batched["dispatches"],
                       "unbatched": unbatched["dispatches"]},
        "unwarmed_signatures": (batched["unwarmed_signatures"]
                                + unbatched["unwarmed_signatures"]),
        "decode": {k: (round(v, 1) if isinstance(v, float) else v)
                   for k, v in decode.items()},
        "config": {"requests": N_REQUESTS, "max_batch": MAX_BATCH,
                   "model": f"resnet18/{NUM_CLASSES}c",
                   "image": "x".join(map(str, IMAGE_SHAPE)),
                   "decode": (f"lstm{DECODE_HIDDEN}"
                              f"x{DECODE_CAPACITY}slots")},
    }
    if not quiet:
        print(json.dumps(record))
    return record


if __name__ == "__main__":
    from _device import require_chip
    require_chip()
    run()
