#!/usr/bin/env python
"""Ragged-serving record: the pad tax, dense vs packed (ROADMAP item 4).

The SAME open-loop mixed-length burst served twice through the
deterministic ``workers=0`` server (both legs drain identically, so the
comparison isolates the batching geometry, not thread scheduling):

- **dense leg** — today's contract: every client pads its sequence to
  the ``L_BUCKET``-token row and sends a ``lengths`` input, the
  coalescer pads the batch axis to the warmed bucket. The pad-waste
  token ratio is what the fleet burns today.
- **packed leg** — the ragged contract: clients send raw ``(1, L, D)``
  rows, the :class:`~mxnet_tpu.serving.SequencePacker` first-fit packs
  them into shared ``L_BUCKET`` rows with segment ids, scatter restores
  each member bitwise.

The record is each leg's requests/sec, p99, pad-waste token ratio and
warmed-signature count, plus ``pad_waste_improvement`` (dense ratio /
packed ratio — the tentpole acceptance gate is >= 3x at equal p99 with
the compile count flat or lower) and a ``symbolic`` sub-record showing
the warm-up matrix collapse (ONE warmed signature where the dense
matrix warms ``len(coalescer_sizes)``).

``run()`` returns one nested bench.py record; the value is the
packed-leg requests/sec. The absolute contracts bench.py enforces
regardless of history: improvement >= 3, packed p99 <= dense p99 x
1.5, packed warmed signatures <= dense, zero unwarmed signatures, zero
lost requests, bitwise packed outputs.
``python benchmarks/bench_ragged.py`` prints the record.
"""
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

N_REQUESTS = 48
MAX_BATCH = 8
L_BUCKET = 32
DIM = 8
LENGTHS = [1, 2, 3, 4]      # cycled: mean 2.5 real tokens per request
DEADLINE_S = 120.0
P99_BAND = 1.5              # packed p99 must stay within dense x this


def _fn(arrays):
    """Per-token affine transform: packing-safe (no cross-token mixing)
    so the packed scatter is bitwise against the dense result."""
    return [np.asarray(arrays["data"], np.float32) * 3.0 + 1.0]


def _burst_lengths():
    return [LENGTHS[i % len(LENGTHS)] for i in range(N_REQUESTS)]


def _raw_rows(rng):
    return [rng.standard_normal((1, n, DIM)).astype(np.float32)
            for n in _burst_lengths()]


def _serve(backend, name, requests):
    """Open-loop burst through a workers=0 server; returns the leg's
    measurements. ``requests`` maps each raw row to its submitted feed."""
    from mxnet_tpu.serving import InferenceServer

    server = InferenceServer(
        backend, name=name, max_batch=MAX_BATCH, workers=0,
        capacity=N_REQUESTS, default_deadline=DEADLINE_S)
    server.warm_up()
    t0 = time.perf_counter()
    pending = [server.submit(feed) for feed in requests]
    server.run_pending()
    outs, latencies = [], []
    for req in pending:
        outs.append(server.result(req))
        latencies.append(time.perf_counter() - t0)
    wall = time.perf_counter() - t0
    stats = server.stats()
    server.close()
    assert stats["completed"] == N_REQUESTS, stats
    return {
        "rps": N_REQUESTS / wall,
        "p99_s": float(np.percentile(latencies, 99)),
        "pad_waste": stats["pad_waste"],
        "dispatches": stats["dispatches"],
        "warmed_signatures": stats["batching"]["warmed_signatures"],
        "unwarmed_signatures":
            stats["batching"]["unwarmed_dispatch_signatures"],
        "lost": N_REQUESTS - stats["completed"],
    }, outs


def bench_dense(rng):
    """Today's contract: client-padded rows + a lengths input, so the
    waste is token-exact on the dense leg too."""
    from mxnet_tpu.serving import CallableBackend

    backend = CallableBackend(
        _fn, input_specs={"data": (L_BUCKET, DIM), "lengths": ()},
        input_dtypes={"lengths": "int32"},
        pack_axis=1, lengths_name="lengths")
    raw = _raw_rows(rng)
    requests = []
    for row in raw:
        padded = np.zeros((1, L_BUCKET, DIM), np.float32)
        padded[:, :row.shape[1]] = row
        requests.append({"data": padded,
                         "lengths": np.array([row.shape[1]], np.int32)})
    leg, outs = _serve(backend, "bench-ragged-dense", requests)
    bitwise = all(
        np.array_equal(got[0], feed["data"] * 3.0 + 1.0)
        for got, feed in zip(outs, requests))
    leg["bitwise"] = bitwise
    return leg


def bench_packed(rng):
    """The ragged contract: raw variable-length rows, packed rows +
    segment ids on the wire, bitwise scatter back."""
    from mxnet_tpu.serving import CallableBackend

    backend = CallableBackend(
        _fn, input_specs={"data": (L_BUCKET, DIM)},
        pack_axis=1, accepts_segment_ids=True)
    raw = _raw_rows(rng)
    leg, outs = _serve(backend, "bench-ragged-packed",
                       [{"data": row} for row in raw])
    bitwise = all(np.array_equal(got[0], row * 3.0 + 1.0)
                  for got, row in zip(outs, raw))
    leg["bitwise"] = bitwise
    return leg


def bench_symbolic():
    """The warm-up matrix collapse: ONE symbolic probe where the dense
    matrix warms every coalescer size."""
    from mxnet_tpu.serving import InferenceServer, SymbolicJitBackend
    from mxnet_tpu.serving.warmup import coalescer_sizes

    dense_sizes = len(coalescer_sizes(MAX_BATCH))
    server = InferenceServer(
        SymbolicJitBackend(lambda arrays: [arrays["data"] * 2.0],
                           max_rows=MAX_BATCH,
                           input_specs={"data": (DIM,)}),
        name="bench-ragged-symbolic", max_batch=MAX_BATCH, workers=0,
        default_deadline=DEADLINE_S)
    server.warm_up()
    pending = [server.submit({"data": np.ones((rows, DIM), np.float32)})
               for rows in (1, 3, 5, 8, 2)]
    server.run_pending()
    for req in pending:
        server.result(req)
    stats = server.stats()
    server.close()
    return {
        "dense_warmup_sizes": dense_sizes,
        "warmed_signatures": stats["batching"]["warmed_signatures"],
        "warmup_skipped_covered": stats["warmup_skipped_covered"],
        "unwarmed_signatures":
            stats["batching"]["unwarmed_dispatch_signatures"],
    }


def run(quiet=False):
    rng = np.random.default_rng(11)
    dense = bench_dense(rng)
    packed = bench_packed(rng)
    symbolic = bench_symbolic()
    dense_ratio = float(dense["pad_waste"]["ratio"])
    packed_ratio = float(packed["pad_waste"]["ratio"])
    improvement = dense_ratio / packed_ratio if packed_ratio else 0.0
    record = {
        "metric": "ragged_serving_throughput",
        "value": round(packed["rps"], 2),
        "unit": "requests/sec",
        "pad_waste_ratio": {"dense": round(dense_ratio, 3),
                            "packed": round(packed_ratio, 3)},
        "pad_waste_improvement": round(improvement, 2),
        "p99_s": {"dense": round(dense["p99_s"], 4),
                  "packed": round(packed["p99_s"], 4)},
        "p99_band": P99_BAND,
        "dispatches": {"dense": dense["dispatches"],
                       "packed": packed["dispatches"]},
        "warmed_signatures": {"dense": dense["warmed_signatures"],
                              "packed": packed["warmed_signatures"]},
        "unwarmed_signatures": (dense["unwarmed_signatures"]
                                + packed["unwarmed_signatures"]),
        "lost": dense["lost"] + packed["lost"],
        "bitwise": bool(dense["bitwise"] and packed["bitwise"]),
        "symbolic": symbolic,
        "config": {"requests": N_REQUESTS, "max_batch": MAX_BATCH,
                   "bucket_tokens": L_BUCKET, "dim": DIM,
                   "lengths": "x".join(map(str, LENGTHS))},
    }
    if not quiet:
        print(json.dumps(record))
    return record


if __name__ == "__main__":
    run()
