#!/usr/bin/env python
"""Straggler-mitigation record: hedged vs unhedged p99 under gray failure.

The metric the gray-failure tier exists for (docs/how_to/fleet.md "Gray
failure & hedging"): the SAME open-loop burst of single-row requests
served twice by a 3-replica :class:`~mxnet_tpu.serving.FleetRouter`
with one replica wedged sticky-slow (the operator `slow_replica` hook —
deterministic, no fault plan), once with hedged dispatch OFF
(``hedge_max=0``) and once ON. The slow-eviction rung is disabled
(``slow_factor=0``) in both legs so the straggler stays in rotation and
the comparison isolates hedging itself, not vote-out. Replica workers
run numpy math that releases the GIL, so aggregate numbers are bounded
by the host core count (``host_cores`` is the honesty field, as in the
fleet bench).

``run()`` returns one nested bench.py record; the value is the
hedged-leg aggregate requests/sec. The acceptance contract (enforced
absolutely in bench.py) is ``hedged_p99 < unhedged_p99``, hedges
actually fired, and ZERO lost requests on both legs.
``python benchmarks/bench_straggler.py`` prints it.
"""
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

N_REQUESTS = 60
N_WARM = 12                     # recorded dispatches before the wedge
DIM = 256
LAYERS = 4
SLOW_S = 0.25                   # sticky per-dispatch burn on the straggler
DEADLINE_S = 60.0


def _factory(rid, source):
    """One replica's model: a tanh MLP in numpy — honest GIL-releasing
    host math, identical weights per replica."""
    from mxnet_tpu.serving import CallableBackend

    rng = np.random.RandomState(42)
    W = (rng.rand(DIM, DIM).astype(np.float32) - 0.5) / np.sqrt(DIM)

    def fn(arrays):
        h = arrays["data"]
        for _ in range(LAYERS):
            h = np.tanh(h @ W)
        return [h]

    return CallableBackend(fn, input_specs={"data": (DIM,)})


def _burst(name, hedge_max):
    """Open-loop burst against a fleet whose r1 is sticky-slow; returns
    rps/p99 plus the hedging counters."""
    from mxnet_tpu.serving import FleetRouter

    fr = FleetRouter(_factory, name=name, replicas=3, standbys=0,
                     workers=1, buckets=[1], capacity=N_REQUESTS,
                     default_deadline=DEADLINE_S, probe_period=0.005,
                     hedge_max=hedge_max, hedge_factor=2.0,
                     hedge_min_samples=8,
                     slow_factor=0.0)   # keep the straggler in rotation
    rng = np.random.RandomState(0)

    # identical warm phase on both legs: gives the fleet histogram the
    # samples hedging needs to arm, and a clean pre-wedge baseline
    warm = [fr.submit({"data": rng.rand(1, DIM).astype(np.float32)})
            for _ in range(N_WARM)]
    for req in warm:
        fr.tick()
        fr.result(req)
    fr.slow_replica("r1", SLOW_S)

    rows = [rng.rand(1, DIM).astype(np.float32) for _ in range(N_REQUESTS)]
    t0 = time.perf_counter()
    pending = [fr.submit({"data": x}) for x in rows]
    latencies, lost = [], 0
    for req in pending:
        fr.tick()                       # the serving control loop
        try:
            out = fr.result(req)
            assert out[0].shape[1] == DIM
        except Exception:               # noqa: BLE001 — counted as loss
            lost += 1
        latencies.append(time.perf_counter() - t0)
    wall = time.perf_counter() - t0
    totals = fr.stats()["totals"]
    fr.close()
    return {
        "rps": N_REQUESTS / wall,
        "p99_s": float(np.percentile(latencies, 99)),
        "lost": lost,
        "delivered": int(totals["delivered"]) - N_WARM,
        "hedges": int(totals["hedges"]),
        "hedge_wins": int(totals["hedge_wins"]),
        "hedges_suppressed": int(totals["hedges_suppressed"]),
    }


def run(quiet=False):
    unhedged = _burst("bench-strag-off", hedge_max=0)
    hedged = _burst("bench-strag-on", hedge_max=4)
    record = {
        "metric": "straggler_hedged_throughput",
        "value": round(hedged["rps"], 2),
        "unit": "requests/sec",
        "host_cores": os.cpu_count(),
        "p99_speedup": round(unhedged["p99_s"] / hedged["p99_s"], 2)
        if hedged["p99_s"] else 0.0,
        "hedged": {k: round(v, 4) if isinstance(v, float) else v
                   for k, v in hedged.items()},
        "unhedged": {k: round(v, 4) if isinstance(v, float) else v
                     for k, v in unhedged.items()},
        "config": {"requests": N_REQUESTS,
                   "model": f"tanh-mlp{DIM}x{LAYERS}",
                   "replicas": 3,
                   "slow_s": SLOW_S,
                   "hedge_max": 4},
    }
    if not quiet:
        print(json.dumps(record))
    return record


if __name__ == "__main__":
    run()
