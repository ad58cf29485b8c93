#!/usr/bin/env python
"""Serving-fleet record: replicated throughput + replica-kill chaos.

The metric the fleet tier exists for (ROADMAP item 3b): the SAME
open-loop burst of single-row requests served twice — once by a
3-replica :class:`~mxnet_tpu.serving.FleetRouter` (one threaded worker
per replica) and once by a 1-replica fleet — reporting aggregate
requests/sec and p99 latency for each. Replica workers run numpy math
that releases the GIL, so the aggregate scaling is bounded by the host
core count (``host_cores`` in the record is the honesty field, exactly
like the multichip bench: on a real pod each replica is its own host
and the same measurement is fleet scaling).

The chaos leg re-runs the 3-replica burst with a seeded
``fleet.dispatch`` fault killing one replica mid-burst: the record
reports requests re-routed, evictions/failovers, the measured
standby-promotion readiness seconds, and the chaos p99 vs the no-fault
p99 — the acceptance contract (enforced absolutely in bench.py) is
ZERO lost requests and a bounded p99 ratio.

``run()`` returns one nested bench.py record; the value is the
3-replica no-fault requests/sec. ``python benchmarks/bench_fleet.py``
prints it.
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
DIM = 512
LAYERS = 8
DEADLINE_S = 60.0
KILL_AT_DISPATCH = 20           # mid-burst
P99_CHAOS_FACTOR = 5.0          # chaos p99 <= no-fault p99 * factor + pad
P99_CHAOS_PAD_S = 0.5


def _factory(rid, source):
    """One replica's model: an 8-layer tanh MLP in numpy — honest
    GIL-releasing host math, identical weights per replica."""
    from mxnet_tpu.serving import CallableBackend

    rng = np.random.RandomState(42)
    W = (rng.rand(DIM, DIM).astype(np.float32) - 0.5) / np.sqrt(DIM)

    def fn(arrays):
        h = arrays["data"]
        for _ in range(LAYERS):
            h = np.tanh(h @ W)
        return [h]

    return CallableBackend(fn, input_specs={"data": (DIM,)})


def _burst(n_replicas, name, chaos=False):
    """Open-loop burst through a threaded fleet; returns rps/p99 plus
    the fleet's chaos counters."""
    from mxnet_tpu.resilience import FaultPlan, faults
    from mxnet_tpu.serving import FleetRouter

    if chaos:
        faults.arm(FaultPlan(seed=7).arm("fleet.dispatch",
                                         nth=KILL_AT_DISPATCH))
    else:
        faults.disarm()
    fr = FleetRouter(_factory, name=name, replicas=n_replicas,
                     standbys=1 if chaos else 0, workers=1,
                     buckets=[1], capacity=N_REQUESTS,
                     default_deadline=DEADLINE_S, probe_period=0.005)
    rng = np.random.RandomState(0)
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
    faults.disarm()
    return {
        "rps": N_REQUESTS / wall,
        "p99_s": float(np.percentile(latencies, 99)),
        "lost": lost,
        "re_routed": int(totals["re_routed"]),
        "evictions": int(totals["evictions"]),
        "failovers": int(totals["failovers"]),
        "standby_ready_s": float(totals["last_standby_ready_s"]),
        "delivered": int(totals["delivered"]),
    }


def run(quiet=False):
    fleet3 = _burst(3, "bench-fleet3")
    fleet1 = _burst(1, "bench-fleet1")
    chaos = _burst(3, "bench-fleet-chaos", chaos=True)
    p99_bound = fleet3["p99_s"] * P99_CHAOS_FACTOR + P99_CHAOS_PAD_S
    record = {
        "metric": "fleet_throughput",
        "value": round(fleet3["rps"], 2),
        "unit": "requests/sec",
        "single_replica_rps": round(fleet1["rps"], 2),
        "fleet_speedup": round(fleet3["rps"] / fleet1["rps"], 2),
        "host_cores": os.cpu_count(),
        "p99_s": {"fleet3": round(fleet3["p99_s"], 4),
                  "fleet1": round(fleet1["p99_s"], 4)},
        "chaos": {
            "lost": chaos["lost"],
            "delivered": chaos["delivered"],
            "re_routed": chaos["re_routed"],
            "evictions": chaos["evictions"],
            "failovers": chaos["failovers"],
            "standby_ready_s": round(chaos["standby_ready_s"], 4),
            "p99_s": round(chaos["p99_s"], 4),
            "p99_bound_s": round(p99_bound, 4),
            "p99_within_bound": bool(chaos["p99_s"] <= p99_bound),
        },
        "config": {"requests": N_REQUESTS,
                   "model": f"tanh-mlp{DIM}x{LAYERS}",
                   "replicas": "3v1+chaos",
                   "kill_at_dispatch": KILL_AT_DISPATCH},
    }
    if not quiet:
        print(json.dumps(record))
    return record


if __name__ == "__main__":
    run()
