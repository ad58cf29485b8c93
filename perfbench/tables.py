#!/usr/bin/env python3
"""PERF.md's "where the time goes" for one cell, from one traced run.

    python3 perfbench/tables.py --workload <cell> --seed <n> --seconds <s>

Runs the cell as ``run.py --trace 1`` does and, after its result line,
prints what the per-layer readers reduce to one number each, in full:

``spans {...}``: for every span name in the window its count, its share of
the window, its self time over the window (less what its child spans
cover; of the spans that touch the window, uncut, so it can pass the share
by an edge span's length), and the median, tenth percentile and least of
its durations in ms.
``scopes {...}``: device seconds by op type and direction and by ResNet
stage, the step programs' device time, and the unscoped instructions that
took most. Left out where the program has no op map or the trace no device
plane (the CPU rehearsal).

No reader and no check runs this; it is the builder's tool.
"""
import json
import os
import statistics
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import run as harness  # noqa: E402
from perfbench import scopes  # noqa: E402
from perfbench.reduce import short_name  # noqa: E402


def span_table(ctx):
    found = scopes.window_spans(ctx)
    if found is None:
        return None
    window_ns = ctx["window_s"] * scopes.NS
    lo, hi = scopes.window_ns(ctx)
    own = scopes.program_profiler(ctx).self_totals(lo, hi)
    by_name = {}
    for name, start, end in found:
        by_name.setdefault(name, []).append(end - start)
    out = {}
    for name, ns in sorted(by_name.items()):
        ns.sort()
        out[name] = {"n": len(ns), "share_pct": 100.0 * sum(ns) / window_ns,
                     "self_pct": 100.0 * own.get(name, 0) / window_ns,
                     "median_ms": statistics.median(ns) / 1e6,
                     "p10_ms": ns[len(ns) // 10] / 1e6,
                     "min_ms": ns[0] / 1e6}
    return out


def scope_table(ctx, top=8):
    found = scopes.scoped_seconds(ctx)
    if found is None:
        return None
    by_scope, step_seconds, unscoped = found
    by_type, by_stage = {}, {}
    for (scope, back), s in by_scope.items():
        kind = (scopes.op_type(scope) or "unscoped") + (".bwd" if back else "")
        by_type[kind] = by_type.get(kind, 0.0) + s
        node = scope.split("/", 1)[1] if scope and "/" in scope else ""
        stage = node.split("_", 1)[0] if node.startswith("stage") else \
            (node or scope or "unscoped")
        by_stage[stage] = by_stage.get(stage, 0.0) + s

    def largest(seconds, n=24):
        return sorted(seconds.items(), key=lambda kv: -kv[1])[:n]

    return {"step_device_s": step_seconds,
            "scoped_plus_unscoped_s": sum(by_scope.values()),
            "by_type_s": dict(largest(by_type)),
            "by_stage_s": dict(largest(by_stage)),
            "unscoped_ops": [[short_name(n), s]
                             for n, s in largest(unscoped, top)]}


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    seen = {}
    load_reader = harness.load_reader

    def keeping_ctx(metric):
        reader = load_reader(metric)

        def read(ctx):
            seen["ctx"] = ctx
            return reader.read(ctx)
        return types.SimpleNamespace(read=read)

    # the driver hands its ctx to the readers and to nobody else
    harness.load_reader = keeping_ctx
    try:
        harness.main(argv + ["--trace", "1"])
    finally:
        harness.load_reader = load_reader
    ctx = seen.get("ctx")
    if ctx is None:
        raise SystemExit("the cell has no per-layer reader: no ctx to read")
    for title, table in (("spans", span_table(ctx)),
                         ("scopes", scope_table(ctx))):
        if table is not None:
            print(title + " " + json.dumps(table), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
