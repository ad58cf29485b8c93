#!/usr/bin/env python3
"""One traced run of a cell, then the readers named on the command line and
what set-up was made of.

    python3 perfbench/readers.py --workload <cell> --seed <n> --seconds <s> \\
        --metrics setup_compile_s,setup_bind_s

Runs the cell as ``run.py --trace 1`` does and, after its result line,
prints

``readers {...}``: the number of every reader under ``perfbench/metrics/``
that ``--metrics`` names (none named, the four ``setup_*`` ones), whether or
not ``BENCHMARK.json`` lists it.
``setup {...}``: what the program's own profiler recorded between the
start of the process and the start of the window: for every span name that
ended before the window its count, summed seconds and summed self seconds
(less what its child spans cover); one record for each program that was
materialized (kind, key, what served it, why, the seconds of each phase);
what JAX compiled outside any of them; the harness's four ``setup_marks``;
and how much of the interval no span covers. The self seconds, less what
spans on different threads cover twice (``overlap_s``), plus what a span
still open at the window's start covers (``open_at_window_s``) and
``unattributed_s`` are ``setup_s``.

It is also where the ``setup_*`` readers keep what they share: the spans of
set-up cut at the window's start (:func:`setup_spans`), the compile work
counted once (:func:`compile_tops`), self seconds (:func:`self_seconds`).
A program that records none of this (the commit before it did) makes every
function here return ``None``.

No check runs this; it is the builder's tool, as ``tables.py`` is.
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import run as harness  # noqa: E402
from perfbench import scopes  # noqa: E402
from perfbench.reduce import gaps  # noqa: E402

SETUP_METRICS = ("setup_compile_s", "setup_bind_s", "setup_input_s",
                 "setup_unattributed_share")

# recorded once an import by every program that records set-up at all
IMPORT_SPAN = "import.mxnet_tpu"
JAX_PHASES = ("jax.trace", "jax.lower", "jax.backend_compile")
NS = scopes.NS


def setup_spans(ctx):
    """``(spans, start_ns, window_ns)``: the program's spans that begin
    before the window, by start, the interval from the start of the process
    (the harness's own stamp, ``perfbench.run.T_PROCESS``, on the spans'
    clock) to the start of the window. None where the program records no
    span of set-up."""
    profiler, window = scopes.program_profiler(ctx), scopes.window_ns(ctx)
    if profiler is None or window is None:
        return None
    found = profiler.spans(0, window[0])
    if not any(s.name == IMPORT_SPAN for s in found):
        return None
    start = int(ctx.get("t_process", harness.T_PROCESS) * NS)
    return found, start, window[0]


def ended(found, hi):
    return [s for s in found if s.end_ns <= hi]


def _ancestors(span, by_seq):
    seen = span
    while seen.parent in by_seq:
        seen = by_seq[seen.parent]
        yield seen


def compile_tops(found):
    """The compile work of ``found``, each piece once: every
    ``compile.materialize`` span, and what JAX reports of a trace, a
    lowering or a backend compile (``jax.*``) under none of them."""
    by_seq = {s.seq: s for s in found}
    return [s for s in found
            if s.name == "compile.materialize"
            or (s.name in JAX_PHASES
                and not any(a.name == "compile.materialize"
                            for a in _ancestors(s, by_seq)))]


def seconds_less_compiles(found, name):
    """Summed seconds of the outermost spans called ``name``, less what the
    compile work under them covers (:func:`compile_tops`)."""
    by_seq = {s.seq: s for s in found}

    def under(span):
        return any(a.name == name for a in _ancestors(span, by_seq))

    total = sum(s.end_ns - s.start_ns for s in found
                if s.name == name and not under(s))
    inside = sum(s.end_ns - s.start_ns for s in compile_tops(found)
                 if under(s))
    return (total - inside) / NS


def self_seconds(found):
    """``{seq: seconds}``: a span's duration less what the spans it caused
    cover of it (the rule of ``profiler.self_totals``)."""
    own = {s.seq: s.end_ns - s.start_ns for s in found}
    for s in found:
        if s.parent in own:
            own[s.parent] -= s.end_ns - s.start_ns
    return {seq: max(ns, 0) / NS for seq, ns in own.items()}


def covered_seconds(found, lo, hi):
    """Seconds of ``[lo, hi]`` that lie in any span of any thread."""
    cut = [(max(s.start_ns, lo) / NS, min(s.end_ns, hi) / NS) for s in found]
    left = gaps([c for c in cut if c[1] > c[0]], lo / NS, hi / NS)
    return (hi - lo) / NS - sum(e - s for s, e in left)


def setup_table(ctx, marks=None):
    got = setup_spans(ctx)
    if got is None:
        return None
    began, lo, hi = got
    found = ended(began, hi)
    own = self_seconds(found)
    by_name = {}
    for s in found:
        row = by_name.setdefault(s.name, {"n": 0, "s": 0.0, "self_s": 0.0})
        row["n"] += 1
        row["s"] += (s.end_ns - s.start_ns) / NS
        row["self_s"] += own[s.seq]
    programs = []
    for s in found:
        if s.name == "compile.materialize":
            phases = {}
            for c in found:
                if c.parent == s.seq:
                    phases[c.name] = phases.get(c.name, 0.0) \
                        + (c.end_ns - c.start_ns) / NS
            programs.append(dict(s.args or {},
                                 s=(s.end_ns - s.start_ns) / NS,
                                 at_s=(s.start_ns - lo) / NS, phases=phases))
    outside = {}
    for s in compile_tops(found):
        if s.name == "compile.materialize":
            continue
        row = outside.setdefault(s.name, {"n": 0, "s": 0.0, "jax_cache_hits":
                                          0, "longest": []})
        row["n"] += 1
        row["s"] += (s.end_ns - s.start_ns) / NS
        row["jax_cache_hits"] += bool((s.args or {}).get("jax_cache_hit"))
        row["longest"].append([(s.args or {}).get("fun", ""),
                               (s.end_ns - s.start_ns) / NS])
    for row in outside.values():
        row["longest"] = sorted(row["longest"], key=lambda r: -r[1])[:6]
    covered = covered_seconds(began, lo, hi)
    rows_cover = covered_seconds(found, lo, hi)
    self_sum = sum(row["self_s"] for row in by_name.values())
    table = {
        "setup_s": (hi - lo) / NS, "covered_s": covered,
        "unattributed_s": (hi - lo) / NS - covered, "self_sum_s": self_sum,
        # what the rows count twice: spans of different threads that cover
        # the same seconds (and what a span that began before the process
        # stamp holds of the time before it)
        "overlap_s": self_sum - rows_cover,
        # covered by a span still open at the window's start, so in no row
        "open_at_window_s": covered - rows_cover,
        "spans": dict(sorted(by_name.items(),
                             key=lambda kv: -kv[1]["self_s"])),
        "programs": programs, "jax_outside_programs": outside,
        "args": [[s.name, s.args] for s in found
                 if s.args and s.name.startswith(("bind", "input."))],
        "marks": marks}
    profiler = scopes.program_profiler(ctx)
    if hasattr(profiler, "counters"):
        table["counters"] = {
            k: v for k, v in profiler.counters().items()
            if k.startswith(("compile.", "bind.", "input.construct"))}
    return table


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    names = SETUP_METRICS
    if "--metrics" in argv:
        at = argv.index("--metrics")
        names = tuple(n for n in argv[at + 1].split(",") if n)
        del argv[at:at + 2]
    seen, load_module = {}, harness.load_module

    def keeping(kind, name):
        # the driver hands its ctx to the readers and its line to main()
        # and to nobody else: keep both as they pass
        mod = load_module(kind, name)
        if kind == "metrics":
            read = mod.read

            def kept_read(ctx):
                seen["ctx"] = ctx
                return read(ctx)
            mod.read = kept_read
        elif kind == "drivers":
            run = mod.run

            def kept_run(*args, **kwargs):
                seen["line"] = run(*args, **kwargs)
                return seen["line"]
            mod.run = kept_run
        return mod

    harness.load_module = keeping
    try:
        harness.main(argv + ["--trace", "1"])
    finally:
        harness.load_module = load_module
    ctx = seen.get("ctx")
    if ctx is None:
        raise SystemExit("the cell has no per-layer reader: no ctx to read")
    print("readers " + json.dumps(
        {name: harness.load_reader(name).read(ctx) for name in names}),
        flush=True)
    table = setup_table(ctx, seen.get("line", {}).get("setup_marks"))
    if table is not None:
        try:
            from mxnet_tpu import compiler
            table["compiler_stats"] = compiler.stats()["programs"]
        except Exception:   # noqa: BLE001 — a program without them
            pass
        print("setup " + json.dumps(table), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
