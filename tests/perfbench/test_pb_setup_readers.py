"""The four readers that split ``setup_s`` by the program's own spans
(``perfbench/metrics/setup_*.py`` over ``perfbench/readers.py``), each
against a made-up list of spans whose answer is worked out by hand, and
``readers.py`` itself through a CPU rehearsal of one cell."""
import json
import subprocess
import sys
from collections import namedtuple

import pytest

from _pb import BENCH, ROOT, cpu_env
from perfbench import readers
from perfbench import run as harness

Span = namedtuple("Span", "seq name start_ns end_ns thread parent args",
                  defaults=(None,))
SETUP = list(readers.SETUP_METRICS)


class Program:
    """Stands where ``mxnet_tpu.profiler`` does; times in seconds."""

    def __init__(self, rows):
        self._spans = [Span(seq, name, int(s * 1e9), int(e * 1e9), thread,
                            parent, *more)
                       for seq, name, s, e, thread, parent, *more in rows]

    def spans(self, lo, hi):
        return sorted((s for s in self._spans
                       if s.end_ns >= lo and s.start_ns <= hi),
                      key=lambda s: (s.start_ns, s.seq))


def make(rows, window_start=130.0, t_process=100.0):
    """A process that started at 100 s on the host's clock and a window of
    10 s that starts at 130 s: 30 s of set-up."""
    return {"profiler": Program(rows) if rows is not None else None,
            "feed": {"calls": [(window_start, window_start + 0.5)]},
            "window_s": 10.0, "t_process": t_process}


def read(metric, ctx):
    return harness.load_reader(metric).read(ctx)


# seq, name, start, end, thread, parent
RUN = [
    (0, "import.mxnet_tpu", 104.0, 107.0, 1, -1),
    # a helper jitted outside everything: trace, lowering, compile
    (1, "jax.trace", 107.0, 107.5, 1, -1),
    (2, "jax.lower", 107.5, 108.0, 1, -1),
    (3, "jax.backend_compile", 108.0, 109.0, 1, -1),
    # bind: 6 s, of which a 1 s compile while the parameters are placed
    (4, "bind", 110.0, 116.0, 1, -1),
    (5, "bind.plan", 110.0, 111.0, 1, 4),
    (6, "bind.params", 111.0, 115.0, 1, 4),
    (7, "jax.backend_compile", 112.0, 113.0, 1, 6),
    (8, "bind.state", 115.0, 116.0, 1, 4),
    # the iterators: 2 s and next to nothing, half a second of compile inside
    (9, "input.construct", 116.0, 118.0, 1, -1),
    (10, "jax.backend_compile", 116.5, 117.0, 1, 9),
    (11, "input.construct", 118.0, 118.25, 1, -1),
    # the first checked step materializes the step program: 5 s, its
    # phases and JAX's own reports inside it counted once
    (12, "fit.step", 120.0, 126.0, 1, -1),
    (13, "step.dispatch", 120.5, 126.0, 1, 12),
    (14, "compile.materialize", 121.0, 126.0, 1, 13,
     {"kind": "spmd-step", "source": "compiled", "cause": "first",
      "key": "0123456789ab"}),
    (15, "compile.lower", 121.0, 123.0, 1, 14),
    (16, "jax.trace", 121.0, 122.0, 1, 15),
    (17, "compile.backend", 123.0, 126.0, 1, 14),
    (18, "jax.backend_compile", 123.0, 126.0, 1, 17),
    # the producer thread works beside the fit thread
    (19, "input.fetch", 125.0, 127.0, 2, -1),
    # a step that straddles the window's start, and one inside it that
    # recompiles: neither is set-up's
    (20, "fit.step", 129.0, 131.0, 1, -1),
    (21, "compile.materialize", 132.0, 133.0, 1, -1,
     {"kind": "spmd-step", "source": "compiled", "cause": "new_signature"}),
    (22, "bind", 134.0, 135.0, 1, -1),
    (23, "input.construct", 134.0, 135.0, 1, -1),
]


def test_compile_seconds_count_each_piece_once_and_stop_at_the_window():
    # the helper 0.5 + 0.5 + 1, the compile under bind 1, the one under the
    # iterator 0.5, the step program 5 (its children not again); the
    # window's recompile is not set-up
    assert read("setup_compile_s", make(RUN)) == pytest.approx(8.5)


def test_bind_and_input_seconds_leave_out_the_compiles_under_them():
    assert read("setup_bind_s", make(RUN)) == pytest.approx(6.0 - 1.0)
    assert read("setup_input_s", make(RUN)) == pytest.approx(2.25 - 0.5)


def test_nested_binds_count_once():
    rows = RUN + [(30, "bind", 110.5, 110.75, 1, 5)]     # a module's inner
    assert read("setup_bind_s", make(rows)) == pytest.approx(5.0)


def test_unattributed_share_is_what_no_span_of_any_thread_covers():
    # covered in [100, 130]: 104-109, 110-118.25, 120-127 (the producer
    # carries on for a second after the fit thread's span), 129-130
    covered = 5.0 + 8.25 + 7.0 + 1.0
    assert read("setup_unattributed_share", make(RUN)) == pytest.approx(
        100.0 * (30.0 - covered) / 30.0)
    # a span that began before the process stamp is cut to the interval
    early = [(40, "import.mxnet_tpu", 98.0, 107.0, 1, -1)] + RUN[1:]
    assert read("setup_unattributed_share", make(early)) == pytest.approx(
        100.0 * (30.0 - covered - 4.0) / 30.0)


def test_a_resident_feed_reads_zero_not_nothing():
    rows = [r for r in RUN if r[1] != "input.construct"
            and r[5] not in (9,)]
    assert read("setup_input_s", make(rows)) == 0.0


@pytest.mark.parametrize("metric", SETUP)
def test_a_program_without_the_spans_reads_nothing_and_raises_nothing(
        metric):
    # the parent commit: the fit loop's spans, none of set-up
    parent = [r for r in RUN if r[1].startswith(("fit.", "step.",
                                                 "input.fetch"))]
    assert read(metric, make(parent)) is None
    assert read(metric, make([])) is None
    assert read(metric, make(None)) is None         # no profiler at all
    ctx = make(RUN)
    ctx["feed"]["calls"] = []                       # a window never opened
    assert read(metric, ctx) is None


def test_the_setup_table_adds_up_and_names_each_program():
    table = readers.setup_table(make(RUN), marks={"import_s": 1.0})
    assert table["setup_s"] == pytest.approx(30.0)
    assert table["covered_s"] == pytest.approx(21.25)
    assert table["unattributed_s"] == pytest.approx(8.75)
    rows = table["spans"]
    # only what ended before the window; self time leaves the children out
    assert rows["compile.materialize"] == {"n": 1, "s": 5.0, "self_s": 0.0}
    assert rows["compile.lower"]["self_s"] == pytest.approx(1.0)
    assert rows["fit.step"] == {"n": 1, "s": 6.0, "self_s": 0.5}
    assert rows["bind"]["self_s"] == 0.0 and rows["bind"]["n"] == 1
    assert rows["bind.params"]["self_s"] == pytest.approx(3.0)
    assert rows["input.construct"]["n"] == 2
    # the fit thread's spans never overlap: the producer's 1 s beside them
    # is the whole overlap, and self + unattributed - overlap is set-up
    assert table["overlap_s"] == pytest.approx(1.0)
    # the step that straddles the window's start is in no row
    assert table["open_at_window_s"] == pytest.approx(1.0)
    assert table["self_sum_s"] - table["overlap_s"] \
        + table["open_at_window_s"] + table["unattributed_s"] \
        == pytest.approx(table["setup_s"])
    program, = table["programs"]
    assert program["kind"] == "spmd-step" and program["source"] == "compiled"
    assert program["s"] == pytest.approx(5.0)
    assert program["at_s"] == pytest.approx(21.0)
    assert program["phases"] == {"compile.lower": pytest.approx(2.0),
                                 "compile.backend": pytest.approx(3.0)}
    outside = table["jax_outside_programs"]
    assert outside["jax.backend_compile"]["n"] == 3
    assert outside["jax.backend_compile"]["s"] == pytest.approx(2.5)
    assert outside["jax.trace"]["n"] == 1
    assert table["marks"] == {"import_s": 1.0}


def test_the_docstrings_state_the_entries_as_they_stand():
    want = {"setup_compile_s": ("``s``", "step runtime and compile"),
            "setup_bind_s": ("``s``", "step runtime and compile"),
            "setup_input_s": ("``s``", "input pipeline"),
            "setup_unattributed_share": ("``%``", "whole set-up")}
    listed = {m["name"]: m for m in BENCH["per_layer"]}
    for name, (unit, layer) in want.items():
        doc = " ".join(harness.load_reader(name).__doc__.split())
        for word in (f"unit {unit}", "``better: lower``",
                     "``source: program_span``", f"``layer: {layer}``",
                     "``moves: setup_s``"):
            assert word in doc, (name, word)
        # entered by PR 37 as the docstring states it, with no list
        assert listed[name] == {
            "name": name, "unit": unit.strip("`"), "better": "lower",
            "source": "program_span", "layer": layer, "moves": "setup_s"}


def test_readers_script_rehearses_a_cell_and_prints_both_lines(tmp_path):
    env = cpu_env()
    # both caches its own: a warm JAX cache would serve the step program
    # and the store would have nothing to load on the second run
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax")
    env.pop("MXTPU_COMPILE_CACHE_DIR", None)
    argv = [sys.executable, "perfbench/readers.py", "--workload",
            "resnet50.train-fed", "--seed", "2147483659", "--seconds", "1",
            "--metrics", "setup_compile_s,setup_bind_s,setup_input_s,"
            "setup_unattributed_share,step_dispatch_ms", "--rehearse"]
    tables = []
    for _ in range(2):                  # an empty store, then a warm one
        done = subprocess.run(argv, cwd=ROOT, env=env, capture_output=True,
                              text=True, timeout=600)
        assert done.returncode == 0, done.stderr[-3000:]
        lines = done.stdout.strip().splitlines()
        assert json.loads(lines[-3])["rehearsal"] == "passed"
        assert lines[-2].startswith("readers ")
        assert lines[-1].startswith("setup ")
        got = json.loads(lines[-2][len("readers "):])
        assert sorted(got) == sorted(SETUP + ["step_dispatch_ms"])
        assert all(v is not None and v >= 0 for v in got.values())
        table = json.loads(lines[-1][len("setup "):])
        tables.append(table)
        assert got["setup_unattributed_share"] == pytest.approx(
            100.0 * table["unattributed_s"] / table["setup_s"])
        assert sorted(table["marks"]) == ["build_s", "checked_steps_s",
                                          "feed_s", "import_s"]
        assert table["marks"]["checked_steps_s"] <= table["setup_s"] + 0.05
        # one fit thread does nearly all of it: the rows add up to set-up
        assert abs(table["overlap_s"]) < 0.05 * table["setup_s"]
        assert table["self_sum_s"] - table["overlap_s"] \
            + table["open_at_window_s"] + table["unattributed_s"] \
            == pytest.approx(table["setup_s"])
        assert table["spans"]["import.mxnet_tpu"]["n"] == 1
        assert table["spans"]["bind"]["n"] >= 1
        assert table["spans"]["input.construct"]["n"] == 2
        made = table["counters"]["compile.materialized"]
        assert made == len(table["programs"]) == \
            table["compiler_stats"]["compiled"] \
            + table["compiler_stats"]["loaded"]
    cold, warm = (t["programs"][0] for t in tables)
    assert (cold["kind"], cold["source"], cold["cause"]) == (
        "spmd-step", "compiled", "first")
    assert warm["source"] == "loaded" and warm["key"] == cold["key"]
    assert sorted(cold["phases"]) == ["compile.backend", "compile.lower",
                                      "compile.op_map", "compile.store_get",
                                      "compile.store_put"]
    assert sorted(warm["phases"]) == ["compile.load", "compile.store_get"]
