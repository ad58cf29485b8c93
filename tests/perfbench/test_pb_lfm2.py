"""What the LFM2-8B-A1B configuration adds to the benchmark: the work
functions of the short convolution's roofline reader by hand, its two readers
on a recorded shape of trace and in a program without the records, the
control and the planted faults at the rehearsal's size, the readings of the
chip runs against the committed limits, and what the cell reports."""
import json
import os
import types

import pytest

import _structure as st
from _pb import BENCH, PB
from perfbench import blocks, compare, hybrid
from perfbench import run as harness

CELL = "lfm2-8b-a1b.train-fed-2x8k"


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(PB, "configs", "lfm2-8b-a1b.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def model():
    return harness.load_module("models", "lfm2-8b-a1b")


def test_short_conv_work_and_bound_by_hand(cfg):
    with open(os.path.join(PB, "peaks.json")) as f:
        peaks = json.load(f)["TPU v5 lite"]
    reader = harness.load_reader("short_conv_roofline")
    flops, nbytes = reader.work(cfg, 2, 8192)
    assert flops == 2 * 4 * 16384 * 2048
    assert nbytes == 4 * 16384 * 2048 * 2        # b, c, h in, one out, bf16
    least, bound = reader.least_seconds(cfg, 2, 8192, peaks)
    assert bound == "memory"
    assert least == pytest.approx(nbytes / 819e9) == pytest.approx(
        0.3277e-3, rel=1e-3)


class _Trace:
    """Two steps of 1 s; a conv layer's three parts, forward and backward."""

    devices = {"/device:TPU:0": [
        ("%fusion.1 = f32[] fusion()", 0.0, 0.1),
        ("%fusion.2 = f32[] fusion()", 0.1, 0.15),
        ("%fusion.3 = f32[] fusion()", 0.15, 0.3),
        ("%fusion.4 = f32[] fusion()", 0.3, 0.6),
        ("%fusion.5 = f32[] fusion()", 0.6, 1.0),
        ("%fusion.1 = f32[] fusion()", 2.0, 2.1),
        ("%fusion.2 = f32[] fusion()", 2.1, 2.15),
        ("%fusion.3 = f32[] fusion()", 2.15, 2.3),
        ("%fusion.4 = f32[] fusion()", 2.3, 2.6),
        ("%fusion.5 = f32[] fusion()", 2.6, 3.0)]}

    def busiest(self):
        return "/device:TPU:0"

    def steps(self, device=None):
        return [(0.0, 1.0), (2.0, 3.0)]


def _ctx(cfg, ops=None):
    profiler = types.SimpleNamespace(
        spans=lambda lo, hi: [], counters=lambda: {},
        op_scopes=lambda kind: ops if kind == "spmd-step" else {})
    return {"trace": _Trace(), "profiler": profiler, "cfg": cfg,
            "traffic": {"per_chip_batch": 2, "seq_len": 8192},
            "model": types.SimpleNamespace(),
            "feed": {"batches": 2, "calls": [(0.0, 0.1)]}, "window_s": 3.0,
            "counters": {"step_programs": 0, "compiles": 0},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


def test_short_conv_readers_on_a_recorded_shape_of_trace(cfg):
    pre = "jit(step)/jvp(layer0)/ShortConv/layer0_conv/"
    back = "jit(step)/transpose(jvp(jvp()))/checkpoint/layer0/ShortConv/" \
           "layer0_conv/"
    ops = {"fusion.1": pre + "in_proj/dot_general",
           "fusion.2": pre + "conv/mul",
           "fusion.3": back + "conv/reduce_sum",
           "fusion.4": back.replace("checkpoint/", "checkpoint/"
                                    "rematted_computation/") + "conv/mul",
           "fusion.5": "jit(step)/optimizer_update/mul"}
    ctx = _ctx(cfg, ops)
    # all three stages of the op: 0.6 s of each 1 s step
    assert harness.load_reader("short_conv_share").read(ctx) \
        == pytest.approx(60.0)
    # the first forward's conv part alone: 0.05 s a step, four conv layers
    reader = harness.load_reader("short_conv_roofline")
    least, _ = reader.least_seconds(cfg, 2, 8192, ctx["peaks"])
    assert reader.read(ctx) == pytest.approx(100 * least * 4 * 2 / 0.1)
    # a configuration without conv layers has nothing to read
    assert reader.read(dict(_ctx({"layer_types": ["full_attention"],
                                  "num_hidden_layers": 1}, ops))) is None


@pytest.mark.parametrize("metric", hybrid.HYBRID_METRICS)
def test_readers_find_nothing_in_a_program_without_the_records(cfg, metric):
    """The parent commit: no ``ShortConv`` scope in the op map, or no op
    map at all; and a rehearsal's trace with no device plane."""
    ops = {"fusion.1": "jit(step)/jvp(layer0)/FullyConnected/fc/dot",
           "fusion.2": "jit(step)/optimizer_update/mul"}
    read = harness.load_reader(metric).read
    assert read(_ctx(cfg, ops)) is None
    assert read(_ctx(cfg)) is None
    bare = _ctx(cfg, ops)
    bare["trace"] = types.SimpleNamespace(
        busiest=lambda: None, steps=lambda device=None: [], devices={})
    assert read(bare) is None


def test_the_hybrid_table_adds_its_two_readers_to_the_decoders():
    assert not set(hybrid.HYBRID_METRICS) & set(blocks.DECODER_METRICS)
    listed = {m["name"]: m for m in BENCH["per_layer"]}
    # entries since PR 37, each read in this cell alone
    for name in hybrid.HYBRID_METRICS:
        assert listed[name]["workloads"] == [CELL]
        assert hasattr(harness.load_reader(name), "read")


def test_the_cell_reports_a_rate_and_the_metrics_with_no_list():
    cell = harness.load_cell(CELL)
    assert [m["name"] for m in cell["end_to_end"]] == ["train_rate",
                                                       "setup_s"]
    reads = {m["name"] for m in cell["per_layer"]}
    assert reads == st.cell_metrics(BENCH, CELL)[1]
    assert st.DECODER_BASE | set(blocks.DECODER_METRICS) \
        - {"window_attention_roofline"} | set(hybrid.HYBRID_METRICS) <= reads
    assert cell["chips"] == 1
    traffic = cell["traffic_params"]
    assert (traffic["per_chip_batch"], traffic["seq_len"],
            traffic["distinct_batches"], traffic["check_steps"],
            traffic["feed"], traffic["driver"]) \
        == (2, 8192, 4, 3, "host", "train_fit")
    laguna = harness.load_cell("laguna-xs2.train-fed-seq8k")["cfg"]
    assert cell["cfg"]["optimizer"] == laguna["optimizer"]


def test_control_and_planted_faults_are_not_correct(model):
    """At the rehearsal's size: the reference in fp8, with half the tokens
    left out, and with the bias left out of the selection, put in the
    program's place, each fail one of the cell's numbers; the reference
    itself passes. (One held expert's output left out moves a tiny model's
    numbers no more than a flipped choice does: the chip's readings judge
    that fault at the published widths.)"""
    import jax
    cell = harness.load_cell(CELL, rehearse=True)
    cfg, tr = cell["cfg"], cell["traffic_params"]
    ref = model.reference(cfg, tr, 41, devices=jax.devices())
    for kwargs in ({"precision": "fp8"}, {"fault": "half_batch"},
                   {"fault": "bias_out"}):
        bad = model.reference(cfg, tr, 41, devices=jax.devices(), **kwargs)
        ok, checks = compare.judge(compare.gaps(bad, ref)[0], cell["limits"])
        assert ok is False, (kwargs, checks)
    assert compare.judge(compare.gaps(ref, ref)[0], cell["limits"])[0]
    with pytest.raises(ValueError, match="unknown fault"):
        model.reference(cfg, tr, 41, fault="no_such_fault")


def _readings(suffix=".jsonl"):
    path = os.path.join(os.path.dirname(__file__), "data", "readings",
                        CELL + suffix)
    with open(path) as f:
        return [json.loads(t) for t in f if '"kind"' in t]


LIMITS = ("loss_gap", "grad_gap_worst", "grad_gap_median",
          "delta_gap_worst", "delta_gap_median")


def _upper_reading(number, rows):
    """The least reading that is the number's to catch: the smallest of the
    fp8 control if that is 3 times the largest sound reading or more, the
    smallest of a planted fault if 10 times or more, and on the parameters'
    change the 1 that a state left unchanged reads (``test_pb_faults.py``
    shows that reading) if 3 times or more. A reading nearer than that is
    another number's to catch and sets no limit here."""
    sound = max(r[number] for r in rows if r["kind"] == "program")
    least = {kind: min(r[number] for r in rows if r["kind"] == kind)
             for kind in {r["kind"] for r in rows} - {"program"}}
    if number.startswith("delta_gap"):
        least["state_unchanged"] = 1.0
    held = {kind: low for kind, low in least.items()
            if low >= sound * (10 if kind.startswith("fault_") else 3)}
    assert held, (number, sound, least)
    return sound, min(held.values()), held


@pytest.mark.parametrize("number", LIMITS)
def test_every_limit_lies_between_its_two_readings(number):
    """Each number the cell holds: its limit above the largest reading of
    the sound chip runs and under the LEAST of the readings it is held
    against (``_upper_reading``), with room on both sides: the control in
    the nearest precision below may not pass a number on any seed it was
    read on. The worst leaf's change is hardly moved by the precision
    (Adam's first steps move every element by about the rate) and one seed
    of 30 read 16 times the others (PERF.md sections 2 and 6), so its limit stands
    between that reading and the unchanged state's 1."""
    rows = _readings()
    limits = harness.load_cell(CELL)["limits"]
    if number not in limits:
        pytest.skip(f"the cell does not hold {number} (PERF.md section 2)")
    limit = limits[number]
    assert sum(r["kind"] == "program" for r in rows) >= 9
    sound, upper, held = _upper_reading(number, rows)
    assert sound * 1.4 < limit, (sound, limit)
    assert limit * 1.4 < upper, (limit, held)


def test_the_upper_reading_is_the_least_that_qualifies():
    """``grad_gap_worst``: the control's smallest (0.0257, 3.08 times the
    sound largest) is the upper reading, not the bias fault's 0.141; a limit
    of 0.03 would let that control pass the number."""
    sound, upper, held = _upper_reading("grad_gap_worst", _readings())
    assert sound == pytest.approx(0.008364, rel=1e-3)
    assert upper == pytest.approx(0.025735, rel=1e-3)
    assert upper == held["control_fp8"] < held["fault_bias_out"]
    # a control under 3 times the sound reading is not the number's to catch
    rows = [{"kind": "program", "x": 1.0}, {"kind": "control_fp8", "x": 2.9},
            {"kind": "fault_half_batch", "x": 9.0},
            {"kind": "fault_bias_out", "x": 40.0}]
    assert _upper_reading("x", rows)[1:] == (40.0, {"fault_bias_out": 40.0})


REHEARSED = ("grad_gap_worst", "grad_gap_median", "delta_gap_worst",
             "delta_gap_median")


@pytest.mark.parametrize("number", REHEARSED)
def test_every_rehearsal_limit_lies_between_its_two_readings(number):
    """The limits of the CPU rehearsal (tiny sizes, ``calibrate.py
    --rehearse`` over 12 seeds with 4 of the control and of each fault; no
    device number) are held to their readings by the same rule."""
    rows = _readings(".rehearse.jsonl")
    limits = harness.load_cell(CELL, rehearse=True)["limits"]
    assert set(limits) == set(REHEARSED)
    sound, upper, held = _upper_reading(number, rows)
    assert sound * 1.4 < limits[number], (sound, limits[number])
    assert limits[number] * 1.4 < upper, (limits[number], held)
    for row in rows:
        ok, _ = compare.judge(row, limits)
        assert ok == (row["kind"] == "program"), row


def test_every_control_and_fault_fails_on_every_seed_it_was_read_on():
    limits = harness.load_cell(CELL)["limits"]
    kinds = {r["kind"] for r in _readings()}
    assert kinds >= {"program", "control_fp8", "fault_half_batch",
                     "fault_expert_out", "fault_bias_out"}
    for row in _readings():
        ok, _ = compare.judge(row, limits)
        assert ok == (row["kind"] == "program"), row
