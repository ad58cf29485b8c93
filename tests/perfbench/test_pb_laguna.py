"""What the Laguna-XS.2 configuration adds to the benchmark: its parameter
and operation counts against the published model and the issue's hand
count, the work functions of its roofline readers, the reading of a step's
device time by block, the control and the planted expert fault at the
rehearsal's size, and readers that find nothing in a program without the
records."""
import json
import os
import types

import numpy as np
import pytest

import _structure as st
from _pb import BENCH, PB
from perfbench import blocks, compare
from perfbench import run as harness

CELL = "laguna-xs2.train-fed-seq8k"


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(PB, "configs", "laguna-xs2.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def model():
    return harness.load_module("models", "laguna-xs2")


def _count(shapes, match=lambda n: True):
    return sum(int(np.prod(s)) for n, s in shapes.items() if match(n))


def test_uncut_configuration_is_the_published_33_4_b(cfg, model):
    """It is what bears out the reading of ``gating`` as a per-head gate: an
    element-wise one would make 34.1 B."""
    whole = model.uncut(cfg)
    assert (whole["num_hidden_layers"], whole["num_experts_held"],
            whole["vocab_size"]) == (40, 256, 100352)
    shapes = model.param_shapes(whole)
    assert abs(_count(shapes) - 33.4e9) / 33.4e9 < 0.005
    assert _count(shapes, lambda n: n.endswith("_gate_weight")
                  and "mlp" not in n and "moe" not in n) == 2048 * (
        10 * 48 + 30 * 64)
    # active a token: everything but the 248 experts a token does not choose
    expert = 3 * 2048 * 512
    assert abs(_count(shapes) - 39 * 248 * expert - 3.0e9) / 3.0e9 < 0.05


def test_cut_configuration_is_691_m_and_fits_the_issues_arithmetic(cfg,
                                                                    model):
    shapes = model.param_shapes(cfg)
    assert abs(_count(shapes) - 691e6) / 691e6 < 0.005
    assert _count(shapes, lambda n: "expert_" in n) == 4 * 32 * 3 * 2048 * 512
    assert shapes["embed_weight"] == shapes["lm_head_weight"] == (12544, 2048)
    assert shapes["layer1_q_weight"] == (64 * 128, 2048)
    assert shapes["layer4_q_weight"] == (48 * 128, 2048)
    assert "layer0_mlp_gate_weight" in shapes and \
        "layer0_moe_router_weight" not in shapes
    # the published widths stand in the file, the cut keys are the three
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts_held",
                              "vocab_size"]
    for key in ("hidden_size", "head_dim", "intermediate_size",
                "moe_intermediate_size", "num_experts",
                "num_experts_per_tok", "sliding_window"):
        assert key not in cfg["reduced"]
    assert (cfg["num_experts"], cfg["num_experts_per_tok"]) == (256, 8)


def test_flops_per_item_lands_on_the_hand_count(cfg, model):
    """ISSUE 27: 800.8 MFLOP a token forward (layer 0 260.1, sliding 105.4
    each, full sparse 173.0, head 51.4), 2.40 GFLOP trained."""
    got = model.flops_per_item(cfg)
    assert abs(got - 2.40e9) / 2.40e9 < 0.02

    def layers(n):
        return model.flops_per_item(dict(cfg, num_hidden_layers=n)) / 3

    head = model.flops_per_item(dict(cfg, num_hidden_layers=0)) / 3
    assert head == 2 * 2048 * 12544
    by_hand = [260.1e6, 105.4e6, 105.4e6, 105.4e6, 173.0e6]
    for n, want in enumerate(by_hand):
        assert abs(layers(n + 1) - layers(n) - want) / want < 0.01
    assert model.items_per_batch(cfg, {"per_chip_batch": 1, "chips": 1,
                                       "seq_len": 8192}) == 8192


def test_program_refuses_a_sequence_length_its_count_is_not_for(cfg, model):
    with pytest.raises(SystemExit):
        model.Program(cfg, {"seq_len": 4096, "chips": 1}, 1, [])


def test_roofline_work_functions_by_hand(cfg):
    with open(os.path.join(PB, "peaks.json")) as f:
        peaks = json.load(f)["TPU v5 lite"]
    window = harness.load_reader("window_attention_roofline")
    flops, nbytes = window.work(cfg, 1, 8192, 64)
    pairs = 8192 * 512 - 512 * 512 / 2
    assert flops == 2 * 2 * 64 * 128 * pairs
    assert nbytes == 2 * (2 * 64 + 2 * 8) * 8192 * 128
    least, bound = window.least_seconds(cfg, 1, 8192, peaks)
    assert bound == "compute"
    assert least == pytest.approx(3 * flops / 197e12)    # three sliding
    experts = harness.load_reader("expert_matmul_roofline")
    flops, nbytes = experts.work(cfg, 8192)
    assert flops == 2 * 8192 * 2048 * 512
    assert nbytes == 32 * 2048 * 512 * 2 + 8192 * (2048 + 512) * 2
    # at the even load the held experts' weights bound it, not the MXU
    assert experts.least_seconds(cfg, 8192, peaks)[1] == "memory"
    assert experts.least_seconds(cfg, 65536, peaks)[1] == "compute"


@pytest.mark.parametrize("op_name,want", [
    ("jit(step)/jvp(layer1)/MoEFFN/layer1_moe/experts/mul",
     ("layer1", "MoEFFN", "experts", "forward")),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/layer1"
     "/MoEFFN/layer1_moe/route/top_k",
     ("layer1", "MoEFFN", "route", "recompute")),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/layer0/"
     "GroupedQueryAttention/layer0_attn/while/body/dot_general",
     ("layer0", "GroupedQueryAttention", "while", "backward")),
    ("jit(step)/jvp(loss_head)/TokenCrossEntropy/loss/jit(take)/gather",
     ("loss_head", "TokenCrossEntropy", "", "forward")),
    ("jit(step)/jvp(layer2)/add", ("layer2", "", "", "forward")),
    ("jit(step)/optimizer_update/mul", None),
    ("jit(step)/jvp(Embedding/embed)/jit(_take)/gather", None),
    ("ragged-dot-none", None), (None, None)])
def test_op_name_is_read_by_block_op_part_and_stage(op_name, want):
    assert blocks.parse(op_name) == want


def test_grouped_matmul_kernels_are_found_by_the_compilers_name():
    assert blocks.grouped_matmul(
        "%ragged-dot-none.47 = bf16[65536,512]{1,0} custom-call(%a, %b)")
    assert not blocks.grouped_matmul(
        "%fusion.3 = bf16[8,8]{1,0} fusion(%ragged-dot-none.47)")


class _Trace:
    """Two steps of 1 s; one instruction in each kind of place."""

    devices = {"/device:TPU:0": [
        ("%fusion.1 = f32[] fusion()", 0.0, 0.4),
        ("%fusion.2 = f32[] fusion()", 0.4, 0.5),
        ("%ragged-dot-none.3 = f32[] custom-call()", 0.5, 0.7),
        ("%fusion.4 = f32[] fusion()", 0.7, 1.0),
        ("%fusion.1 = f32[] fusion()", 2.0, 2.4),
        ("%fusion.2 = f32[] fusion()", 2.4, 2.5),
        ("%ragged-dot-none.3 = f32[] custom-call()", 2.5, 2.7),
        ("%fusion.4 = f32[] fusion()", 2.7, 3.0)]}

    def busiest(self):
        return "/device:TPU:0"

    def steps(self, device=None):
        return [(0.0, 1.0), (2.0, 3.0)]

    def seconds_matching(self, match, device=None):
        hit = [e - s for n, s, e in self.devices["/device:TPU:0"] if match(n)]
        return sum(hit), len(hit)


def _ctx(cfg, counters=None, ops=None, trace=None):
    profiler = types.SimpleNamespace(
        spans=lambda lo, hi: [], counters=lambda: dict(counters or {}),
        op_scopes=lambda kind: ops if kind == "spmd-step" else {})
    return {"trace": trace or _Trace(), "profiler": profiler, "cfg": cfg,
            "traffic": {"per_chip_batch": 1, "seq_len": 8192},
            "model": types.SimpleNamespace(),
            "feed": {"batches": 2, "calls": [(0.0, 0.1)]}, "window_s": 3.0,
            "counters": {"step_programs": 0, "compiles": 0},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


def test_readers_on_a_recorded_shape_of_trace(cfg):
    ops = {"fusion.1": "jit(step)/jvp(layer1)/GroupedQueryAttention/"
                       "layer1_attn/gqa_flash_attention",
           "fusion.2": "jit(step)/transpose(jvp(jvp()))/checkpoint/layer1/"
                       "MoEFFN/layer1_moe/combine/gather",
           "ragged-dot-none.3": "ragged-dot-none",
           "fusion.4": "jit(step)/optimizer_update/mul"}
    held = 2 * 4 * 8192.0        # the even load: two steps, four layers
    ctx = _ctx(cfg, {"step.count": 6, "moe.assignments_held": int(held),
                     "moe.load_max": int(3 * held / 32), "moe.overflow": 0},
               ops)
    found, step_s, other = blocks.block_seconds(ctx)
    assert step_s == pytest.approx(2.0) and other == pytest.approx(0.6)
    assert found[("", "MoEFFN", "experts", "kernel")] == pytest.approx(0.4)
    assert harness.load_reader("attention_share").read(ctx) \
        == pytest.approx(40.0)
    assert harness.load_reader("moe_share").read(ctx) == pytest.approx(30.0)
    assert harness.load_reader("moe_load_max_over_mean").read(ctx) \
        == pytest.approx(3.0)
    # layer 1 is a sliding layer: 0.8 s of forward attention over two steps
    window = harness.load_reader("window_attention_roofline")
    least, _ = window.least_seconds(cfg, 1, 8192, ctx["peaks"])
    assert window.read(ctx) == pytest.approx(100 * least * 2 / 0.8)
    experts = harness.load_reader("expert_matmul_roofline")
    least, _ = experts.least_seconds(cfg, 8192, ctx["peaks"])
    assert experts.read(ctx) == pytest.approx(100 * least * 2 / 0.4)
    # none of them over 100% of anything here, all numbers
    table = blocks.table(ctx)
    assert table["in_blocks_s"] + table["outside_blocks_s"] \
        == pytest.approx(table["step_device_s"])


@pytest.mark.parametrize("metric", blocks.DECODER_METRICS)
def test_readers_find_nothing_in_a_program_without_the_records(cfg, metric):
    """The parent commit: no op map with blocks, no grouped matmul, no
    routed counters."""
    trace = _Trace()
    trace.devices = {d: [e for e in events if "ragged" not in e[0]]
                     for d, events in _Trace.devices.items()}
    ops = {"fusion.1": "jit(step)/jvp(FullyConnected/fc)/dot",
           "fusion.2": "jit(step)/optimizer_update/mul"}
    assert harness.load_reader(metric).read(_ctx(cfg, ops=ops,
                                                 trace=trace)) is None
    assert harness.load_reader(metric).read(_ctx(cfg, trace=trace)) is None


def test_control_and_planted_faults_are_not_correct(model):
    """At the rehearsal's size: the reference in fp8, and the reference with
    half the tokens left out, put in the program's place, each fail one of
    the cell's numbers; the reference itself passes. (One held expert's
    output left out moves a tiny model's numbers no more than a flipped
    routing choice does: the chip's readings under ``data/readings`` judge
    that fault at the published widths.)"""
    import jax
    cell = harness.load_cell(CELL, rehearse=True)
    cfg, tr = cell["cfg"], cell["traffic_params"]
    ref = model.reference(cfg, tr, 41, devices=jax.devices())
    for kwargs in ({"precision": "fp8"}, {"fault": "half_batch"}):
        bad = model.reference(cfg, tr, 41, devices=jax.devices(), **kwargs)
        ok, checks = compare.judge(compare.gaps(bad, ref)[0], cell["limits"])
        assert ok is False, (kwargs, checks)
    assert compare.judge(compare.gaps(ref, ref)[0], cell["limits"])[0]


def test_the_cell_reports_a_rate_and_the_metrics_with_no_list():
    """The cell reports an end-to-end rate, the per-layer metrics with no
    list that move what it reports, and those that list it: the five
    decoder readers among them (entries since PR 37)."""
    cell = harness.load_cell(CELL)
    assert [m["name"] for m in cell["end_to_end"]] == ["train_rate",
                                                       "setup_s"]
    reads = {m["name"] for m in cell["per_layer"]}
    assert reads == st.cell_metrics(BENCH, CELL)[1]
    assert st.DECODER_BASE | set(blocks.DECODER_METRICS) <= reads
    for name in blocks.DECODER_METRICS:
        assert hasattr(harness.load_reader(name), "read")


def test_batches_are_zipf_documents_with_shifted_labels(cfg, model):
    traffic = {"per_chip_batch": 1, "chips": 1, "seq_len": 8192,
               "zipf_exponent": 1.0, "distinct_batches": 2}
    a = model.make_batches(cfg, traffic, 2 ** 31 + 11)
    b = model.make_batches(cfg, traffic, 2 ** 31 + 11)
    assert len(a) == 2 and a[0][0].shape == (1, 8192)
    np.testing.assert_array_equal(a[1][0], b[1][0])
    np.testing.assert_array_equal(a[0][0][0, 1:], a[0][1][0, :-1])
    ids = np.concatenate([x.ravel() for x, _ in a])
    assert ids.max() < 12544 and ids.min() >= 0
    # Zipf over the held rows themselves: id 0 is every document's most
    # frequent token, at about 1 / H(12544) = 10% of the positions
    for x, _ in a:
        values, counts = np.unique(x, return_counts=True)
        assert 0.07 < counts.max() / x.size < 0.14
        assert values[counts.argmax()] == 0
    other = model.make_batches(cfg, traffic, 2 ** 31 + 12)
    assert not np.array_equal(a[0][0], other[0][0])


LIMITS = ("loss_gap", "grad_gap_worst", "grad_gap_median",
          "delta_gap_worst", "delta_gap_median")


@pytest.mark.parametrize("number", LIMITS)
def test_every_limit_lies_between_its_two_readings(number):
    """Each number the cell holds: its limit above the largest reading of
    the sound chip runs with room, and under the smallest reading of the
    fp8 control or of a planted fault (one of them has to fail it, not
    each); the cell holds all five."""
    readings = os.path.join(os.path.dirname(__file__), "data", "readings",
                            CELL + ".jsonl")
    rows = [json.loads(t) for t in open(readings) if '"kind"' in t]
    limit = harness.load_cell(CELL)["limits"][number]
    sound = max(r[number] for r in rows if r["kind"] == "program")
    caught_by = {kind: min(r[number] for r in rows if r["kind"] == kind)
                 for kind in {r["kind"] for r in rows} - {"program"}}
    assert len([r for r in rows if r["kind"] == "program"]) >= 9
    assert sound * 1.4 < limit, (sound, limit)
    assert any(low > limit * 1.4 for low in caught_by.values()), caught_by
