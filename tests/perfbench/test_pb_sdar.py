"""What the SDAR-30B-A3B configuration adds to the benchmark: the operation
count and the attention roofline's work by hand, its two readers on a
recorded shape of trace and in a program without the records, the control
and the planted faults at the rehearsal's size, the readings of the chip
runs against the committed limits, and what the cell reports."""
import json
import os
import types

import pytest

import _structure as st
from _pb import BENCH, PB
from perfbench import blocks, compare, diffusion
from perfbench import run as harness
from test_pb_lfm2 import _upper_reading     # the reading rule, PR 31's

CELL = "sdar-30b-a3b.train-fed-bd4-8k"


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(PB, "configs", "sdar-30b-a3b.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def model():
    return harness.load_module("models", "sdar-30b-a3b")


def test_flops_per_item_lands_on_the_hand_count(cfg, model):
    """ISSUE 33, forward multiply-adds a token at L = 8,192 and B = 4: two
    rows a token through the layers (23,855,104 a row: projections, router,
    the routed experts at the even load), 67,141,632 of attention, four
    layers, one row through the head: 498,302,976, so 2.990 GFLOP a token
    trained and 24.49 TFLOP a step."""
    got = model.flops_per_item(cfg)
    assert got == 3 * 2 * 498_302_976
    assert abs(got - 2.990e9) / 2.990e9 < 0.001

    def layers(n):
        return model.flops_per_item(dict(cfg, num_hidden_layers=n)) / 6

    assert layers(0) == 2048 * 18992                            # the head
    row = 18_874_368 + 262_144 + 8 * 16 / 128 * 4_718_592
    assert row == 23_855_104
    assert layers(1) - layers(0) == 2 * row + 2 * 32 * 128 * (8192 + 4) \
        == 114_851_840
    assert layers(4) - layers(0) == 459_407_360
    traffic = {"per_chip_batch": 1, "chips": 1, "seq_len": 8192}
    # tokens of data, not rows of the step
    assert model.items_per_batch(cfg, traffic) == 8192
    assert got * 8192 == pytest.approx(24.49e12, rel=1e-3)


def test_program_refuses_a_sequence_length_its_count_is_not_for(cfg, model):
    with pytest.raises(SystemExit, match="flops_seq_len"):
        model.Program(cfg, {"seq_len": 4096, "chips": 1}, 1, [])


def test_attention_work_and_bound_by_hand(cfg):
    with open(os.path.join(PB, "peaks.json")) as f:
        peaks = json.load(f)["TPU v5 lite"]
    reader = harness.load_reader("block_diffusion_attention_roofline")
    flops, nbytes = reader.work(cfg, 1, 8192)
    assert flops == 2 * 2 * 32 * 128 * 8192 * (8192 + 4)
    assert flops == pytest.approx(1.10e12, rel=1e-3)
    # q and o over 32 heads, k and v over 4, 16,384 rows of 128, bf16
    assert nbytes == 2 * (32 + 32 + 4 + 4) * 16384 * 128 == 301_989_888
    least, bound = reader.least_seconds(cfg, 1, 8192, peaks)
    assert bound == "compute"
    assert least == pytest.approx(4 * flops / 197e12)          # four layers
    assert least / 4 == pytest.approx(5.584e-3, rel=1e-3)
    # twice a causal pass of L, whatever tiles a kernel visits
    assert reader.work(cfg, 2, 8192)[0] == 2 * flops


class _Trace:
    """Two steps of 1 s; attention forward, recomputed and backward, the
    loss, the optimizer."""

    devices = {"/device:TPU:0": [
        ("%fusion.1 = f32[] fusion()", 0.0, 0.1),
        ("%fusion.2 = f32[] fusion()", 0.1, 0.2),
        ("%fusion.3 = f32[] fusion()", 0.2, 0.5),
        ("%fusion.4 = f32[] fusion()", 0.5, 0.6),
        ("%fusion.5 = f32[] fusion()", 0.6, 1.0),
        ("%fusion.1 = f32[] fusion()", 2.0, 2.1),
        ("%fusion.2 = f32[] fusion()", 2.1, 2.2),
        ("%fusion.3 = f32[] fusion()", 2.2, 2.5),
        ("%fusion.4 = f32[] fusion()", 2.5, 2.6),
        ("%fusion.5 = f32[] fusion()", 2.6, 3.0)]}

    def busiest(self):
        return "/device:TPU:0"

    def steps(self, device=None):
        return [(0.0, 1.0), (2.0, 3.0)]


def _ctx(cfg, model, ops=None, counters=None):
    profiler = types.SimpleNamespace(
        spans=lambda lo, hi: [], counters=lambda: dict(counters or {}),
        op_scopes=lambda kind: ops if kind == "spmd-step" else {})
    return {"trace": _Trace(), "profiler": profiler, "cfg": cfg,
            "traffic": {"per_chip_batch": 1, "seq_len": 8192, "chips": 1},
            "model": model,
            "feed": {"batches": 2, "calls": [(0.0, 0.1)]}, "window_s": 3.0,
            "counters": {"step_programs": 0, "compiles": 0},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


def test_readers_on_a_recorded_shape_of_trace(cfg, model):
    pre = "jit(step)/jvp(layer0)/GroupedQueryAttention/layer0_attn/"
    back = "jit(step)/transpose(jvp(jvp()))/checkpoint/layer0/" \
           "GroupedQueryAttention/layer0_attn/"
    ops = {"fusion.1": pre + "block_diffusion/gqa_block_diffusion_attention",
           "fusion.2": pre + "transpose",
           "fusion.3": back + "block_diffusion/while/body/dot_general",
           "fusion.4": "jit(step)/jvp(loss_head)/TokenCrossEntropy/loss/"
                       "reduce_sum",
           "fusion.5": "jit(step)/optimizer_update/mul"}
    ctx = _ctx(cfg, model, ops, {"loss.weighted_tokens": 9011,
                                 "step.count": 5})
    assert blocks.parse(ops["fusion.1"]) == (
        "layer0", "GroupedQueryAttention", "block_diffusion", "forward")
    # forward, head layout and backward: half of each 1 s step
    assert harness.load_reader("attention_share").read(ctx) \
        == pytest.approx(50.0)
    # the first forward alone, kernel and layout: 0.2 s a step
    reader = harness.load_reader("block_diffusion_attention_roofline")
    least, _ = reader.least_seconds(cfg, 1, 8192, ctx["peaks"])
    assert reader.read(ctx) == pytest.approx(100 * least * 2 / 0.4)
    assert reader.read(ctx) < 100
    # 9,011 of the window's 2 x 8,192 tokens carried loss
    assert harness.load_reader("loss_weighted_share").read(ctx) \
        == pytest.approx(100 * 9011 / 16384)
    # a configuration with another objective has nothing to read
    other = dict(cfg, objective="next_token")
    assert reader.read(_ctx(other, model, ops)) is None


@pytest.mark.parametrize("metric", diffusion.DIFFUSION_METRICS)
def test_readers_find_nothing_in_a_program_without_the_records(cfg, model,
                                                               metric):
    """The parent commit: no block in the op map, no op map at all, no
    counter; a rehearsal's trace with no device plane; a model file with no
    ``items_per_batch``."""
    ops = {"fusion.1": "jit(step)/jvp(FullyConnected/fc)/dot",
           "fusion.2": "jit(step)/optimizer_update/mul"}
    read = harness.load_reader(metric).read
    assert read(_ctx(cfg, model, ops)) is None
    assert read(_ctx(cfg, model)) is None
    bare = _ctx(cfg, model, ops)
    bare["trace"] = types.SimpleNamespace(
        busiest=lambda: None, steps=lambda device=None: [], devices={})
    assert read(bare) is None
    assert read(_ctx(cfg, types.SimpleNamespace(), ops)) is None
    gone = _ctx(cfg, model, ops)
    gone["profiler"] = None
    assert read(gone) is None


def test_the_diffusion_table_adds_its_two_readers_to_the_decoders():
    assert not set(diffusion.DIFFUSION_METRICS) & set(blocks.DECODER_METRICS)
    listed = {m["name"]: m for m in BENCH["per_layer"]}
    # the roofline an entry since PR 37, read in this cell alone; the share
    # of tokens that carried loss a reader only: it reads the traffic's
    # noise schedule, which no change to the program moves
    assert listed["block_diffusion_attention_roofline"]["workloads"] == [CELL]
    assert "loss_weighted_share" not in listed
    for name in diffusion.DIFFUSION_METRICS:
        assert hasattr(harness.load_reader(name), "read")


def test_the_cell_reports_a_rate_and_the_metrics_with_no_list():
    cell = harness.load_cell(CELL)
    assert [m["name"] for m in cell["end_to_end"]] == ["train_rate",
                                                       "setup_s"]
    reads = {m["name"] for m in cell["per_layer"]}
    assert reads == st.cell_metrics(BENCH, CELL)[1]
    assert st.DECODER_BASE | set(blocks.DECODER_METRICS) \
        - {"window_attention_roofline"} \
        | {"block_diffusion_attention_roofline"} <= reads
    assert "loss_weighted_share" not in reads
    assert cell["chips"] == 1
    traffic = cell["traffic_params"]
    assert (traffic["per_chip_batch"], traffic["seq_len"],
            traffic["block_length"], traffic["distinct_batches"],
            traffic["check_steps"], traffic["feed"], traffic["driver"]) \
        == (1, 8192, 4, 4, 3, "host", "train_fit")
    laguna = harness.load_cell("laguna-xs2.train-fed-seq8k")["cfg"]
    assert cell["cfg"]["optimizer"] == laguna["optimizer"]
    # found by name: a configuration or cell appended after them is fine
    entry, = [c for c in BENCH["configs"] if c["name"] == "sdar-30b-a3b"]
    assert entry["reduced"] == [
        "num_hidden_layers", "num_experts_held", "vocab_size"]
    found, = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert found["config"] == entry["name"] and found["chips"] == 1


def test_every_catalog_number_stands_under_its_key(cfg):
    """The published ``config.json`` as the catalog of public architectures
    holds it: every key at its published value but the three in
    ``reduced``, whose published values stand under ``published``."""
    catalog = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 32768, "max_window_layers": 48,
        "mlp_only_layers": [], "model_type": "sdar_moe",
        "moe_intermediate_size": 768, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 48,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936}
    differs = sorted(k for k, v in catalog.items() if cfg[k] != v)
    assert differs == ["num_hidden_layers", "vocab_size"]
    assert set(differs) < set(cfg["reduced"])
    assert {k: cfg["published"][k] for k in cfg["reduced"]} == {
        "num_hidden_layers": 48, "num_experts_held": 128,
        "vocab_size": 151936}
    # the floors: four layers, eight experts or more, an eighth of the rows
    assert cfg["num_hidden_layers"] == 4 and cfg["num_experts_held"] == 16
    assert cfg["vocab_size"] * 8 == 151936


def test_control_and_planted_faults_are_not_correct(model):
    """At the rehearsal's size: the reference in fp8, with the mask's leak
    (a noisy block sees its own clean tokens: 4 keys more among 64 at most,
    which shows here and not at 8,192), with the mask blind (a noisy block
    sees no clean token), and with half of each document's blocks given the
    weight 0, put in the program's place, each fail one of the cell's
    numbers; the reference itself passes."""
    import jax
    cell = harness.load_cell(CELL, rehearse=True)
    cfg, tr = cell["cfg"], cell["traffic_params"]
    ref = model.reference(cfg, tr, 41, devices=jax.devices())
    for kwargs in ({"precision": "fp8"}, {"fault": "leak"},
                   {"fault": "blind"}, {"fault": "half_batch"}):
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


@pytest.mark.parametrize("number", LIMITS)
def test_every_limit_lies_between_its_two_readings(number):
    """Each number the cell holds: its limit above the largest reading of
    the sound chip runs and under the LEAST of the readings it is held
    against (``_upper_reading``), with room on both sides: the control in
    the nearest precision below may not pass a number on any seed it was
    read on. All five are held: a number with an upper reading has a limit
    (``loss_gap``: the fp8 control reads 2.4 times the sound largest and is
    not its to catch, ``blind`` 54 times and ``half`` 247 times are)."""
    rows = _readings()
    limit = harness.load_cell(CELL)["limits"][number]
    assert sum(r["kind"] == "program" for r in rows) >= 8
    sound, upper, held = _upper_reading(number, rows)
    assert sound * 1.4 < limit, (sound, limit)
    assert limit * 1.4 < upper, (limit, held)


def test_the_upper_reading_is_the_least_that_qualifies():
    """The reading rule on made-up rows: a control under 3 times the sound
    reading is not the number's to catch, nor a fault under 10 times; of
    those that qualify the least is the upper reading; the unchanged
    state's 1 stands for the parameters' change alone."""
    rows = [{"kind": "program", "x": 1.0, "delta_gap_x": 0.01},
            {"kind": "program", "x": 0.4, "delta_gap_x": 0.02},
            {"kind": "control_fp8", "x": 2.9, "delta_gap_x": 0.03},
            {"kind": "fault_leak", "x": 9.0, "delta_gap_x": 0.05},
            {"kind": "fault_half_batch", "x": 40.0, "delta_gap_x": 0.1},
            {"kind": "fault_half_batch", "x": 55.0, "delta_gap_x": 0.3}]
    assert _upper_reading("x", rows) == (1.0, 40.0,
                                         {"fault_half_batch": 40.0})
    rows[2]["x"] = 3.0
    assert _upper_reading("x", rows)[1:] == (
        3.0, {"control_fp8": 3.0, "fault_half_batch": 40.0})
    assert _upper_reading("delta_gap_x", rows) == (
        0.02, 1.0, {"state_unchanged": 1.0})
    with pytest.raises(AssertionError):
        _upper_reading("x", rows[:2] + [{"kind": "fault_leak", "x": 9.0}])


REHEARSED = ("loss_gap", "grad_gap_median", "delta_gap_worst",
             "delta_gap_median")


@pytest.mark.parametrize("number", REHEARSED)
def test_every_rehearsal_limit_lies_between_its_two_readings(number):
    """The limits of the CPU rehearsal (tiny sizes, ``calibrate.py
    --rehearse`` over 24 seeds, the control and two faults on 6; no device
    number) are held to their readings by the same rule, and every recorded
    line is judged as it should be. ``grad_gap_worst`` has no upper reading
    there (sound 0.063, the control 0.038) and is the one number not
    held."""
    rows = _readings(".rehearse.jsonl")
    limits = harness.load_cell(CELL, rehearse=True)["limits"]
    assert set(limits) == set(REHEARSED)
    assert sum(r["kind"] == "program" for r in rows) >= 24
    with pytest.raises(AssertionError):
        _upper_reading("grad_gap_worst", rows)
    sound, upper, held = _upper_reading(number, rows)
    assert sound * 1.4 < limits[number], (number, sound, limits[number])
    assert limits[number] * 1.4 < upper, (number, limits[number], held)
    for row in rows:
        ok, _ = compare.judge(row, limits)
        assert ok == (row["kind"] == "program"), row


def test_every_control_and_fault_fails_on_every_seed_it_was_read_on():
    limits = harness.load_cell(CELL)["limits"]
    kinds = {r["kind"] for r in _readings()}
    assert kinds == {"program", "control_fp8", "fault_blind",
                     "fault_half_batch"}
    for row in _readings():
        ok, _ = compare.judge(row, limits)
        assert ok == (row["kind"] == "program"), row


def test_the_leak_sets_no_limit_of_the_comparison_at_this_size():
    """ISSUE 33's first mask fault, the offset block-causal part made
    ``>=``: 4 keys more a noisy row among up to 8,192, under weights drawn
    N(0, 0.02). On the chip it reads inside the sound runs' own range on
    every number and every seed it was read on (PERF.md sections 2 and 6),
    so no limit of the cell's comparison can stand under it: ``correct``
    holds the mask's whole tiles (the fault ``blind``) and not a cut that is
    wrong inside the walk's diagonal tiles. That is held by the CPU suite
    (``tests/test_decoder_diffusion.py``) and, on the chip, by
    ``diffusion.py``'s ``mask_probe`` (below), which the next change to
    those tiles has to run."""
    limits = harness.load_cell(CELL)["limits"]
    sound, leak = _readings(), _readings(".leak.jsonl")
    assert len(leak) >= 3 and {r["kind"] for r in leak} == {"fault_leak"}
    for number in limits:
        most = max(r[number] for r in sound if r["kind"] == "program")
        assert max(r[number] for r in leak) < 1.5 * most, number


# -- the probe that sees the cut ----------------------------------------------

def test_a_rows_gap_by_hand():
    import jax.numpy as jnp
    want = jnp.ones((2, 8, 4))                      # every row's norm 2
    got = want.at[1, 5, 0].add(1.0).at[0, 2].add(0.5)
    # tile 0 holds row 2 of head 0 (off by 0.5 in 4 dims: norm 1), tile 1
    # row 5 of head 1 (off by 1 in one)
    assert [float(g) for g in diffusion._row_gaps(got, want, 4)] \
        == [0.5, 0.5]
    assert [float(g) for g in diffusion._row_gaps(want, want, 2)] == [0] * 4


@pytest.fixture(scope="module")
def probe():
    """The probe at the rehearsal's heads over 128 tokens in tiles of 32:
    four tiles a half, the kernel through the interpreter."""
    cfg = harness.load_cell(CELL, rehearse=True)["cfg"]
    return cfg, diffusion.mask_probe(cfg, 128, 7, tile=32, force_pallas=True)


def test_the_mask_probe_holds_the_kernel_and_sees_the_leak(probe):
    cfg, found = probe
    assert found["held"] and found["rows"] == 256 and found["tile"] == 32
    limit = diffusion.ROW_GAP_LIMIT
    assert set(found["sound"]) == {"out", "dq", "dk", "dv"}
    assert max(found["sound"].values()) * 2 < limit
    # against the reference with the leak every tile the fault touches reads
    # over the limit, on all four tensors; the clean queries read as before
    assert min(found["leak"][n] for n in found["sound"]) > 4 * limit
    assert found["leak"]["clean_queries"] * 2 < limit


def test_the_mask_probe_fails_a_program_whose_diagonal_tiles_leak(
        probe, monkeypatch):
    """The same fault in the program's own mask, where the kernel and the
    backward read it: the tiles the mask does not cut run unmasked, so only
    the walk's diagonal tiles change, which is what no number of the cell's
    comparison sees at 8,192 tokens."""
    from mxnet_tpu.ops.pallas import attention
    real = attention._band_mask

    def leaky(qpos, kpos, causal, window, block_length=0, half=0):
        mask = real(qpos, kpos, causal, window, block_length, half)
        if not block_length:
            return mask
        own = (qpos < half) & (kpos >= half) & (
            qpos // block_length == (kpos - half) // block_length)
        return mask | own

    monkeypatch.setattr(attention, "_band_mask", leaky)
    cfg, sound = probe
    found = diffusion.mask_probe(cfg, 128, 7, tile=32, force_pallas=True)
    assert not found["held"]
    assert max(found["sound"].values()) > 4 * diffusion.ROW_GAP_LIMIT
    # it now agrees with the leaking reference instead
    assert max(found["leak"][n] for n in sound["sound"]) \
        < diffusion.ROW_GAP_LIMIT / 2


def test_the_probes_limit_lies_between_its_two_readings():
    """On the chip at 32 heads over 4 of 128 and 16,384 rows (four seeds at
    the probe's sharpness): the compiled kernel's worst row under the limit
    and the leak's least tile over it, with the room the cell's limits
    have. At sharpness 1, where a row's softmax is a mean over thousands of
    keys as it is under the cell's N(0, 0.02) weights, the leak's deepest
    tiles read like the kernel's own rounding: what the cell's comparison
    is up against."""
    with open(os.path.join(os.path.dirname(__file__), "data", "readings",
                           "sdar-30b-a3b.mask_probe.jsonl")) as f:
        rows = [json.loads(t) for t in f]
    assert all(r["device"] == "TPU v5 lite" and r["rows"] == 16384
               and r["heads"] == [32, 4, 128] and r["tile"] == 512
               for r in rows)
    at = [r for r in rows if r["sharp"] == diffusion.SHARP]
    assert len({r["seed"] for r in at}) >= 4 and all(r["held"] for r in at)
    names = ("out", "dq", "dk", "dv")
    sound = max(max(r["sound"].values()) for r in at)
    leak = min(min(r["leak"][n] for n in names) for r in at)
    limit = diffusion.ROW_GAP_LIMIT
    assert at[0]["limit"] == limit
    assert sound * 1.4 < limit and limit * 1.4 < leak, (sound, leak)
    assert max(r["leak"]["clean_queries"] for r in at) * 1.4 < limit
    flat = [r for r in rows if r["sharp"] == 1.0]
    assert flat and not any(r["held"] for r in flat)
    assert all(min(r["leak"][n] for n in names)
               < max(r["sound"].values()) for r in flat)
