"""Ragged serving: pad-waste accounting, length-masked compute,
symbolic-dim programs, sequence packing (mxnet_tpu/serving/ragged.py,
mxnet_tpu/compiler/symbolic.py, the masked flash-attention kernel).

The contracts under test, per ROADMAP item 4:

- the pad tax is a tracked number before anything optimizes it:
  ``serving.stats()[ep]["pad_waste"]`` and the decode batcher's
  ``stats()["pad_waste"]`` count real vs padded rows x tokens;
- every optimization rung is value-preserving — packed scatter is
  BITWISE against running each member alone, masked kernels are
  allclose against dense slices, the masked decode step is bitwise
  against the unmasked one including join/leave mid-stream;
- ``MXTPU_RAGGED=0`` (or ``ragged=False``) restores today's dense
  padded path exactly — the backend sees the same feeds as before;
- a symbolic-dim backend serves a mixed-size burst through ONE warmed
  signature with zero retraces under ``MXTPU_RETRACE_STRICT=1``, and
  the warm-up matrix collapse is reported (``warmup_skipped_covered``).

Every timing-sensitive path runs on the injectable fake clock — zero
real sleeps, workers=0 deterministic servers throughout.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import resilience, serving
from mxnet_tpu.compiler import GraphIR, batch_signature
from mxnet_tpu.compiler.symbolic import (SymbolicBatchProgram,
                                         symbolic_transform_sig)
from mxnet_tpu.ops.pallas.attention import flash_attention
from mxnet_tpu.resilience import faults
from mxnet_tpu.resilience.retry import set_default_policy
from mxnet_tpu.serving import (CallableBackend, CallableStepBackend,
                               Deadline, InferenceServer, InflightBatcher,
                               PadWasteTracker, Request, RequestTooLarge,
                               SequencePacker, SymbolicJitBackend,
                               suggest_buckets)
from mxnet_tpu.serving.ragged import dispatch_waste


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


@pytest.fixture(autouse=True)
def _clean_world():
    faults.disarm()
    resilience.reset_stats()
    set_default_policy(None)
    yield
    faults.disarm()
    resilience.reset_stats()
    set_default_policy(None)
    for srv in serving.endpoints().values():
        srv.close()


def _req(clock, inputs, **kw):
    return Request(inputs, Deadline(None, clock), **kw)


def _seq_req(clock, length, dim=2, fill=1.0):
    """One single-row variable-length request: (1, length, dim)."""
    arr = (np.arange(length * dim, dtype=np.float32).reshape(
        1, length, dim) + fill)
    return _req(clock, {"data": arr})


# ---------------------------------------------------------------------------
# pad-waste accounting units
# ---------------------------------------------------------------------------

def test_pad_waste_tracker_counters_and_ratio():
    t = PadWasteTracker()
    snap = t.snapshot()
    assert snap["dispatches"] == 0
    assert snap["ratio"] == 1.0                  # no traffic = no waste
    t.record(3, 4)                               # rows-only accounting
    t.record(1, 4, real_tokens=5, padded_tokens=64)
    snap = t.snapshot()
    assert snap["dispatches"] == 2
    assert snap["real_rows"] == 4 and snap["padded_rows"] == 8
    assert snap["real_tokens"] == 8 and snap["padded_tokens"] == 68
    assert snap["ratio"] == round(68 / 8, 4)
    assert snap["rows_ratio"] == 2.0
    assert snap["last"]["real_tokens"] == 5      # per-dispatch debugging


def test_dispatch_waste_three_evidence_tiers():
    # rows only: tokens == rows
    fed = {"data": np.zeros((8, 3), np.float32)}
    assert dispatch_waste(fed, 5) == (5, 8, 5, 8)
    # declared lengths input + pack axis: exact real tokens, dense plane
    fed = {"data": np.zeros((4, 16, 3), np.float32),
           "lengths": np.array([3, 7, 2, 9], np.int32)}
    assert dispatch_waste(fed, 3, pack_axis=1, lengths_name="lengths") \
        == (3, 4, 12, 64)                        # 3+7+2 real, 4x16 padded
    # segment ids: exact both ways, regardless of other hints
    seg = np.zeros((2, 8), np.int32)
    seg[0, :5] = 1
    seg[1, :3] = 1
    seg[1, 3:7] = 2
    fed = {"data": np.zeros((2, 8, 3), np.float32), "segment_ids": seg}
    assert dispatch_waste(fed, 2) == (2, 2, 12, 16)


# ---------------------------------------------------------------------------
# sequence packer units: plan, builder, merge/scatter
# ---------------------------------------------------------------------------

def test_packer_first_fit_plan_is_deterministic():
    clock = FakeClock()
    p = SequencePacker(pack_axis=1, bucket=8)
    batch = [_seq_req(clock, n) for n in (5, 4, 3, 2)]
    plan = p.plan(batch)
    # first-fit: 5 opens row 0, 4 opens row 1, 3 lands after the 5,
    # 2 lands after the 4 — two rows total, zero token waste beyond pad
    assert plan.spans == [(0, 0, 5), (1, 0, 4), (0, 5, 8), (1, 4, 6)]
    assert plan.rows == 2
    assert plan.real_tokens == 14
    assert p.plan(batch).spans == plan.spans     # same order, same plan
    with pytest.raises(mx.MXNetError):
        p.plan([_seq_req(clock, 9)])             # exceeds the bucket


def test_packer_max_segments_caps_row_sharing():
    clock = FakeClock()
    p = SequencePacker(pack_axis=1, bucket=8, max_segments=1)
    plan = p.plan([_seq_req(clock, 2), _seq_req(clock, 2)])
    assert plan.rows == 2                        # no sharing allowed
    assert plan.spans == [(0, 0, 2), (1, 0, 2)]


def test_packer_builder_mirrors_plan_and_bounds_rows():
    clock = FakeClock()
    p = SequencePacker(pack_axis=1, bucket=8)
    b = p.builder(max_rows=1)
    assert b.try_add(_seq_req(clock, 5))
    assert b.try_add(_seq_req(clock, 3))         # shares row 0
    assert not b.try_add(_seq_req(clock, 2))     # would open row 1
    assert not b.try_add(_seq_req(clock, 9))     # never fits any row


def test_packer_merge_scatter_bitwise_roundtrip():
    clock = FakeClock()
    p = SequencePacker(pack_axis=1, bucket=8)
    batch = [_seq_req(clock, n, fill=float(i))
             for i, n in enumerate((5, 4, 3))]
    merged, plan = p.merge(batch)
    assert merged["data"].shape == (2, 8, 2)
    seg = merged["segment_ids"]
    assert seg.dtype == np.int32
    # members are numbered per row in pack order; 0 marks pad
    assert list(seg[0]) == [1, 1, 1, 1, 1, 2, 2, 2]
    assert list(seg[1]) == [1, 1, 1, 1, 0, 0, 0, 0]
    # an identity backend: scatter must hand back each member's exact
    # tokens (leading axis restored to the member's own 1)
    outs = [merged["data"] * 1.0, np.float32(7.0)]
    per_req = p.scatter(outs, plan)
    for req, got in zip(batch, per_req):
        np.testing.assert_array_equal(got[0], req.inputs["data"])
        assert got[1] == np.float32(7.0)         # scalars replicate


def test_packer_merge_rejects_length_disagreement():
    clock = FakeClock()
    p = SequencePacker(pack_axis=1, bucket=8)
    bad = _req(clock, {"data": np.zeros((1, 4, 2), np.float32),
                       "aux": np.zeros((1, 3, 2), np.float32)})
    with pytest.raises(mx.MXNetError):
        p.merge([bad])


def test_packer_request_signature_wildcards_pack_axis():
    clock = FakeClock()
    p = SequencePacker(pack_axis=1, bucket=8)
    a = p.request_signature(_seq_req(clock, 3))
    b = p.request_signature(_seq_req(clock, 7))
    assert a == b                                # lengths merge
    c = p.request_signature(_req(clock, {"data": np.zeros((1, 3, 5),
                                                          np.float32)}))
    assert a != c                                # other dims still split


# ---------------------------------------------------------------------------
# symbolic-dim programs: signatures, GraphIR declarations, the export
# ---------------------------------------------------------------------------

def test_symbolic_batch_signature_collapses_row_counts():
    a = {"data": np.zeros((4, 3), np.float32)}
    b = {"data": np.zeros((7, 3), np.float32)}
    assert batch_signature(a) != batch_signature(b)
    assert batch_signature(a, symbolic_rows=8) == \
        batch_signature(b, symbolic_rows=8)
    assert "B<=8" in batch_signature(a, symbolic_rows=8)
    # the bound is part of the identity, as is symbolic-vs-concrete
    assert batch_signature(a, symbolic_rows=8) != \
        batch_signature(a, symbolic_rows=16)
    assert batch_signature(a, symbolic_rows=8) != batch_signature(a)


def test_graphir_symbolic_dims_declaration_and_signature():
    data = mx.sym.var("data")
    out = mx.sym.exp(data, name="e")
    ir = GraphIR.from_symbol(out)
    assert ir.symbolic_signature() == ""
    ir.mark_symbolic_dim("data", axis=0, bound=16)
    assert ir.symbolic_signature() == "symdims=data@0<=16"
    assert ir.annotations["symbolic_dims"] == {"data": (0, 16)}
    with pytest.raises(ValueError):
        ir.mark_symbolic_dim("nonesuch")
    # the serving-level fragment speaks the same grammar
    assert symbolic_transform_sig(["data"], 16) == "symdims=data@0<=16"


def test_symbolic_batch_program_one_compile_any_rows():
    prog = SymbolicBatchProgram(
        lambda arrays: [arrays["data"] * 2.0 + arrays["bias"]],
        {"data": (3,), "bias": (3,)}, max_rows=8)
    for rows in (1, 3, 8):
        feed = {"data": np.full((rows, 3), 2.0, np.float32),
                "bias": np.ones((rows, 3), np.float32)}
        (out,) = prog(feed)
        np.testing.assert_array_equal(out, np.full((rows, 3), 5.0))
    assert prog.compiles == 1                    # ONE program, any rows
    assert prog.transform_sig == "symdims=bias@0<=8,data@0<=8"


def test_symbolic_batch_program_refuses_a_concrete_batch_fn():
    """A function that pins the batch dim cannot serve a range; it
    fails at construction instead of degrading to per-shape jit."""
    with pytest.raises(ValueError):
        SymbolicBatchProgram(
            lambda arrays: [arrays["data"].reshape(8, 3) * 2.0],
            {"data": (3,)}, max_rows=8)


# ---------------------------------------------------------------------------
# bucket mining: suggest_buckets
# ---------------------------------------------------------------------------

def test_suggest_buckets_mines_histogram():
    hist = {"1r|(3,)f32": 60, "2r|(3,)f32": 30, "3r|(3,)f32": 8,
            "13r|(3,)f32": 2, "__other__": 5}
    out = suggest_buckets(hist)
    assert out["buckets"][-1] == 13              # rejected demand fits
    assert 1 in out["buckets"] or 2 in out["buckets"]
    assert out["coverage"] == 1.0
    assert "buckets=" in out["rules"]
    assert out["rows_histogram"][13] == 2
    assert len(suggest_buckets(hist, max_buckets=2)["buckets"]) <= 2


def test_suggest_buckets_empty_histogram():
    out = suggest_buckets({})
    assert out["buckets"] == [] and out["coverage"] == 0.0
    assert out["rules"].startswith("#")


# ---------------------------------------------------------------------------
# serving: length-masked forward, packing, symbolic warm-up, kill switch
# ---------------------------------------------------------------------------

def _masked_echo(arrays):
    """A mask-consuming forward: pad rows are mask-DEAD (zeroed), real
    rows bitwise-identical to the dense fn. If the mask is missing the
    dense result comes back — the kill-switch test tells them apart by
    feeding pad rows garbage."""
    out = np.ascontiguousarray(arrays["data"], np.float32) * 2.0
    if "mask" in arrays:
        out = out * arrays["mask"][:, None]
    return [out]


def test_masked_forward_matches_dense_and_records_waste(monkeypatch):
    monkeypatch.setenv("MXTPU_RETRACE_STRICT", "1")
    clock = FakeClock()
    srv = InferenceServer(
        CallableBackend(_masked_echo, input_specs={"data": (3,)},
                        accepts_mask=True),
        name="masked", workers=0, clock=clock, max_batch=4)
    srv.warm_up()
    data = np.arange(9, dtype=np.float32).reshape(3, 3)
    req = srv.submit({"data": data})
    srv.run_pending()
    np.testing.assert_array_equal(srv.result(req), [data * 2.0])
    st = srv.stats()
    assert st["ragged"]["enabled"] and not st["ragged"]["packing"]
    pw = st["pad_waste"]
    assert pw["dispatches"] == 1
    assert (pw["real_rows"], pw["padded_rows"]) == (3, 4)
    assert pw["rows_ratio"] == round(4 / 3, 4)
    # the mask input is part of the warmed signature set: zero retraces
    assert st["batching"]["unwarmed_dispatch_signatures"] == 0


def test_kill_switch_restores_dense_feed_bitwise(monkeypatch):
    monkeypatch.setenv("MXTPU_RAGGED", "0")
    clock = FakeClock()
    seen = []

    def spy(arrays):
        seen.append(sorted(arrays))
        return _masked_echo(arrays)

    srv = InferenceServer(
        CallableBackend(spy, input_specs={"data": (3,)},
                        accepts_mask=True, pack_axis=1,
                        accepts_segment_ids=True),
        name="killed", workers=0, clock=clock, max_batch=4)
    srv.warm_up()
    st = srv.stats()["ragged"]
    assert not st["enabled"] and not st["packing"] and not st["symbolic"]
    data = np.ones((3, 3), np.float32)
    req = srv.submit({"data": data})
    srv.run_pending()
    np.testing.assert_array_equal(srv.result(req), [data * 2.0])
    # the dense path: no mask, no segment plane — today's exact feed
    assert all(names == ["data"] for names in seen)
    assert srv.stats()["packed_dispatches"] == 0


def _segment_sum(arrays):
    """A packed-aware toy forward: per-token transform (so scatter is
    bitwise) that also READS segment_ids to prove the plane arrives."""
    data = np.asarray(arrays["data"], np.float32)
    seg = np.asarray(arrays["segment_ids"])
    assert seg.shape == data.shape[:2]
    return [data * 3.0 + 1.0]


def test_packed_serving_bitwise_vs_unpacked(monkeypatch):
    monkeypatch.setenv("MXTPU_RETRACE_STRICT", "1")
    clock = FakeClock()
    srv = InferenceServer(
        CallableBackend(_segment_sum, input_specs={"data": (8, 2)},
                        pack_axis=1, accepts_segment_ids=True),
        name="packed", workers=0, clock=clock, max_batch=4)
    srv.warm_up()
    lengths = [5, 4, 3, 2, 6, 1]
    arrays = [(np.arange(n * 2, dtype=np.float32).reshape(1, n, 2)
               + 10.0 * i) for i, n in enumerate(lengths)]
    reqs = [srv.submit({"data": a}) for a in arrays]
    srv.run_pending()
    for arr, req in zip(arrays, reqs):
        got = srv.result(req)
        # bitwise against running the member ALONE through the same fn
        np.testing.assert_array_equal(got[0], arr * 3.0 + 1.0)
    st = srv.stats()
    assert st["ragged"]["packing"]
    assert st["ragged"]["pack_bucket"] == 8
    assert st["packed_dispatches"] >= 1
    assert st["batching"]["unwarmed_dispatch_signatures"] == 0
    pw = st["pad_waste"]
    assert pw["real_tokens"] == sum(lengths)     # segment-exact tokens
    assert pw["padded_tokens"] >= pw["real_tokens"]
    # packing beats dense padding: dense would burn 6 rows x 8 tokens
    assert pw["padded_tokens"] < len(lengths) * 8


def test_packed_oversize_and_multirow_rejected_at_admission():
    clock = FakeClock()
    srv = InferenceServer(
        CallableBackend(_segment_sum, input_specs={"data": (8, 2)},
                        pack_axis=1, accepts_segment_ids=True),
        name="packed-reject", workers=0, clock=clock, max_batch=4)
    srv.warm_up()
    with pytest.raises(RequestTooLarge):
        srv.submit({"data": np.zeros((1, 9, 2), np.float32)})  # too long
    with pytest.raises(RequestTooLarge):
        srv.submit({"data": np.zeros((2, 4, 2), np.float32)})  # multirow
    st = srv.stats()
    assert st["shed"] == 2
    # rejections are still DEMAND: the histogram suggest_buckets mines
    assert sum(st["queue"]["shape_histogram"].values()) >= 2


def test_symbolic_backend_collapses_warmup_zero_retrace(monkeypatch):
    monkeypatch.setenv("MXTPU_RETRACE_STRICT", "1")
    clock = FakeClock()
    srv = InferenceServer(
        SymbolicJitBackend(lambda arrays: [arrays["data"] * 2.0],
                           max_rows=8, input_specs={"data": (3,)}),
        name="symbolic", workers=0, clock=clock, max_batch=8)
    srv.warm_up()
    st = srv.stats()
    assert st["ragged"]["symbolic"]
    # coalescer_sizes(8) = (1, 2, 4, 8): one probe covers the other 3
    assert st["warmed_buckets"] == 1
    assert st["warmup_skipped_covered"] == 3
    assert st["batching"]["warmed_signatures"] == 1
    # a mixed-size burst rides the ONE symbolic signature, strict mode on
    reqs = [srv.submit({"data": np.full((rows, 3), float(rows),
                                        np.float32)})
            for rows in (1, 3, 5, 2)]
    srv.run_pending()
    for rows, req in zip((1, 3, 5, 2), reqs):
        np.testing.assert_array_equal(
            srv.result(req)[0], np.full((rows, 3), rows * 2.0))
    st = srv.stats()
    assert st["batching"]["unwarmed_dispatch_signatures"] == 0
    # no batch-axis padding on the symbolic leg: rows are never inflated
    assert st["pad_waste"]["rows_ratio"] == 1.0


# ---------------------------------------------------------------------------
# masked decode: the InflightBatcher rung
# ---------------------------------------------------------------------------

def _dense_step(inputs, states):
    h = np.tanh(states["h"] + inputs["x"])
    return [h * 2.0], {"h": h}


def _masked_step(inputs, states, mask=None):
    outs, next_states = _dense_step(inputs, states)
    if mask is not None:
        # un-fed rows are mask-dead garbage (zeroed); fed rows are
        # bitwise the dense result (multiplying by exactly 1.0)
        outs = [o * mask[:, None] for o in outs]
        next_states = {k: v * mask[:, None]
                       for k, v in next_states.items()}
    return outs, next_states


def _drive_schedule(batcher):
    """join a,b -> step both -> join c -> step {a,c} -> leave b ->
    step {c}: the join/leave-mid-stream shape. Returns per-sequence
    output rows and final states keyed by sequence name."""
    outs = {"a": [], "b": [], "c": []}
    rows = {name: np.full((2,), x, np.float32)
            for name, x in (("a", 0.5), ("b", -0.25), ("c", 1.5))}
    slot = {"a": batcher.join(), "b": batcher.join()}
    r = batcher.step({slot["a"]: {"x": rows["a"]},
                      slot["b"]: {"x": rows["b"]}})
    outs["a"].append(r[slot["a"]][0])
    outs["b"].append(r[slot["b"]][0])
    slot["c"] = batcher.join()
    r = batcher.step({slot["a"]: {"x": rows["a"]},
                      slot["c"]: {"x": rows["c"]}})
    outs["a"].append(r[slot["a"]][0])
    outs["c"].append(r[slot["c"]][0])
    final = {"b": batcher.leave(slot["b"])}
    r = batcher.step({slot["c"]: {"x": rows["c"]}})
    outs["c"].append(r[slot["c"]][0])
    final["a"] = batcher.leave(slot["a"])
    final["c"] = batcher.leave(slot["c"])
    return outs, final


def test_masked_decode_bitwise_vs_dense_with_join_leave():
    clock = FakeClock()
    specs = ({"x": (2,)}, {"h": (2,)})
    dense = InflightBatcher(
        CallableStepBackend(_dense_step, *specs), capacity=4,
        name="decode-dense", clock=clock, ragged=False).warm_up()
    masked = InflightBatcher(
        CallableStepBackend(_masked_step, *specs, accepts_mask=True),
        capacity=4, name="decode-masked", clock=clock,
        ragged=True).warm_up()
    assert masked.stats()["masked"] and not dense.stats()["masked"]
    outs_d, final_d = _drive_schedule(dense)
    outs_m, final_m = _drive_schedule(masked)
    for name in ("a", "b", "c"):
        assert len(outs_d[name]) == len(outs_m[name])
        for got_d, got_m in zip(outs_d[name], outs_m[name]):
            np.testing.assert_array_equal(got_d, got_m)  # BITWISE
        np.testing.assert_array_equal(final_d[name]["h"],
                                      final_m[name]["h"])
    # the decode pad tax is tracked: 2 + 2 + 1 fed rows over 3 steps
    # of capacity 4
    pw = masked.stats()["pad_waste"]
    assert pw["dispatches"] == 3
    assert (pw["real_rows"], pw["padded_rows"]) == (5, 12)
    assert masked.stats()["retraced"] == 0


def test_decode_kill_switch_steps_without_mask():
    clock = FakeClock()
    calls = []

    def spy_step(inputs, states, mask=None):
        calls.append(mask)
        return _dense_step(inputs, states)

    batcher = InflightBatcher(
        CallableStepBackend(spy_step, {"x": (2,)}, {"h": (2,)},
                            accepts_mask=True),
        capacity=2, name="decode-killed", clock=clock,
        ragged=False).warm_up()
    assert not batcher.stats()["masked"]
    slot = batcher.join()
    batcher.step({slot: {"x": np.ones((2,), np.float32)}})
    assert calls == [None, None]                 # warm-up + live step
    # observability stays on even with the rungs off
    assert batcher.stats()["pad_waste"]["dispatches"] == 1


# ---------------------------------------------------------------------------
# the masked flash-attention kernel
# ---------------------------------------------------------------------------

def _rand_qkv(rng, b, h, s, d, sk=None):
    sk = s if sk is None else sk
    return (rng.standard_normal((b, h, s, d)).astype(np.float32),
            rng.standard_normal((b, h, sk, d)).astype(np.float32),
            rng.standard_normal((b, h, sk, d)).astype(np.float32))


def test_flash_attention_dense_dispatch_and_grads_unchanged():
    import jax
    from mxnet_tpu.ops.pallas.attention import _flash_attention_dense
    rng = np.random.default_rng(0)
    q, k, v = _rand_qkv(rng, 2, 2, 8, 4)
    np.testing.assert_array_equal(
        np.asarray(flash_attention(q, k, v, causal=True)),
        np.asarray(_flash_attention_dense(q, k, v, True, None, 256,
                                          512, False)))
    g = jax.grad(lambda x: flash_attention(x, k, v).sum())(q)
    assert np.all(np.isfinite(np.asarray(g)))


def test_flash_attention_lengths_mask_matches_dense_slices():
    rng = np.random.default_rng(1)
    q, k, v = _rand_qkv(rng, 3, 2, 8, 4)
    lengths = np.array([3, 8, 5], np.int32)
    out = np.asarray(flash_attention(q, k, v, lengths=lengths))
    for i, n in enumerate(lengths):
        ref = np.asarray(flash_attention(q[i:i + 1], k[i:i + 1, :, :n],
                                         v[i:i + 1, :, :n]))
        np.testing.assert_allclose(out[i], ref[0], atol=1e-5)


def test_flash_attention_segment_mask_matches_per_segment_dense():
    rng = np.random.default_rng(2)
    q, k, v = _rand_qkv(rng, 1, 2, 8, 4)
    seg = np.array([[1, 1, 1, 2, 2, 2, 2, 0]], np.int32)
    out = np.asarray(flash_attention(q, k, v, segment_ids=seg))
    for sid, lo, hi in ((1, 0, 3), (2, 3, 7)):
        ref = np.asarray(flash_attention(q[:, :, lo:hi], k[:, :, lo:hi],
                                         v[:, :, lo:hi]))
        np.testing.assert_allclose(out[0, :, lo:hi], ref[0], atol=1e-5)
    # pad tokens (segment 0) output EXACT zero, both directions
    np.testing.assert_array_equal(out[0, :, 7], 0.0)


@pytest.mark.parametrize("h,s,d,bq,bk", [(1, 8, 4, 8, 8), (3, 32, 8, 8, 16)],
                         ids=["one-block", "heads-and-blocks"])
def test_flash_attention_masked_pallas_interpret_matches_reference(
        h, s, d, bq, bk):
    from mxnet_tpu.ops.pallas.attention import _masked_reference
    rng = np.random.default_rng(3)
    q, k, v = _rand_qkv(rng, 2, h, s, d)
    lengths = np.array([5, s], np.int32)
    seg = np.zeros((2, s), np.int32)
    seg[0, :2], seg[0, 2:5] = 1, 2                  # tail stays pad (0)
    seg[1, :s // 2], seg[1, s // 2:] = 1, 2
    for kw in ({"lengths": lengths},
               {"segment_ids": seg},
               {"lengths": lengths, "segment_ids": seg, "causal": True}):
        got = np.asarray(flash_attention(q, k, v, force_pallas=True,
                                         block_q=bq, block_k=bk, **kw))
        ref = np.asarray(_masked_reference(
            q, k, v, kw.get("lengths"), kw.get("segment_ids"),
            kw.get("causal", False), 1.0 / d ** 0.5))
        np.testing.assert_allclose(got, ref, atol=1e-5)
        if "segment_ids" in kw:         # fully-masked rows: exact 0
            np.testing.assert_array_equal(got[0, :, 5:], 0.0)
