"""The low-precision tier (mxnet_tpu/quant, docs/how_to/quantization.md).

Covers: quantize/dequantize formats, the annotate-slot quant signature
(transform_sig + persistent program keys), cross-process bitwise
determinism of the quantized program (golden via a real subprocess),
the accuracy gate's TP/TN + typed-warning fallback, the calibration
sidecar (roundtrip, corrupt/missing/truncated/fault-injected
``quant.sidecar.read`` all fall back to recalibration, never a crash),
DataIter calibration, int8-vs-fp32 coalescer padding bytes, quantized
coalesced serving under ``MXTPU_RETRACE_STRICT=1``, the admission
queue's request-shape histogram, the dynamic loss-scale schedule
(fake grad stream: overflow, recovery, clamps), the
``MXTPU_PRECISION=bf16`` mode through Module/Gluon/SPMD (non-finite
steps skipped bitwise), and ZeRO + bf16 composing bitwise vs
replicated.
"""
import json
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import quant
from mxnet_tpu.base import MXNetError
from mxnet_tpu.io import DataBatch, DataDesc
from mxnet_tpu.quant import (CalibrationStats, LossScaleConfig,
                             QuantAccuracyWarning, QuantConfig, calibrate,
                             load_stats, quantize_backend, save_stats)
from mxnet_tpu.quant import loss_scale as ls_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _no_disk_cache(tmp_path, monkeypatch):
    """Tests compile into a throwaway cache dir (and never pollute the
    user's) — the cross-process golden overrides deliberately."""
    monkeypatch.setenv("MXTPU_COMPILE_CACHE_DIR", str(tmp_path / "cc"))
    yield


def mlp_infer_module(batch=8, in_dim=16, hidden=32, classes=8, seed=3):
    data = mx.sym.var("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=hidden, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu")
    fc2 = mx.sym.FullyConnected(act, num_hidden=classes, name="fc2")
    net = mx.sym.SoftmaxOutput(fc2, name="softmax")
    mod = mx.mod.Module(net, label_names=[], context=mx.cpu())
    mod.bind(data_shapes=[("data", (batch, in_dim))], label_shapes=None,
             for_training=False)
    mx.random.seed(seed)
    mod.init_params(mx.init.Xavier())
    return mod


def calib_feeds(n=4, batch=8, in_dim=16, seed=0):
    rng = np.random.RandomState(seed)
    return [{"data": rng.rand(batch, in_dim).astype(np.float32)}
            for _ in range(n)]


# ---------------------------------------------------------------------------
# formats + core
# ---------------------------------------------------------------------------

def test_int8_roundtrip_error_bound():
    fmt = quant.FORMATS["int8"]
    rng = np.random.RandomState(0)
    x = rng.randn(64, 32).astype(np.float32)
    scale = quant.scale_for(float(np.max(np.abs(x))), fmt)
    q = quant.quantize(x, scale, fmt)
    assert str(np.asarray(q).dtype) == "int8"
    back = np.asarray(quant.dequantize(q, scale))
    # per-tensor symmetric int8: error bounded by half a step
    assert np.max(np.abs(back - x)) <= float(np.asarray(scale)) * 0.5 + 1e-7
    # zeros stay exact (scale falls back to 1.0)
    z = quant.quantize(np.zeros(4), quant.scale_for(0.0, fmt), fmt)
    assert np.array_equal(np.asarray(z), np.zeros(4, np.int8))


def test_unknown_format_is_typed_error():
    with pytest.raises(MXNetError, match="unknown quantization format"):
        QuantConfig(fmt="int3")


def test_host_and_device_quantize_agree():
    """One scale rule, two implementations (np for weights/clients, jnp
    in-program): integer formats agree bit-for-bit; float formats (fp8)
    to within one representable step — XLA's f32->f8 convert on this
    jax line rounds near-midpoint values differently from ml_dtypes'
    round-to-nearest-even, which is why the HOST quantizer is the
    canonical serving-path one (quantize_host docstring)."""
    rng = np.random.RandomState(2)
    # (16, 8) @ seed 2 contains near-midpoint fp8 cases that expose the
    # rounding divergence — keep it as the regression fixture
    x = rng.randn(16, 8).astype(np.float32)
    for fmt in quant.FORMATS.values():
        scale = quant.host_scale(float(np.max(np.abs(x))), fmt)
        host = quant.quantize_host(x, scale, fmt)
        dev = np.asarray(quant.quantize(x, scale, fmt))
        if np.issubdtype(np.dtype(fmt.dtype), np.integer):
            assert host.tobytes() == dev.tobytes(), fmt.name
        else:
            h, d = host.astype(np.float64), dev.astype(np.float64)
            # adjacent representables at most: e4m3 has 3 mantissa
            # bits, so one grid step is ~|value|/8 for normals
            assert np.all(np.abs(h - d) <= np.abs(h) / 8 + 1e-6), fmt.name


def test_fp8_quantize_keeps_fractional_resolution():
    """fp8 is a FLOAT format: quantize must clip-then-cast onto e4m3's
    own mantissa grid, not round to integers — sub-1.0 scaled values
    survive instead of collapsing to 0."""
    fmt = quant.FORMATS["fp8_e4m3"]
    x = np.asarray([0.3, 0.55, -0.7, 1.25], np.float32)
    q = quant.quantize_host(x, 1.0, fmt)
    back = np.asarray(quant.dequantize(np.asarray(q), 1.0))
    assert np.all(np.abs(back - x) < 0.1), back       # not integerized
    assert np.count_nonzero(back) == 4                # nothing collapsed


def test_input_name_honored_with_quant_on_and_on_fallback():
    """input_name must survive quant=True on the quantized backend AND
    on the gate-refusal fp32 fallback (it names the primary input a
    bare-array submit binds to)."""
    mod = mlp_infer_module()
    qb = mod.as_serving_backend(input_name="data", quant=True,
                                calib_data=calib_feeds())
    assert qb.input_name == "data"
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        fb = quantize_backend(mod, calib_feeds(), input_name="data",
                              config=QuantConfig(max_accuracy_delta=0.0))
    assert type(fb).__name__ == "ModuleBackend"
    assert fb.input_name == "data"


def test_quant_annotator_stamps_transform_sig():
    from mxnet_tpu import compiler
    from mxnet_tpu.quant.core import quant_scope
    mod = mlp_infer_module()
    shapes = {n: tuple(v.shape)
              for n, v in mod._exec.arg_dict.items()}
    plain = compiler.optimize(mod._symbol, for_training=False,
                              input_shapes=shapes)
    assert "quant=" not in plain.transform_sig
    with quant_scope(QuantConfig(), ["fc1_weight", "fc2_weight"]):
        quanted = compiler.optimize(mod._symbol, for_training=False,
                                    input_shapes=shapes)
    assert "quant=" in quanted.transform_sig
    with quant_scope(QuantConfig(), ["fc1_weight"]):
        partial = compiler.optimize(mod._symbol, for_training=False,
                                    input_shapes=shapes)
    # a different gated parameter set is a different precision decision
    assert partial.transform_sig != quanted.transform_sig


def test_quant_vs_fp32_program_keys_distinct():
    """The persistent cache must never serve a stale-precision program:
    same graph, same avals — different program_key once the quant
    signature joins the transform sig (the sharding_sig pattern)."""
    from mxnet_tpu.compiler import fingerprint as fp
    k_fp32 = fp.program_key("quant-forward", "graphfp", "avals",
                            transform_sig="passes=0;remat=0")
    k_int8 = fp.program_key("quant-forward", "graphfp", "avals",
                            transform_sig="passes=0;remat=0;quant=abc123")
    assert k_fp32 != k_int8


# ---------------------------------------------------------------------------
# calibration + the manifest-covered sidecar
# ---------------------------------------------------------------------------

def test_calibrate_accepts_dataiter_and_dicts():
    rng = np.random.RandomState(1)
    arr = rng.rand(16, 16).astype(np.float32) * 3.0
    it = mx.io.NDArrayIter(arr, batch_size=4)
    stats = calibrate(["data"], it, num_batches=4)
    assert stats.batches == 4
    assert stats.input_absmax["data"] == pytest.approx(
        float(np.max(np.abs(arr))), rel=0.5)
    stats2 = calibrate(["data"], [{"data": arr}])
    assert stats2.input_absmax["data"] == pytest.approx(
        float(np.max(np.abs(arr))))
    with pytest.raises(MXNetError, match="no batches"):
        calibrate(["data"], [])


def test_calibrate_rejects_wrongly_keyed_feeds():
    """Feeds that never carry any named input must raise — silently
    shipping scale-1.0 quantization is the failure mode the docstring
    forbids. A PARTIALLY missing name warns and keeps scale 1.0."""
    with pytest.raises(MXNetError, match="none carried"):
        calibrate(["data"], [{"wrong_key": np.ones((2, 4))}])
    stats = calibrate(["data", "aux_in"],
                      [{"data": np.ones((2, 4)) * 3.0}])
    assert stats.input_absmax["data"] == 3.0
    assert stats.input_absmax["aux_in"] == 0.0


def test_accuracy_gate_not_diluted_by_pad_rows():
    """Calibration batches smaller than the bound batch are zero-padded
    up; the gate must measure the REAL rows only, or the pad rows'
    near-zero error dilutes the delta by padded/real and an
    over-threshold model ships."""
    from mxnet_tpu.quant.ptq import _fit_rows, measure_accuracy_delta

    class _Fixed:
        def __init__(self, row_out):
            self.row_out = row_out

        def infer(self, arrays):
            n = arrays["data"].shape[0]
            out = np.zeros((n, 4), np.float32)
            out[0] = self.row_out          # only row 0 is "real"
            return [out]

    base = _Fixed(np.asarray([1.0, 0, 0, 0], np.float32))
    quantish = _Fixed(np.asarray([2.0, 0, 0, 0], np.float32))
    feed = _fit_rows({"data": np.ones((1, 4), np.float32)}, 32)
    diluted = measure_accuracy_delta(base, quantish, [feed])
    honest = measure_accuracy_delta(base, quantish, [feed],
                                    real_rows=[1])
    # the real row's relative error is 1.0; without row restriction the
    # pad rows cannot hide it here (outputs are zero there), but the
    # restricted measurement must equal the true per-row error exactly
    assert honest["accuracy_delta"] == pytest.approx(1.0)
    assert diluted["accuracy_delta"] == pytest.approx(1.0)

    class _Biased(_Fixed):
        def infer(self, arrays):
            n = arrays["data"].shape[0]
            out = np.ones((n, 4), np.float32)  # bias mass on pad rows
            out[0] = self.row_out
            return [out]

    b2 = _Biased(np.asarray([1.0, 0, 0, 0], np.float32))
    q2 = _Biased(np.asarray([2.0, 0, 0, 0], np.float32))
    diluted = measure_accuracy_delta(b2, q2, [feed])
    honest = measure_accuracy_delta(b2, q2, [feed], real_rows=[1])
    assert honest["accuracy_delta"] == pytest.approx(1.0)
    assert diluted["accuracy_delta"] < 0.05   # the hole the fix closes


def test_sidecar_roundtrip(tmp_path):
    path = str(tmp_path / "calib.json")
    stats = CalibrationStats({"data": 2.5}, batches=3)
    save_stats(stats, path)
    assert os.path.exists(path + ".manifest.json")
    loaded = load_stats(path)
    assert loaded is not None
    assert loaded.to_dict() == stats.to_dict()


def test_sidecar_corrupt_missing_truncated_fall_back(tmp_path):
    """A reloaded Predictor must recalibrate on ANY bad sidecar — flip,
    truncation, missing manifest, absent file — never crash."""
    path = str(tmp_path / "calib.json")
    assert load_stats(path) is None                       # missing
    save_stats(CalibrationStats({"data": 2.5}, 3), path)
    with open(path, "a") as f:                            # flipped bytes
        f.write("garbage")
    assert load_stats(path) is None
    save_stats(CalibrationStats({"data": 2.5}, 3), path)
    with open(path, "w") as f:                            # truncated
        f.write("{")
    assert load_stats(path) is None
    save_stats(CalibrationStats({"data": 2.5}, 3), path)
    os.remove(path + ".manifest.json")                    # manifest gone
    assert load_stats(path) is None


def test_sidecar_read_fault_falls_back_to_recalibration(tmp_path):
    """An injected transient fault at ``quant.sidecar.read`` reads as
    recalibrate — the entry is left in place and the next read works."""
    from mxnet_tpu.resilience import FaultPlan, faults
    path = str(tmp_path / "calib.json")
    save_stats(CalibrationStats({"data": 1.5}, 2), path)
    faults.arm(FaultPlan().arm("quant.sidecar.read", nth=1, count=1,
                               exc="ioerror"))
    try:
        assert load_stats(path) is None          # fault -> recalibrate
        assert faults.stats()["fired"]["quant.sidecar.read"] == 1
        reloaded = load_stats(path)              # entry survived
        assert reloaded is not None and reloaded.batches == 2
    finally:
        faults.disarm()


def test_quantize_backend_reuses_sidecar_without_recalibrating(tmp_path):
    path = str(tmp_path / "calib.json")
    mod = mlp_infer_module()
    feeds = calib_feeds()
    b1 = quantize_backend(mod, feeds, stats_path=path)
    assert b1.quant_report.shipped
    # a second load with DIFFERENT (in-range) batches: recalibration
    # would observe a different absmax; the sidecar hit reuses the
    # first calibration exactly
    other = calib_feeds(seed=99)
    recal = calibrate(["data"], other)
    assert recal.input_absmax != b1.stats.input_absmax
    b2 = quantize_backend(mod, other, stats_path=path)
    assert b2.quant_report.shipped
    assert b2.stats.input_absmax == b1.stats.input_absmax


# ---------------------------------------------------------------------------
# the accuracy gate
# ---------------------------------------------------------------------------

def test_accuracy_gate_ships_good_model():
    mod = mlp_infer_module()
    backend = quantize_backend(mod, calib_feeds())
    assert type(backend).__name__ == "QuantizedModuleBackend"
    rep = backend.quant_report
    assert rep.shipped and rep.accuracy_delta <= rep.threshold
    assert rep.format == "int8" and rep.fallback_reason is None
    assert rep.top1_agreement is not None


def test_accuracy_gate_refuses_and_falls_back_fp32():
    """TP: an impossible threshold refuses the quantized model — the
    fp32 backend ships with the typed QuantAccuracyWarning, and the
    report says why."""
    mod = mlp_infer_module()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        backend = quantize_backend(
            mod, calib_feeds(), config=QuantConfig(max_accuracy_delta=0.0))
    assert type(backend).__name__ == "ModuleBackend"
    assert any(issubclass(w.category, QuantAccuracyWarning)
               for w in caught)
    rep = backend.quant_report
    assert not rep.shipped and "threshold" in rep.fallback_reason


def test_quantized_outputs_close_to_fp32():
    from mxnet_tpu.serving import ModuleBackend
    mod = mlp_infer_module()
    feeds = calib_feeds()
    qb = quantize_backend(mod, feeds)
    base = ModuleBackend(mod)
    base.load()
    b = base.infer(feeds[0])[0]
    q = qb.infer(feeds[0])[0]
    assert np.argmax(b, axis=1).tolist() == np.argmax(q, axis=1).tolist()
    assert float(np.mean(np.abs(b - q))) < 0.02


def test_int8_and_fp32_submissions_identical():
    """A client that pre-quantizes with the published scales and one
    that submits fp32 land in the SAME int8 program — bitwise."""
    mod = mlp_infer_module()
    feeds = calib_feeds()
    qb = quantize_backend(mod, feeds)
    out_f = qb.infer(feeds[0])
    out_q = qb.infer(qb.quantize_inputs(feeds[0]))
    for a, b in zip(out_f, out_q):
        assert np.array_equal(a, b)


def test_embedding_index_inputs_never_quantized():
    """Index-semantic inputs (an Embedding's data slot) must not be
    range-quantized — round(token/scale) destroys the id."""
    data = mx.sym.var("data")
    emb = mx.sym.Embedding(data, input_dim=40, output_dim=8, name="emb")
    fc = mx.sym.FullyConnected(emb, num_hidden=4, name="fc")
    net = mx.sym.SoftmaxOutput(fc, name="softmax")
    mod = mx.mod.Module(net, label_names=[], context=mx.cpu())
    mod.bind(data_shapes=[("data", (4,))], label_shapes=None,
             for_training=False)
    mx.random.seed(5)
    mod.init_params(mx.init.Xavier())
    rng = np.random.RandomState(0)
    feeds = [{"data": rng.randint(0, 40, (4,)).astype(np.float32)}
             for _ in range(2)]
    qb = quantize_backend(mod, feeds)
    assert qb.quant_report.shipped
    assert "data" not in qb.quant_report.quantized_inputs
    # the embedding TABLE (a 2-D weight) still quantizes
    assert "emb_weight" in qb.quantized_params


def test_as_serving_backend_knob_and_errors(monkeypatch):
    mod = mlp_infer_module()
    assert type(mod.as_serving_backend()).__name__ == "ModuleBackend"
    with pytest.raises(MXNetError, match="calib_data"):
        mod.as_serving_backend(quant=True)
    monkeypatch.setenv("MXTPU_QUANT", "1")
    with pytest.raises(MXNetError, match="calib_data"):
        mod.as_serving_backend()
    backend = mod.as_serving_backend(calib_data=calib_feeds())
    assert type(backend).__name__ == "QuantizedModuleBackend"
    monkeypatch.setenv("MXTPU_QUANT_MAX_DELTA", "0.0")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        fb = mod.as_serving_backend(calib_data=calib_feeds())
    assert type(fb).__name__ == "ModuleBackend"


def test_quantized_backend_from_artifact(tmp_path):
    """Predictor-load quantization: the same symbol-JSON + .params
    artifact surface, with corrupt artifacts keeping their typed
    error."""
    import io as _io
    from mxnet_tpu.quant import quantized_backend_from_artifact
    mod = mlp_infer_module(batch=4)
    arg, aux = mod.get_params()
    buf = _io.BytesIO()
    np.savez(buf, **{f"arg:{k}": v.asnumpy() for k, v in arg.items()},
             **{f"aux:{k}": v.asnumpy() for k, v in aux.items()})
    feeds = calib_feeds(n=2, batch=4)
    qb = quantized_backend_from_artifact(
        mod._symbol.tojson(), buf.getvalue(), (16,), feeds, batch_size=4)
    assert type(qb).__name__ == "QuantizedModuleBackend"
    assert qb.quant_report.shipped
    assert qb.infer(feeds[0])[0].shape == (4, 8)
    with pytest.raises(MXNetError, match="corrupt or truncated"):
        quantized_backend_from_artifact(mod._symbol.tojson(), b"junk",
                                        (16,), feeds, batch_size=4)


# ---------------------------------------------------------------------------
# cross-process determinism (the fingerprint golden)
# ---------------------------------------------------------------------------

_GOLDEN_CHILD = r"""
import hashlib, json, os, sys
import numpy as np
sys.path.insert(0, {root!r})
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import mxnet_tpu as mx
from mxnet_tpu.quant import quantize_backend

data = mx.sym.var("data")
fc1 = mx.sym.FullyConnected(data, num_hidden=32, name="fc1")
act = mx.sym.Activation(fc1, act_type="relu")
fc2 = mx.sym.FullyConnected(act, num_hidden=8, name="fc2")
net = mx.sym.SoftmaxOutput(fc2, name="softmax")
mod = mx.mod.Module(net, label_names=[], context=mx.cpu())
mod.bind(data_shapes=[("data", (8, 16))], label_shapes=None,
         for_training=False)
mx.random.seed(3)
mod.init_params(mx.init.Xavier())
rng = np.random.RandomState(0)
feeds = [{{"data": rng.rand(8, 16).astype(np.float32)}}
         for _ in range(4)]
qb = quantize_backend(mod, feeds)
h = hashlib.sha256()
for n in sorted(qb._qweights):
    h.update(np.asarray(qb._qweights[n]).tobytes())
    h.update(np.float32(qb._wscales[n]).tobytes())
out = qb.infer(feeds[0])[0]
h.update(np.asarray(out, np.float32).tobytes())
print(json.dumps({{"digest": h.hexdigest(),
                   "sig": qb.program_key_parts()[1]}}))
"""


@pytest.mark.slow
def test_cross_process_quantized_golden(tmp_path):
    """Bitwise determinism across processes: two separate interpreters
    quantize the same seeded model and must agree on the int8 weight
    bytes, the per-tensor scales, the quantized outputs, AND the quant
    program signature — the property that makes the persistent compile
    cache (keyed on that signature) safe to share between processes."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MXTPU_COMPILE_CACHE_DIR=str(tmp_path / "cc"))
    script = _GOLDEN_CHILD.format(root=ROOT)
    outs = []
    for _ in range(2):
        proc = subprocess.run([sys.executable, "-c", script],
                              capture_output=True, text=True, env=env,
                              timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]
        outs.append(json.loads(proc.stdout.strip().splitlines()[-1]))
    assert outs[0]["digest"] == outs[1]["digest"]
    assert outs[0]["sig"] == outs[1]["sig"]
    assert "quant=" in outs[0]["sig"]


# ---------------------------------------------------------------------------
# serving: padding bytes, strict coalescing, the shape histogram
# ---------------------------------------------------------------------------

def test_int8_padding_bytes_quarter_of_fp32():
    from mxnet_tpu.serving import ShapeBuckets
    buckets = ShapeBuckets([16])
    p8, rows8 = buckets.pad_batch(np.zeros((3, 32, 32, 3), np.int8))
    p32, rows32 = buckets.pad_batch(np.zeros((3, 32, 32, 3), np.float32))
    assert rows8 == rows32 == 3
    assert p8.dtype == np.int8 and p32.dtype == np.float32
    assert p8.nbytes * 4 == p32.nbytes


def test_quantized_serving_coalesced_strict(monkeypatch):
    """The compounding win: int8 requests ride the BatchCoalescer with
    ZERO unwarmed signatures under MXTPU_RETRACE_STRICT=1 (the server
    warmed int8 buckets because the backend declares input_dtypes), and
    per-request scatter equals one batched infer."""
    from mxnet_tpu.serving import InferenceServer
    monkeypatch.setenv("MXTPU_RETRACE_STRICT", "1")
    mod = mlp_infer_module()
    backend = quantize_backend(mod, calib_feeds())
    assert backend.input_dtypes["data"] == "int8"
    server = InferenceServer(backend, name="quant-strict", max_batch=8,
                             workers=0, capacity=64,
                             default_deadline=60.0)
    try:
        server.warm_up()
        rng = np.random.RandomState(7)
        rows = [backend.quantize_inputs(
            {"data": rng.rand(1, 16).astype(np.float32)})
            for _ in range(12)]
        pending = [server.submit(r) for r in rows]
        server.run_pending()
        outs = [server.result(p) for p in pending]
        stats = server.stats()
        assert stats["completed"] == 12
        assert stats["batching"]["unwarmed_dispatch_signatures"] == 0
        assert stats["dispatches"] < 12
        merged = backend.infer(
            {"data": np.concatenate([r["data"] for r in rows])})
        for i, o in enumerate(outs):
            assert np.array_equal(o[0][0], merged[0][i])
    finally:
        server.close()


def test_admission_shape_histogram_records_and_bounds():
    from mxnet_tpu.serving import AdmissionQueue, Deadline, Request
    q = AdmissionQueue(capacity=512)
    for _ in range(3):
        q.offer(Request({"data": np.zeros((1, 16), np.int8)},
                        Deadline(None)))
    q.offer(Request({"data": np.zeros((2, 16), np.float32)},
                    Deadline(None)))
    hist = q.shape_histogram()
    assert hist["1r|data:(16,):int8"] == 3
    assert hist["2r|data:(16,):float32"] == 1
    # bounded: past the cap, new shapes fold into the overflow bucket
    q2 = AdmissionQueue(capacity=8192)
    for i in range(AdmissionQueue._SHAPE_HIST_CAP + 10):
        q2.offer(Request({"data": np.zeros((1, i + 1), np.float32)},
                         Deadline(None)))
    h2 = q2.shape_histogram()
    assert len(h2) <= AdmissionQueue._SHAPE_HIST_CAP + 1
    assert h2[AdmissionQueue._SHAPE_HIST_OVERFLOW] == 10


def test_oversized_requests_reach_the_shape_histogram():
    """Requests rejected as RequestTooLarge never reach the queue, but
    they are exactly the demand signal bucket mining needs — the server
    must record them before raising."""
    from mxnet_tpu.serving import (CallableBackend, InferenceServer,
                                   RequestTooLarge)
    backend = CallableBackend(lambda a: a["data"].sum(axis=1),
                              input_specs={"data": (4,)})
    srv = InferenceServer(backend, name="hist-oversize", buckets=[2],
                          workers=0)
    try:
        srv.warm_up()
        with pytest.raises(RequestTooLarge):
            srv.submit({"data": np.zeros((5, 4), np.float32)})
        hist = srv.stats()["queue"]["shape_histogram"]
        assert hist["5r|data:(4,):float32"] == 1
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# the dynamic loss-scale schedule
# ---------------------------------------------------------------------------

def test_loss_scale_schedule_on_fake_grad_stream():
    """The schedule contract on a scripted stream: grow after a full
    finite streak, back off on overflow, streak resets, clamps hold."""
    import jax.numpy as jnp
    cfg = LossScaleConfig(init_scale=8.0, growth_interval=2,
                          max_scale=32.0, min_scale=2.0)
    state = ls_mod.init_state(cfg)
    stream = ["f", "f",          # full streak -> 16
              "f", "inf",        # overflow   -> 8, streak 0
              "inf",             # again      -> 4
              "f", "f",          # streak     -> 8
              "inf", "inf", "inf", "inf"]  # clamp at min 2
    expected_scale = [8, 16, 16, 8, 4, 4, 8, 4, 2, 2, 2]
    for kind, want in zip(stream, expected_scale):
        grads = {"w": jnp.ones(3) if kind == "f"
                 else jnp.asarray([1.0, np.inf, 1.0])}
        finite = ls_mod.tree_all_finite(grads)
        assert bool(np.asarray(finite)) == (kind == "f")
        state = ls_mod.next_state(state, finite, cfg)
        assert float(np.asarray(state[0])) == want, (kind, want)
    # growth clamps at max_scale
    state = (jnp.float32(32.0), jnp.int32(1))
    state = ls_mod.next_state(state, jnp.bool_(True), cfg)
    assert float(np.asarray(state[0])) == 32.0


def test_host_mirror_matches_functional_schedule():
    import jax.numpy as jnp
    cfg = LossScaleConfig(init_scale=4.0, growth_interval=3,
                          max_scale=64.0, min_scale=1.0)
    host = ls_mod.DynamicLossScale(cfg)
    state = ls_mod.init_state(cfg)
    rng = np.random.RandomState(0)
    for _ in range(40):
        finite = bool(rng.rand() > 0.3)
        host.update(finite)
        state = ls_mod.next_state(state, jnp.bool_(finite), cfg)
        assert float(np.asarray(state[0])) == host.scale


def test_precision_env_resolution(monkeypatch):
    from mxnet_tpu import perf
    monkeypatch.delenv("MXTPU_PRECISION", raising=False)
    assert perf.precision_compute_dtype(None) is None
    assert perf.precision_loss_scale(None) is None
    assert perf.precision_compute_dtype("float16") == "float16"
    monkeypatch.setenv("MXTPU_PRECISION", "bf16")
    assert perf.precision_compute_dtype(None) == "bfloat16"
    assert perf.precision_loss_scale(None) is not None
    assert perf.precision_loss_scale(False) is None
    monkeypatch.setenv("MXTPU_PRECISION", "int7")
    with pytest.raises(MXNetError, match="MXTPU_PRECISION"):
        perf.precision_compute_dtype(None)


# ---------------------------------------------------------------------------
# the MXTPU_PRECISION=bf16 training mode
# ---------------------------------------------------------------------------

def _mlp_train_module(seed=7):
    data = mx.sym.var("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu")
    fc2 = mx.sym.FullyConnected(act, num_hidden=4, name="fc2")
    net = mx.sym.SoftmaxOutput(fc2, mx.sym.var("softmax_label"),
                               name="softmax")
    mod = mx.mod.Module(net)
    mod.bind(data_shapes=[DataDesc("data", (8, 10))],
             label_shapes=[DataDesc("softmax_label", (8,))])
    mx.random.seed(seed)
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9})
    return mod


def _train_batch(rng=None):
    rng = rng or np.random.RandomState(0)
    return DataBatch(
        data=[mx.nd.array(rng.rand(8, 10).astype(np.float32))],
        label=[mx.nd.array(rng.randint(0, 4, (8,)).astype(np.float32))])


def test_bf16_mode_module_skips_nonfinite_step_bitwise(monkeypatch):
    """MXTPU_PRECISION=bf16 arms the in-program guard in the Module
    fused step: a poison (NaN) batch leaves params BITWISE unchanged,
    backs the scale off, and the next finite step trains normally."""
    from mxnet_tpu import perf
    monkeypatch.setenv("MXTPU_PRECISION", "bf16")
    mod = _mlp_train_module()
    stepper = perf.module_stepper(mod)
    assert stepper is not None
    batch = _train_batch()
    stepper.step(batch)
    stepper.sync_to_module()
    before = {n: v.asnumpy().copy()
              for n, v in mod.get_params()[0].items()}
    poison = DataBatch(
        data=[mx.nd.array(np.full((8, 10), np.nan, np.float32))],
        label=batch.label)
    stepper.step(poison)
    stepper.sync_to_module()
    after = mod.get_params()[0]
    for n in before:
        assert np.array_equal(before[n], after[n].asnumpy()), n
    ls = stepper._fused.loss_scale_stats()
    assert ls["scale"] == 2.0 ** 14 and ls["finite_streak"] == 0
    stepper.step(batch)      # recovery: a finite step applies again
    ls2 = stepper._fused.loss_scale_stats()
    assert ls2["finite_streak"] == 1
    stepper.sync_to_module()
    recovered = mod.get_params()[0]
    assert not np.array_equal(before["fc1_weight"],
                              recovered["fc1_weight"].asnumpy())


def test_gluon_loss_scale_skip_and_schedule():
    from mxnet_tpu import autograd, gluon
    net = gluon.nn.Sequential()
    with net.name_scope():
        net.add(gluon.nn.Dense(8, activation="relu"))
        net.add(gluon.nn.Dense(4))
    net.initialize(mx.init.Xavier())
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1}, loss_scale=True)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    rng = np.random.RandomState(0)
    x = mx.nd.array(rng.rand(8, 10).astype(np.float32))
    y = mx.nd.array(rng.randint(0, 4, (8,)).astype(np.float32))
    with autograd.record():
        loss = loss_fn(net(x), y) * tr.loss_scale.scale
    loss.backward()
    tr.step(8)
    assert tr.loss_scale.steps_skipped == 0
    with autograd.record():
        out = net(mx.nd.array(np.full((8, 10), np.nan, np.float32)))
        loss = loss_fn(out, y) * tr.loss_scale.scale
    loss.backward()
    before = {k: p.data().asnumpy().copy()
              for k, p in net.collect_params().items()}
    tr.step(8)
    for k, p in net.collect_params().items():
        assert np.array_equal(before[k], p.data().asnumpy()), k
    assert tr.loss_scale.steps_skipped == 1
    assert tr.loss_scale.scale == 2.0 ** 14


def test_gluon_loss_scale_needs_functional_rule():
    from mxnet_tpu import gluon
    net = gluon.nn.Dense(4)
    net.initialize(mx.init.Xavier())
    with pytest.raises(MXNetError, match="functional update rule"):
        gluon.Trainer(net.collect_params(), "adagrad", {},
                      loss_scale=True)


def test_bf16_fp32_default_unaffected(monkeypatch):
    """Without the mode, nothing changes: no guard, no cast."""
    from mxnet_tpu import perf
    monkeypatch.delenv("MXTPU_PRECISION", raising=False)
    mod = _mlp_train_module()
    stepper = perf.module_stepper(mod)
    stepper.step(_train_batch())
    assert stepper._fused.loss_scale_stats() is None
    assert stepper._fused.compute_dtype is None


# ---------------------------------------------------------------------------
# ZeRO + bf16 compose
# ---------------------------------------------------------------------------

def test_zero_bf16_compose_bitwise_vs_replicated(monkeypatch):
    """The ZeRO=1 bitwise contract (PR 9) must survive the bf16 mode:
    sharded-update training under MXTPU_PRECISION=bf16 reproduces the
    replicated bf16 run bit-for-bit, loss-scale guard armed in both."""
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh
    monkeypatch.setenv("MXTPU_PRECISION", "bf16")
    feeds = [{"data": np.random.RandomState(i).rand(16, 12)
              .astype(np.float32),
              "softmax_label": np.random.RandomState(100 + i)
              .randint(0, 4, (16,)).astype(np.float32)}
             for i in range(3)]
    data = mx.sym.var("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu")
    fc2 = mx.sym.FullyConnected(act, num_hidden=4, name="fc2")
    net = mx.sym.SoftmaxOutput(fc2, mx.sym.var("softmax_label"),
                               name="softmax")
    outs = {}
    for shard in (False, True):
        mesh = make_mesh({"data": 8})
        tr = SPMDTrainer(net, optimizer="sgd",
                         optimizer_params=dict(learning_rate=0.5,
                                               momentum=0.9,
                                               rescale_grad=1.0 / 16),
                         mesh=mesh, shard_optimizer_state=shard)
        mx.random.seed(42)
        tr.bind(data_shapes={"data": (16, 12)},
                label_shapes={"softmax_label": (16,)})
        assert tr.loss_scale_stats() is not None   # mode armed the guard
        for f in feeds:
            tr.step(f)
        assert tr.loss_scale_stats()["finite_streak"] == 3
        arg, _ = tr.get_params()
        outs[shard] = {n: v.asnumpy() for n, v in arg.items()}
    for n in outs[False]:
        assert np.array_equal(outs[True][n], outs[False][n]), n
