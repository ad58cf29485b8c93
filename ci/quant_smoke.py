#!/usr/bin/env python
"""CI smoke for the low-precision tier (make ci-quant).

Timeout-bounded end-to-end proof, run under MXTPU_RETRACE_STRICT=1 so
finishing clean IS the zero-retrace assertion:

1. calibrate + quantize a micro ResNet and a micro scoring LSTM
   (sidecar snapshot + reload: the second backend must NOT recalibrate);
2. the accuracy gate ships both (delta <= threshold) — and a
   deliberately impossible threshold REFUSES with the typed warning and
   serves fp32;
3. both quantized backends serve a coalesced int8 burst through the
   InferenceServer with zero unwarmed dispatch signatures and
   per-request outputs bitwise equal to one batched infer;
4. the quantized program's persistent key differs from the fp32 key
   for the same graph (stale-precision-proof), and a bf16-mode training
   step skips a poison batch bitwise.
"""
import os
import sys
import warnings

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("MXTPU_RETRACE_STRICT", "1")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu.quant import (QuantAccuracyWarning, QuantConfig,  # noqa: E402
                             load_stats, quantize_backend)
from mxnet_tpu.serving import InferenceServer  # noqa: E402

MAX_BATCH = 8
N_REQUESTS = 24
IMAGE_SHAPE = (24, 24, 3)
NUM_CLASSES = 8
SEQ, VOCAB = 12, 40


def micro_resnet():
    from mxnet_tpu import models
    sym = models.get_symbol("resnet", num_layers=18,
                            num_classes=NUM_CLASSES,
                            image_shape=",".join(map(str, IMAGE_SHAPE)))
    mod = mx.mod.Module(sym, label_names=[], context=mx.cpu())
    mod.bind(data_shapes=[("data", (MAX_BATCH,) + IMAGE_SHAPE)],
             label_shapes=None, for_training=False)
    mx.random.seed(5)
    mod.init_params(mx.init.Xavier())
    return mod


def micro_lstm():
    data = mx.sym.var("data")
    emb = mx.sym.Embedding(data, input_dim=VOCAB, output_dim=16,
                           name="embed")
    emb = mx.sym.SwapAxis(emb, dim1=0, dim2=1)
    stack = mx.rnn.FusedRNNCell(32, num_layers=1, mode="lstm",
                                prefix="lstm_")
    out, _ = stack.unroll(SEQ, inputs=emb, merge_outputs=True,
                          layout="TNC")
    pred = mx.sym.FullyConnected(mx.sym.SequenceLast(out),
                                 num_hidden=NUM_CLASSES, name="pred")
    net = mx.sym.SoftmaxOutput(pred, name="softmax")
    mod = mx.mod.Module(net, label_names=[], context=mx.cpu())
    mod.bind(data_shapes=[("data", (MAX_BATCH, SEQ))],
             label_shapes=None, for_training=False)
    mx.random.seed(11)
    mod.init_params(mx.init.Xavier())
    return mod


def serve_burst(backend, name, rows):
    server = InferenceServer(backend, name=name, max_batch=MAX_BATCH,
                             workers=0, capacity=N_REQUESTS,
                             default_deadline=120.0)
    server.warm_up()
    pending = [server.submit(r) for r in rows]
    server.run_pending()
    outs = [server.result(p) for p in pending]
    stats = server.stats()
    server.close()
    assert stats["completed"] == N_REQUESTS, stats
    assert stats["batching"]["unwarmed_dispatch_signatures"] == 0, stats
    assert stats["dispatches"] < N_REQUESTS, \
        f"no coalescing happened: {stats['dispatches']} dispatches"
    assert stats["queue"]["shape_histogram"], "histogram empty"
    return outs, stats


def check_model(mod, make_row, seed, label, tmpdir):
    rng = np.random.RandomState(seed)
    calib = [make_row(rng, MAX_BATCH) for _ in range(3)]
    sidecar = os.path.join(tmpdir, f"{label}.calib.json")
    qb = quantize_backend(mod, calib, stats_path=sidecar)
    rep = qb.quant_report
    assert rep.shipped, f"{label}: gate refused ({rep.to_dict()})"
    assert rep.accuracy_delta <= rep.threshold
    # a reloaded backend consumes the sidecar instead of recalibrating
    assert load_stats(sidecar) is not None
    qb2 = quantize_backend(mod, calib, stats_path=sidecar)
    assert qb2.stats.input_absmax == qb.stats.input_absmax
    rows = [qb.quantize_inputs(make_row(rng, 1))
            for _ in range(N_REQUESTS)]
    outs, stats = serve_burst(qb, f"quant-smoke-{label}", rows)
    merged = qb.infer({k: np.concatenate([r[k] for r in rows])
                       for k in rows[0]})
    for i, o in enumerate(outs):
        assert np.array_equal(o[0][0], merged[0][i]), i
    print(f"[quant-smoke] {label}: delta={rep.accuracy_delta:.5f} "
          f"(gate {rep.threshold}), {len(rep.quantized_params)} params "
          f"int8, {stats['dispatches']} dispatches for "
          f"{N_REQUESTS} requests, 0 unwarmed")
    return qb


def main():
    import tempfile
    tmpdir = tempfile.mkdtemp(prefix="quant-smoke-")

    def resnet_row(rng, n):
        return {"data": rng.rand(n, *IMAGE_SHAPE).astype(np.float32)}

    def lstm_row(rng, n):
        return {"data": rng.randint(0, VOCAB, (n, SEQ))
                .astype(np.float32)}

    qb = check_model(micro_resnet(), resnet_row, 0, "resnet", tmpdir)
    check_model(micro_lstm(), lstm_row, 7, "lstm", tmpdir)

    # quant-vs-fp32 program keys distinct (stale-precision-proof)
    from mxnet_tpu.compiler import fingerprint as fp
    sig = qb.program_key_parts()
    assert any("quant=" in p for p in sig), sig
    k_q = fp.program_key("quant-forward", sig[0], "avals",
                         transform_sig=sig[1])
    k_f = fp.program_key("quant-forward", sig[0], "avals",
                         transform_sig="passes=0;remat=0")
    assert k_q != k_f
    print("[quant-smoke] quant-vs-fp32 program keys distinct")

    # the gate's refusal leg: impossible threshold -> typed warning +
    # fp32 fallback
    mod = micro_resnet()
    rng = np.random.RandomState(3)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        fb = quantize_backend(mod, [resnet_row(rng, MAX_BATCH)],
                              config=QuantConfig(max_accuracy_delta=0.0))
    assert type(fb).__name__ == "ModuleBackend"
    assert any(issubclass(w.category, QuantAccuracyWarning)
               for w in caught)
    print("[quant-smoke] accuracy gate refusal -> fp32 fallback OK")

    # bf16 mode: poison step skipped bitwise, schedule backs off
    from mxnet_tpu import perf
    from mxnet_tpu.io import DataBatch, DataDesc
    os.environ["MXTPU_PRECISION"] = "bf16"
    try:
        data = mx.sym.var("data")
        fc = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
        net = mx.sym.SoftmaxOutput(
            mx.sym.FullyConnected(mx.sym.Activation(fc, act_type="relu"),
                                  num_hidden=4, name="fc2"),
            mx.sym.var("softmax_label"), name="softmax")
        tmod = mx.mod.Module(net)
        tmod.bind(data_shapes=[DataDesc("data", (8, 10))],
                  label_shapes=[DataDesc("softmax_label", (8,))])
        mx.random.seed(7)
        tmod.init_params(mx.init.Xavier())
        tmod.init_optimizer(optimizer="sgd",
                            optimizer_params={"learning_rate": 0.1})
        stepper = perf.module_stepper(tmod)
        r = np.random.RandomState(0)
        good = DataBatch(
            data=[mx.nd.array(r.rand(8, 10).astype(np.float32))],
            label=[mx.nd.array(r.randint(0, 4, (8,))
                               .astype(np.float32))])
        stepper.step(good)
        stepper.sync_to_module()
        before = {n: v.asnumpy().copy()
                  for n, v in tmod.get_params()[0].items()}
        stepper.step(DataBatch(
            data=[mx.nd.array(np.full((8, 10), np.nan, np.float32))],
            label=good.label))
        stepper.sync_to_module()
        for n, v in tmod.get_params()[0].items():
            assert np.array_equal(before[n], v.asnumpy()), n
        ls = stepper._fused.loss_scale_stats()
        assert ls["scale"] < 2.0 ** 15 and ls["finite_streak"] == 0
        print(f"[quant-smoke] bf16 poison step skipped bitwise, "
              f"scale backed off to {ls['scale']:.0f}")
    finally:
        os.environ.pop("MXTPU_PRECISION", None)

    print("[quant-smoke] PASS")


if __name__ == "__main__":
    main()
