#!/usr/bin/env python
"""Low-precision-tier records: int8 quantized serving + bf16 training.

Two legs, matching ROADMAP item 1's acceptance:

* ``quant_serving`` — the SAME open-loop burst of single-row requests
  served twice through the coalescing `InferenceServer` (max_batch=16,
  same deadline): once against the fp32 backend, once against the
  int8-PTQ backend (`quantize_backend`: calibrated scales, accuracy
  gate). ResNet-18 reports img/s, a scoring LSTM reports tok/s
  (rows x seq tokens per wall second). The value is the
  quantized ResNet img/s; the ABSOLUTE contract bench.py enforces is
  ``accuracy_delta <= threshold`` for both models (the gate actually
  shipped int8 — a quantized record from a fallback fp32 backend would
  be a lie) and zero unwarmed dispatch signatures.

* ``bf16_train`` — the same micro training config stepped under
  ``MXTPU_PRECISION=fp32`` and ``=bf16`` (fused Module step, dynamic
  loss-scale guard armed in bf16): per-step wall time each, their
  ratio (the effective-TFLOPS delta at fixed FLOPs; the record names
  the device it was taken on), and
  the mean relative loss delta, which must stay inside
  ``LOSS_RTOL`` (bf16 rounding moves the loss, it must not move the
  optimization: documented tolerance 5e-2).

``run()`` returns one nested bench.py record; standalone:
``python benchmarks/bench_quant.py``.
"""
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

N_REQUESTS = 48
MAX_BATCH = 16
DEADLINE_S = 120.0
IMAGE_SHAPE = (32, 32, 3)
NUM_CLASSES = 16

LSTM_SEQ = 16
LSTM_VOCAB = 64
LSTM_HIDDEN = 64

TRAIN_STEPS = 12
LOSS_RTOL = 5e-2        # documented bf16-vs-fp32 loss tolerance


def _resnet_module():
    import mxnet_tpu as mx
    from mxnet_tpu import models
    sym = models.get_symbol("resnet", num_layers=18,
                            num_classes=NUM_CLASSES,
                            image_shape=",".join(map(str, IMAGE_SHAPE)))
    mod = mx.mod.Module(sym, label_names=[])
    mod.bind(data_shapes=[("data", (MAX_BATCH,) + IMAGE_SHAPE)],
             label_shapes=None, for_training=False)
    mx.random.seed(5)
    mod.init_params(mx.init.Xavier())
    return mod


def _lstm_module():
    """A scoring LSTM: token sequence in, per-sequence class scores out
    (the index input stays fp32 by the integer-semantics rule; the
    embedding table + recurrent/projection weights quantize)."""
    import mxnet_tpu as mx
    data = mx.sym.var("data")
    emb = mx.sym.Embedding(data, input_dim=LSTM_VOCAB,
                           output_dim=32, name="embed")
    emb = mx.sym.SwapAxis(emb, dim1=0, dim2=1)
    stack = mx.rnn.FusedRNNCell(LSTM_HIDDEN, num_layers=1, mode="lstm",
                                prefix="lstm_")
    out, _ = stack.unroll(LSTM_SEQ, inputs=emb, merge_outputs=True,
                          layout="TNC")
    last = mx.sym.SequenceLast(out)
    pred = mx.sym.FullyConnected(last, num_hidden=NUM_CLASSES,
                                 name="pred")
    net = mx.sym.SoftmaxOutput(pred, name="softmax")
    mod = mx.mod.Module(net, label_names=[])
    mod.bind(data_shapes=[("data", (MAX_BATCH, LSTM_SEQ))],
             label_shapes=None, for_training=False)
    mx.random.seed(11)
    mod.init_params(mx.init.Xavier())
    return mod


def _serve_burst(backend, name, rows):
    from mxnet_tpu.serving import InferenceServer
    server = InferenceServer(backend, name=name, max_batch=MAX_BATCH,
                             batch_wait=0.002, workers=1,
                             capacity=N_REQUESTS,
                             default_deadline=DEADLINE_S)
    server.warm_up()
    t0 = time.perf_counter()
    pending = [server.submit(r) for r in rows]
    latencies = []
    for req in pending:
        server.result(req)
        latencies.append(time.perf_counter() - t0)
    wall = time.perf_counter() - t0
    stats = server.stats()
    server.close()
    assert stats["completed"] == N_REQUESTS, stats
    return {"rps": N_REQUESTS / wall,
            "p99_s": float(np.percentile(latencies, 99)),
            "dispatches": stats["dispatches"],
            "unwarmed": stats["batching"]["unwarmed_dispatch_signatures"]}


def _quant_leg(make_module, make_row, calib_seed, name):
    """fp32 vs int8 burst for one model; returns the nested leg."""
    from mxnet_tpu.quant import quantize_backend
    from mxnet_tpu.serving import ModuleBackend
    mod = make_module()
    rng = np.random.RandomState(calib_seed)
    calib = [make_row(rng, MAX_BATCH) for _ in range(4)]
    qb = quantize_backend(mod, calib)
    report = qb.quant_report
    base = ModuleBackend(mod)
    base.load()
    req_rng = np.random.RandomState(calib_seed + 1)
    fp32_rows = [make_row(req_rng, 1) for _ in range(N_REQUESTS)]
    fp32 = _serve_burst(base, f"{name}-fp32", fp32_rows)
    int8_rows = ([qb.quantize_inputs(r) for r in fp32_rows]
                 if report.shipped else fp32_rows)
    quant = _serve_burst(qb, f"{name}-int8", int8_rows)
    return {
        "fp32_rps": round(fp32["rps"], 2),
        "quant_rps": round(quant["rps"], 2),
        "speedup": round(quant["rps"] / fp32["rps"], 3),
        "p99_s": {"fp32": round(fp32["p99_s"], 4),
                  "quant": round(quant["p99_s"], 4)},
        "unwarmed_signatures": fp32["unwarmed"] + quant["unwarmed"],
        "accuracy_delta": round(report.accuracy_delta, 5),
        "threshold": report.threshold,
        "shipped_quantized": report.shipped,
        "top1_agreement": report.top1_agreement,
    }


def bench_quant_serving():
    def resnet_row(rng, n):
        return {"data": rng.rand(n, *IMAGE_SHAPE).astype(np.float32)}

    def lstm_row(rng, n):
        return {"data": rng.randint(0, LSTM_VOCAB, (n, LSTM_SEQ))
                .astype(np.float32)}

    resnet = _quant_leg(_resnet_module, resnet_row, 0, "qbench-resnet")
    lstm = _quant_leg(_lstm_module, lstm_row, 7, "qbench-lstm")
    lstm["fp32_tok_s"] = round(lstm["fp32_rps"] * LSTM_SEQ, 1)
    lstm["quant_tok_s"] = round(lstm["quant_rps"] * LSTM_SEQ, 1)
    return {
        "metric": "quant_serving_throughput",
        "value": resnet["quant_rps"],
        "unit": "img/s",
        "resnet": resnet,
        "lstm": lstm,
        "config": {"requests": N_REQUESTS, "max_batch": MAX_BATCH,
                   "model": f"resnet18/{NUM_CLASSES}c + "
                            f"lstm{LSTM_HIDDEN}x{LSTM_SEQ}"},
    }


def _train_losses(precision):
    """TRAIN_STEPS fused Module steps at one precision; returns
    (losses, secs/step). The env knob is scoped here — the bench
    compares the two modes the way an operator flips them."""
    import mxnet_tpu as mx
    from mxnet_tpu import perf
    from mxnet_tpu.io import DataBatch, DataDesc
    prev = os.environ.get("MXTPU_PRECISION")
    os.environ["MXTPU_PRECISION"] = precision
    try:
        data = mx.sym.var("data")
        fc1 = mx.sym.FullyConnected(data, num_hidden=256, name="fc1")
        a1 = mx.sym.Activation(fc1, act_type="relu")
        fc2 = mx.sym.FullyConnected(a1, num_hidden=256, name="fc2")
        a2 = mx.sym.Activation(fc2, act_type="relu")
        fc3 = mx.sym.FullyConnected(a2, num_hidden=16, name="fc3")
        net = mx.sym.SoftmaxOutput(fc3, mx.sym.var("softmax_label"),
                                   name="softmax")
        mod = mx.mod.Module(net)
        mod.bind(data_shapes=[DataDesc("data", (64, 128))],
                 label_shapes=[DataDesc("softmax_label", (64,))])
        mx.random.seed(21)
        mod.init_params(mx.init.Xavier())
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": 0.05,
                                             "momentum": 0.9})
        stepper = perf.module_stepper(mod)
        assert stepper is not None
        rng = np.random.RandomState(0)
        batches = [DataBatch(
            data=[mx.nd.array(rng.rand(64, 128).astype(np.float32))],
            label=[mx.nd.array(rng.randint(0, 16, (64,))
                               .astype(np.float32))])
            for _ in range(TRAIN_STEPS)]
        stepper.step(batches[0])     # compile + settle
        losses = []
        t0 = time.perf_counter()
        for b in batches:
            outs = stepper.step(b)
            # per-step CE loss from the softmax probs (host readback is
            # part of both timed runs identically)
            probs = np.asarray(outs[0], np.float64)
            lab = np.asarray(b.label[0].asnumpy(), np.int64)
            losses.append(float(np.mean(
                -np.log(np.maximum(probs[np.arange(64), lab], 1e-12)))))
        secs = (time.perf_counter() - t0) / TRAIN_STEPS
        if precision == "bf16":
            assert stepper._fused.loss_scale_stats() is not None
        return losses, secs
    finally:
        if prev is None:
            os.environ.pop("MXTPU_PRECISION", None)
        else:
            os.environ["MXTPU_PRECISION"] = prev


def bench_bf16_train():
    from _device import device_stamp
    fp32_losses, fp32_s = _train_losses("fp32")
    bf16_losses, bf16_s = _train_losses("bf16")
    rel = [abs(a - b) / (abs(a) + 1e-12)
           for a, b in zip(fp32_losses, bf16_losses)]
    return {
        "metric": "bf16_train_step_speedup",
        # >1 means the bf16 step is faster (effective TFLOPS scale with
        # 1/step-time at fixed FLOPs)
        "value": round(fp32_s / bf16_s, 3),
        "unit": "x (fp32 step time / bf16 step time)",
        "fp32_step_s": round(fp32_s, 5),
        "bf16_step_s": round(bf16_s, 5),
        "loss_rel_delta": round(float(np.mean(rel)), 5),
        "loss_rtol": LOSS_RTOL,
        "loss_allclose": bool(np.mean(rel) <= LOSS_RTOL),
        "steps": TRAIN_STEPS,
        "device": device_stamp(),
    }


def run(quiet=False):
    from _device import device_stamp
    serving = bench_quant_serving()
    serving["device"] = device_stamp()
    serving["bf16_train"] = bench_bf16_train()
    if not quiet:
        print(json.dumps(serving))
    return serving


if __name__ == "__main__":
    from _device import require_chip
    require_chip()
    run()
