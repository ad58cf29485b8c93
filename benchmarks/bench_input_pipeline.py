#!/usr/bin/env python
"""Does the input pipeline keep the chip busy? (VERDICT r1 weak #6)

Compares ResNet-50 train step throughput with (a) one resident
synthetic device batch (the bench.py upper bound) against (b) the full
data path: host batches -> PrefetchingIter (background thread) ->
device_put per step, and (c) the same without prefetch. Reports the
utilization ratio (b)/(a).
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main_lstm():
    """LSTM-LM variant (--model lstm): per-step input is 64 KB of
    tokens against the ResNet variant's 154 MB of pixels, through the
    SAME pipeline (NDArrayIter -> PrefetchingIter -> device)."""
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.io import DataBatch, DataDesc, NDArrayIter, PrefetchingIter

    T, N, H, V = 256, 64, 1024, 10000
    iters = int(os.environ.get("BENCH_ITERS", "8"))
    data = mx.sym.var("data")
    embed = mx.sym.Embedding(data, input_dim=V, output_dim=H, name="embed")
    embed = mx.sym.SwapAxis(embed, dim1=0, dim2=1)
    stack = mx.rnn.FusedRNNCell(H, num_layers=2, mode="lstm",
                                prefix="lstm_")
    out, _ = stack.unroll(T, inputs=embed, merge_outputs=True,
                          layout="TNC")
    pred = mx.sym.Reshape(out, shape=(-1, H))
    pred = mx.sym.FullyConnected(pred, num_hidden=V, name="pred")
    label = mx.sym.Reshape(mx.sym.var("softmax_label"), shape=(-1,))
    net = mx.sym.SoftmaxOutput(pred, label, name="softmax")
    mod = mx.mod.Module(net, data_names=["data"],
                        label_names=["softmax_label"])
    mod.bind(data_shapes=[DataDesc("data", (N, T))],
             label_shapes=[DataDesc("softmax_label", (N, T))])
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.5})
    rng = np.random.RandomState(0)

    def sync():
        jax.block_until_ready(mod._exec.arg_dict["pred_weight"]._data)

    def step(b):
        mod.forward(b, is_train=True)
        mod.backward()
        mod.update()

    b0 = DataBatch([mx.nd.array(rng.randint(0, V, (N, T))
                                .astype(np.float32))],
                   [mx.nd.array(rng.randint(0, V, (N, T))
                                .astype(np.float32))])
    step(b0)
    sync()
    t0 = time.perf_counter()
    for _ in range(iters):
        step(b0)
    sync()
    dt_res = (time.perf_counter() - t0) / iters

    X = rng.randint(0, V, (iters * N, T)).astype(np.float32)
    Y = rng.randint(0, V, (iters * N, T)).astype(np.float32)
    it = PrefetchingIter(NDArrayIter(X, Y, batch_size=N,
                                     label_name="softmax_label"))
    for batch in it:  # warm (iterator-side compiles)
        step(batch)
    sync()
    it.reset()
    n = 0
    t0 = time.perf_counter()
    for batch in it:
        step(batch)
        n += 1
    sync()
    dt_pipe = (time.perf_counter() - t0) / n
    tok = N * T
    print(f"resident {dt_res * 1e3:.0f} ms/step "
          f"({tok / dt_res / 1e3:.0f}k tok/s)  pipeline "
          f"{dt_pipe * 1e3:.0f} ms/step ({tok / dt_pipe / 1e3:.0f}k "
          f"tok/s)  utilization {dt_res / dt_pipe:.1%}")


def main():
    import jax

    from mxnet_tpu import models
    from mxnet_tpu.io import DataBatch, DataDesc, NDArrayIter, PrefetchingIter
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh

    B = int(os.environ.get("BENCH_BATCH", "256"))
    iters = int(os.environ.get("BENCH_ITERS", "20"))
    mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
    sym = models.get_symbol("resnet", num_layers=50, num_classes=1000,
                            image_shape="224,224,3", dtype="bfloat16")
    tr = SPMDTrainer(
        sym, optimizer="sgd",
        optimizer_params=dict(learning_rate=0.1, momentum=0.9,
                              rescale_grad=1.0 / B),
        mesh=mesh, compute_dtype="bfloat16")
    tr.bind(data_shapes={"data": (B, 224, 224, 3)},
            label_shapes={"softmax_label": (B,)})

    rng = np.random.RandomState(0)

    sync = jax.block_until_ready

    # (a) resident device batch
    xd = jax.device_put(rng.rand(B, 224, 224, 3).astype(np.float32),
                        tr._in_shardings["data"])
    yd = jax.device_put(rng.randint(0, 1000, (B,)).astype(np.float32),
                        tr._in_shardings["softmax_label"])
    feed = {"data": xd, "softmax_label": yd}
    sync(tr.step(feed))  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        outs = tr.step(feed)
    sync(outs)
    dt_resident = (time.perf_counter() - t0) / iters

    # host dataset: a few distinct host batches (so device_put actually
    # transfers fresh data each step, like a real epoch). float32 from
    # the start — float64 staging would double host memory and time.
    nb = 4
    gen = np.random.default_rng(0)
    host_x = gen.standard_normal((nb * B, 224, 224, 3),
                                 dtype=np.float32)
    host_y = rng.randint(0, 1000, (nb * B,)).astype(np.float32)

    def run_iter(it):
        it = iter(it)
        n = 0
        t0 = time.perf_counter()
        outs = None
        for batch in it:
            outs = tr.step({"data": batch.data[0],
                            "softmax_label": batch.label[0]})
            n += 1
            if n >= iters:
                break
        sync(outs)
        return (time.perf_counter() - t0) / n

    # (c) plain iterator (synchronous H2D in the step loop)
    plain = NDArrayIter(host_x, host_y, batch_size=B,
                        label_name="softmax_label")
    run_iter(plain)  # warm
    plain.reset()
    dt_plain = run_iter(plain)

    # (b) prefetching iterator (background thread overlaps H2D prep)
    plain.reset()
    pre = PrefetchingIter(plain)
    dt_pre = run_iter(pre)

    print(f"resident batch : {dt_resident * 1e3:7.1f} ms/step "
          f"({B / dt_resident:7.1f} img/s)")
    print(f"plain iter     : {dt_plain * 1e3:7.1f} ms/step "
          f"({B / dt_plain:7.1f} img/s)")
    print(f"prefetch iter  : {dt_pre * 1e3:7.1f} ms/step "
          f"({B / dt_pre:7.1f} img/s)")
    print(f"pipeline utilization: plain {dt_resident / dt_plain:5.1%}  "
          f"prefetch {dt_resident / dt_pre:5.1%} of the resident-batch "
          "rate")


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from _device import require_chip
    print("device:", require_chip(), flush=True)
    if "--model" in sys.argv and "lstm" in sys.argv:
        main_lstm()
    else:
        main()
