#!/usr/bin/env python
"""Gluon LSTM language-model throughput (tokens/sec/chip).

BASELINE.md north star #2: "Gluon LSTM tokens/sec" — no published
reference number exists (the reference's CPU RNN was a stub and cuDNN
numbers weren't published for 0.11).

The step runs through the shared fused runtime (mxnet_tpu/perf): ONE
donated XLA program per step — forward, backward and the SGD update —
with the packed LSTM parameter pre-split into per-layer pieces at layout
time and bf16 compute over fp32 master weights (the same mixed-precision
policy as the ResNet-50 half of bench.py). ``--classic`` runs the
pre-round-6 forward/backward/update path for A/B attribution
(benchmarks/profile_lstm.py prints both).
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build(batch_size=64, seq_len=256, num_hidden=1024, num_layers=2,
          vocab=10000, momentum=0.0):
    """The exact bench model: Embedding -> fused LSTM stack -> FC -> softmax.

    Returns (module, batch) bound, initialized, optimizer-ready.
    ``momentum`` is 0 for the tracked single-chip metric (unchanged
    since round 2); bench_multichip passes 0.9 so the ZeRO
    optimizer-state measurement has per-slot state to shard."""
    import mxnet_tpu as mx
    from mxnet_tpu.io import DataBatch, DataDesc

    T, N, H, V = seq_len, batch_size, num_hidden, vocab
    data = mx.sym.var("data")
    embed = mx.sym.Embedding(data, input_dim=V, output_dim=H, name="embed")
    embed = mx.sym.SwapAxis(embed, dim1=0, dim2=1)  # NTC -> TNC
    stack = mx.rnn.FusedRNNCell(H, num_layers=num_layers, mode="lstm",
                                prefix="lstm_")
    out, _ = stack.unroll(T, inputs=embed, merge_outputs=True, layout="TNC")
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
                       optimizer_params={"learning_rate": 0.5,
                                         "momentum": momentum})
    rng = np.random.RandomState(0)
    batch = DataBatch(
        data=[mx.nd.array(rng.randint(0, V, (N, T)).astype(np.float32))],
        label=[mx.nd.array(rng.randint(0, V, (N, T)).astype(np.float32))])
    return mod, batch


def run(batch_size=64, seq_len=256, num_hidden=1024, num_layers=2,
        vocab=10000, iters=10, quiet=False, classic=False,
        compute_dtype="bfloat16"):
    """Measure LSTM training throughput; returns the metric record.

    Importable entry — bench.py calls this to emit the second north-star
    metric (BASELINE.md:64) alongside the ResNet-50 number."""
    import jax

    from _device import device_stamp

    T, N, H, V = seq_len, batch_size, num_hidden, vocab
    mod, batch = build(batch_size, seq_len, num_hidden, num_layers, vocab)

    if classic:
        impl = "classic"

        def step():
            mod.forward(batch, is_train=True)
            mod.backward()
            mod.update()

        def sync():
            jax.block_until_ready(mod._exec.arg_dict["pred_weight"]._data)
    else:
        from mxnet_tpu import perf
        stepper = perf.module_stepper(mod, compute_dtype=compute_dtype)
        if stepper is None:
            raise RuntimeError("bench module unexpectedly ineligible for "
                               "the fused step runtime")
        impl = f"fused-{compute_dtype or 'fp32'}"

        def step():
            stepper.step(batch)

        def sync():
            jax.block_until_ready(stepper._params)

    step()  # compile
    sync()
    t0 = time.time()
    for _ in range(iters):
        step()
    sync()
    dt = (time.time() - t0) / iters
    tps = N * T / dt
    # fwd flops/token: 8H^2 per LSTM layer (4 gates x two HxH matmuls)
    # + 2HV head + 0 embedding (gather); train step ~ 3x fwd
    flops_tok = 3 * (8 * H * H * num_layers + 2 * H * V)
    if not quiet:
        print(f"LSTM {num_layers}x{H} bs{N} T={T} [{impl}]: "
              f"{dt * 1000:.1f} ms/step, {tps:,.0f} tokens/sec/chip")
    return {
        "metric": "lstm_train_throughput",
        "value": round(tps, 0),
        "unit": "tokens/sec/chip",
        "config": f"{num_layers}x{H} bs{N} T={T} V={V}",
        "device": device_stamp(),
        "impl": impl,
        "effective_tflops": round(tps * flops_tok / 1e12, 1),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--num-hidden", type=int, default=1024)
    ap.add_argument("--num-layers", type=int, default=2)
    ap.add_argument("--vocab", type=int, default=10000)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--classic", action="store_true",
                    help="pre-round-6 forward/backward/update path")
    ap.add_argument("--fp32", action="store_true",
                    help="disable the bf16 compute cast")
    args = ap.parse_args()
    from _device import require_chip
    require_chip()
    print(json.dumps(run(args.batch_size, args.seq_len, args.num_hidden,
                         args.num_layers, args.vocab, args.iters,
                         classic=args.classic,
                         compute_dtype=None if args.fp32 else "bfloat16")))


if __name__ == "__main__":
    main()
