#!/usr/bin/env python
"""Headline benchmarks: the two north-star metrics of BASELINE.md:64.

1. ResNet-50 training throughput, images/sec/chip (baseline = 181.53
   img/s, the reference's best published single-GPU number — P100,
   docs/how_to/perf.md:157-188).
2. Gluon LSTM training throughput, tokens/sec/chip (no published
   reference number exists).

Needs an accelerator: without one it exits non-zero before it times
anything (benchmarks/_device.py). Prints ONE json line: the ResNet-50
record (metric/value/unit/vs_baseline) carrying ``device`` (platform,
device_kind, count — as JAX reports them), with the LSTM record nested
under ``lstm_train_tokens_per_sec``, the flagship-tier records nested
under ``flash_attention`` / ``moe_dispatch``, the pod-scale tier under
``multichip`` (data-parallel ResNet-50 + LSTM over the devices THIS
process holds, 1→N scaling, ZeRO optimizer-state bytes/chip —
benchmarks/bench_multichip.py; skipped, and says so, on fewer than two
devices), and the serving tier under ``serving`` (continuous-batching
requests/sec vs one-at-a-time at the same deadline + stateful decode
tokens/sec — benchmarks/bench_serving.py) and ``fleet`` (3-replica vs
1-replica aggregate requests/sec + p99 with a replica-kill chaos leg —
benchmarks/bench_fleet.py) and ``straggler`` (hedged vs unhedged p99
against a sticky-slow replica — benchmarks/bench_straggler.py) and
``ragged_serving`` (pad-waste token ratio dense vs packed at equal p99
with the warm-up matrix collapse — benchmarks/bench_ragged.py). Each
nested record carries its absolute contract flag; any violated contract
makes the exit code 2.

Everything runs in this one process, which holds the chip: nothing here
spawns a JAX child. The persistent-cache cold/warm pair needs two fresh
processes, so it is its own command (benchmarks/bench_compile_cache.py,
CPU children, labelled so) and not part of this line.

Batch/iters overridable via BENCH_BATCH / BENCH_ITERS — such smoke runs
print the ResNet record alone.
"""
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "benchmarks"))

BASELINE_IPS = 181.53  # ResNet-50 train img/s, P100 (docs/how_to/perf.md)


def bench_resnet(batch, iters, device):
    import jax
    from _device import peak_bf16_tflops
    from mxnet_tpu import models
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh

    peak_tflops = peak_bf16_tflops(device["device_kind"])  # unknown: error
    mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
    sym = models.get_symbol("resnet", num_layers=50, num_classes=1000,
                            image_shape="224,224,3", dtype="bfloat16")
    tr = SPMDTrainer(
        sym, optimizer="sgd",
        optimizer_params=dict(learning_rate=0.1, momentum=0.9,
                              rescale_grad=1.0 / batch),
        mesh=mesh, compute_dtype="bfloat16")
    tr.bind(data_shapes={"data": (batch, 224, 224, 3)},
            label_shapes={"softmax_label": (batch,)})

    rng = np.random.RandomState(0)
    x = jax.device_put(rng.rand(batch, 224, 224, 3).astype(np.float32),
                       tr._in_shardings["data"])
    y = jax.device_put(rng.randint(0, 1000, (batch,)).astype(np.float32),
                       tr._in_shardings["softmax_label"])
    feed = {"data": x, "softmax_label": y}

    for _ in range(2):  # compile + settle
        jax.block_until_ready(tr.step(feed))
    t0 = time.perf_counter()
    for _ in range(iters):
        outs = tr.step(feed)
    jax.block_until_ready(outs)
    dt = time.perf_counter() - t0

    ips = batch * iters / dt
    # ResNet-50 @224: ~4.1 GFLOP fwd/img, train step ~3x fwd. MFU is
    # against the published bf16 peak of the chip this ran on.
    eff_tflops = ips * 3 * 4.1e9 / 1e12
    return {
        "metric": "resnet50_train_throughput",
        "value": round(ips, 2),
        "unit": "images/sec/chip",
        "device": device,
        "vs_baseline": round(ips / BASELINE_IPS, 3),
        "effective_tflops": round(eff_tflops, 1),
        "mfu": round(eff_tflops / peak_tflops, 3),
    }


def bench_lstm():
    import bench_lstm as _lstm
    rec = _lstm.run(quiet=True)
    return {
        "value": rec["value"],
        "unit": rec["unit"],
        "config": rec["config"],
        "impl": rec.get("impl", "classic"),
        "effective_tflops": rec["effective_tflops"],
    }


def bench_flagship():
    """Flash-attention + MoE-dispatch records (flagship tier)."""
    import bench_flagship as _flag
    fa = _flag.bench_flash_attention(quiet=True)
    moe = _flag.bench_moe_dispatch(quiet=True)
    return fa, moe


def bench_multichip(device):
    """Pod-scale record: ResNet-50 + Gluon-LSTM data-parallel across
    every device this process holds, with ZeRO weight-update sharding —
    per-chip/aggregate throughput, 1→N aggregate scaling,
    optimizer-state bytes/chip measured from the live state pytrees,
    ZeRO-vs-replicated equivalence (benchmarks/bench_multichip.py).
    There is nothing to measure across one device: the record then says
    so instead of borrowing virtual CPU devices."""
    if device["count"] < 2:
        return {"skipped": "needs >= 2 devices; this process holds "
                           f"{device['count']}"}
    import bench_multichip as _mc
    return _mc.run(quiet=True, n_devices=device["count"])


def bench_serving():
    """Serving-throughput record (ISSUE 10): the same open-loop burst of
    single-row ResNet requests through the same server with the batch
    coalescer on (max_batch=16) vs off (one dispatch per request), both
    inside the same per-request deadline, plus the stateful LSTM decode
    tokens/sec with a mid-stream join/leave churn
    (benchmarks/bench_serving.py). The guarded value is the batched
    requests/sec; the acceptance contract (enforced absolutely in
    main()) is speedup >= 3x, decode bitwise == sequential, and zero
    retraces/unwarmed dispatch signatures."""
    import bench_serving as _srv
    return _srv.run(quiet=True)


def bench_ragged():
    """Pad-tax record (ISSUE 20): the same mixed-length open-loop burst
    through the deterministic server twice — dense client-padded rows
    vs sequence-packed rows with segment ids — plus the symbolic-dim
    warm-up matrix collapse (benchmarks/bench_ragged.py). The guarded
    value is the packed-leg requests/sec; the acceptance contract
    (enforced absolutely in main()) is pad-waste token ratio down >=
    3x, packed p99 within the stated band of dense, packed warmed
    signatures <= dense (compile count flat or lower), zero unwarmed
    signatures, zero lost requests, bitwise packed outputs."""
    import bench_ragged as _rg
    return _rg.run(quiet=True)


def bench_fleet():
    """Serving-fleet record (ISSUE 11): the same open-loop burst through
    a 3-replica FleetRouter vs a single replica (aggregate requests/sec
    + p99 each, scaling bounded by host_cores on this one-host bench),
    plus the replica-kill chaos leg — a seeded fleet.dispatch fault
    kills one replica mid-burst (benchmarks/bench_fleet.py). The
    guarded value is the 3-replica requests/sec; the acceptance
    contract (enforced absolutely in main()) is zero lost requests,
    the eviction+failover observable, and chaos p99 within the stated
    bound of the no-fault run."""
    import bench_fleet as _flt
    return _flt.run(quiet=True)


def bench_straggler():
    """Gray-failure record (ISSUE 19): the same open-loop burst against
    a 3-replica fleet whose r1 is wedged sticky-slow, served with
    hedged dispatch off vs on (slow vote-out disabled so the straggler
    stays in rotation — the comparison isolates hedging)
    (benchmarks/bench_straggler.py). The guarded value is the
    hedged-leg aggregate requests/sec; the acceptance contract
    (enforced absolutely in main()) is hedged p99 strictly below
    unhedged p99, hedges actually fired, and zero lost requests on
    both legs."""
    import bench_straggler as _strag
    return _strag.run(quiet=True)


def bench_quant():
    """Low-precision-tier records (ISSUE 15): the same open-loop burst
    through the coalescing server against the fp32 backend and the
    int8-PTQ backend (ResNet img/s + scoring-LSTM tok/s, p99 both,
    calibrated + accuracy-gated), plus the bf16-vs-fp32 training leg
    (fused Module step under MXTPU_PRECISION: step-time ratio — the
    chip round's MFU delta — and the mean relative loss delta, which
    must stay inside the documented tolerance). The absolute contracts
    enforced in main(): the gate actually SHIPPED int8 for both models
    with accuracy delta <= threshold, zero unwarmed dispatch
    signatures, and bf16 losses allclose (benchmarks/bench_quant.py)."""
    import bench_quant as _q
    return _q.run(quiet=True)


def bench_ckpt():
    """Checkpoint-stall record (ISSUE 16): the blocking sync write
    (serialize + atomic rename + manifest) vs the async
    snapshot-then-persist hiccup (host snapshot + submit) on the same
    param tree through the same commit machinery
    (benchmarks/bench_ckpt.py). The guarded value is the ratio
    sync_write_ms / async_hiccup_ms; the acceptance contract (enforced
    absolutely in main()) is hiccup < 10% of the sync write."""
    import bench_ckpt as _ck
    return _ck.run(quiet=True)


def main():
    from _device import require_chip
    device = require_chip()      # exits non-zero where there is no chip

    batch = int(os.environ.get("BENCH_BATCH", "256"))
    iters = int(os.environ.get("BENCH_ITERS", "20"))
    # the nested tiers run only on the default config — an overridden
    # BENCH_BATCH/BENCH_ITERS is a smoke run of the ResNet record
    default_config = ("BENCH_BATCH" not in os.environ
                      and "BENCH_ITERS" not in os.environ)

    record = bench_resnet(batch, iters, device)
    violated = False
    if default_config:
        record["lstm_train_tokens_per_sec"] = bench_lstm()

        fa, moe = bench_flagship()
        record["flash_attention"] = fa
        record["moe_dispatch"] = moe

        # pod-scale tier: the multichip record (ISSUE 9). The ZeRO
        # memory contract is enforced absolutely — optimizer state per
        # chip must actually shrink in ZeRO mode, and the ZeRO step must
        # reproduce the replicated step.
        mc = bench_multichip(device)
        if "skipped" not in mc:
            zrec = mc.get("zero", {})
            mc["zero_contract_violation"] = bool(
                float(zrec.get("reduction", 0.0)) < 2.0
                or not zrec.get("allclose_vs_replicated", False))
            violated |= mc["zero_contract_violation"]
        record["multichip"] = mc

        # serving tier: continuous batching (ISSUE 10). The acceptance
        # contract is absolute — the coalesced path must beat
        # one-at-a-time >= 3x at the same deadline, stateful decode must
        # be bitwise equal to sequential with zero retraces, and no
        # dispatch may leave the warmed signature set.
        srv = bench_serving()
        dec = srv.get("decode", {})
        srv["serving_contract_violation"] = bool(
            float(srv.get("batched_speedup", 0.0)) < 3.0
            or not dec.get("bitwise_vs_sequential", False)
            or int(dec.get("retraces", 1)) != 0
            or int(srv.get("unwarmed_signatures", 1)) != 0)
        violated |= srv["serving_contract_violation"]
        record["serving"] = srv

        # ragged tier: the pad tax (ISSUE 20). The acceptance contract
        # is absolute — the pad-waste token ratio must drop >= 3x vs the
        # dense leg at equal p99 (within the stated band), the packed
        # leg must warm no MORE signatures than the dense leg, no
        # dispatch may leave the warmed set, no request may be lost,
        # and every packed output must be bitwise equal to running the
        # member alone.
        rg = bench_ragged()
        rg["ragged_contract_violation"] = bool(
            float(rg.get("pad_waste_improvement", 0.0)) < 3.0
            or float(rg["p99_s"]["packed"])
            > float(rg["p99_s"]["dense"]) * float(rg["p99_band"])
            or int(rg["warmed_signatures"]["packed"])
            > int(rg["warmed_signatures"]["dense"])
            or int(rg.get("unwarmed_signatures", 1)) != 0
            or int(rg.get("lost", 1)) != 0
            or not rg.get("bitwise", False))
        violated |= rg["ragged_contract_violation"]
        record["ragged_serving"] = rg

        # fleet tier: replicated routing (ISSUE 11). The chaos contract
        # is absolute — killing a replica mid-burst must lose ZERO
        # requests (every one re-routed to a terminal response), the
        # eviction + failover must be observable, and the chaos p99
        # must stay within the stated bound of the no-fault run.
        flt = bench_fleet()
        chaos = flt.get("chaos", {})
        flt["fleet_contract_violation"] = bool(
            int(chaos.get("lost", 1)) != 0
            or int(chaos.get("evictions", 0)) < 1
            or int(chaos.get("failovers", 0)) < 1
            or not chaos.get("p99_within_bound", False))
        violated |= flt["fleet_contract_violation"]
        record["fleet"] = flt

        # gray-failure tier: hedged dispatch vs a sticky-slow replica
        # (ISSUE 19). The contract is absolute — hedging must strictly
        # beat the unhedged p99 against the same straggler, hedges must
        # have fired, and neither leg may lose a request.
        strag = bench_straggler()
        strag["straggler_contract_violation"] = bool(
            float(strag["hedged"].get("p99_s", 1.0))
            >= float(strag["unhedged"].get("p99_s", 0.0))
            or int(strag["hedged"].get("hedges", 0)) < 1
            or int(strag["hedged"].get("lost", 1)) != 0
            or int(strag["unhedged"].get("lost", 1)) != 0)
        violated |= strag["straggler_contract_violation"]
        record["straggler"] = strag

        # low-precision tier: int8 PTQ serving + bf16 training (ISSUE
        # 15). The absolute contract — accuracy delta <= threshold with
        # int8 actually shipped for BOTH models, zero unwarmed
        # signatures, and bf16 training losses allclose to fp32 within
        # the documented tolerance.
        q = bench_quant()
        bf16 = q.pop("bf16_train")
        bf16["loss_contract_violation"] = not bf16.get("loss_allclose",
                                                       False)
        q["quant_contract_violation"] = bool(
            not q["resnet"].get("shipped_quantized", False)
            or not q["lstm"].get("shipped_quantized", False)
            or float(q["resnet"].get("accuracy_delta", 1.0))
            > float(q["resnet"].get("threshold", 0.0))
            or float(q["lstm"].get("accuracy_delta", 1.0))
            > float(q["lstm"].get("threshold", 0.0))
            or int(q["resnet"].get("unwarmed_signatures", 1)) != 0
            or int(q["lstm"].get("unwarmed_signatures", 1)) != 0)
        violated |= q["quant_contract_violation"]
        violated |= bf16["loss_contract_violation"]
        record["quant_serving"] = q
        record["bf16_train"] = bf16

        # robustness tier: async checkpoint stall (ISSUE 16). The
        # absolute contract — the step loop's per-checkpoint stall
        # under the async writer stays below 10% of the blocking write.
        ck = bench_ckpt()
        ck["ckpt_contract_violation"] = bool(
            not ck.get("contract_hiccup_lt_0p1_sync", False))
        violated |= ck["ckpt_contract_violation"]
        record["ckpt_stall"] = ck

    print(json.dumps(record))
    if violated:
        raise SystemExit(2)


if __name__ == "__main__":
    main()
