#!/usr/bin/env python
"""The quickest proof that the framework still starts on the chip.

    python chip_smoke.py              # one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4    # only the four-chip path + its baseline

Drives the framework's main path once, through the entry points a user
calls, at the full width of the two models the repo tracks (weights are
random, from a seed; step counts and warm-up lists are cut, widths and
depth are not). Everything runs in THIS process — a chip belongs to one
process at a time, so nothing is spawned after JAX is touched. Phases:

* ``resnet50_train``  ResNet-50 224x224 bs256 bf16 through
  ``SPMDTrainer.bind/step`` on a one-device mesh (bench.py's recipe), one
  fixed batch, two of the steps fed through ``NDArrayIter``: the
  cross-entropy of the returned softmax falls and the step never retraces.
* ``lstm_train``      the LSTM LM of benchmarks/bench_lstm.py (2x1024, bs64,
  T=256, V=10000) through ``Module`` + ``perf.module_stepper``: bf16 steps
  and one fp32 step, with the Pallas cell (``tpu_custom_call``) in the
  lowered step program; then one step of that cell against the jnp cell
  (H=1024 fp32 and bf16, and H=650 — off the lane tiling — fp32).
* ``attention``       ``flash_attention`` dense causal fwd+bwd at B4 H16
  S2048 D64 bf16, then the masked kernel with ``lengths=`` and with
  ``segment_ids=``, each against its jnp reference on the chip; one traced
  ``CustomOp`` (a host callback inside a compiled program).
* ``serve``           a full-width ResNet-50 forward ``Module`` ->
  ``as_serving_backend()`` -> warmed ``InferenceServer(max_batch=16)``, a
  burst of 64 single-row requests inside their deadlines with no unwarmed
  signature; then ``InflightBatcher`` LSTM decode (width 1024) with one
  join/leave, bitwise equal to sequential.

Each phase prints one JSON line (seconds, compile seconds, cache counters,
peak device bytes, the platform of every output array's device). A phase
that raises, an array on a CPU device, or a first device that is not a
``tpu`` ends the run non-zero with no result line. The last stdout line of
a good run is exactly::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

``--rehearse`` is the CPU rehearsal (guide on-chip-measurement §2, 1-2):
tiny sizes, Pallas kernels through the interpreter, any platform accepted,
and the last line says ``"ok": false, "rehearsal": "passed"`` — a
rehearsal is never a chip run. For the four-chip rehearsal add
``XLA_FLAGS=--xla_force_host_platform_device_count=4``.
"""
import argparse
import functools
import gc
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(HERE, "benchmarks"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import compiler, models, perf  # noqa: E402
from mxnet_tpu.io import NDArrayIter  # noqa: E402
from mxnet_tpu.ndarray.ndarray import _as_jax  # noqa: E402
from mxnet_tpu.ops.pallas import attention as attn  # noqa: E402
from mxnet_tpu.ops.pallas import lstm as lstm_kernel  # noqa: E402
from mxnet_tpu.parallel import (SPMDTrainer, make_mesh,  # noqa: E402
                                state_bytes_per_device)
from mxnet_tpu.serving import InferenceServer, InflightBatcher  # noqa: E402

SEED = 0


# -- what every phase line carries -------------------------------------------

class Counters:
    """JAX's own compile events, summed since the process started."""

    DURATIONS = ("/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration",
                 "/jax/core/compile/backend_compile_duration")
    EVENTS = {"/jax/compilation_cache/compile_requests_use_cache": "requests",
              "/jax/compilation_cache/cache_hits": "hits",
              "/jax/compilation_cache/cache_misses": "misses"}

    def __init__(self):
        self.compile_seconds = 0.0
        self.cache = dict.fromkeys(self.EVENTS.values(), 0)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event in self.DURATIONS:
            self.compile_seconds += seconds

    def _event(self, event, **_):
        if event in self.EVENTS:
            self.cache[self.EVENTS[event]] += 1

    def snapshot(self):
        st = compiler.stats()
        return {"compile_seconds": self.compile_seconds,
                "jax_cache": dict(self.cache),
                "executable_store": {**st["cache"], **st["programs"]}}


def _delta(after, before):
    if isinstance(after, dict):
        return {k: _delta(v, before.get(k, 0)) for k, v in after.items()}
    return round(after - before, 3) if isinstance(after, float) \
        else after - before


def platforms(tree):
    """Platforms of the devices that hold the arrays of ``tree``."""
    found = set()
    for leaf in jax.tree_util.tree_leaves(tree):
        leaf = getattr(leaf, "_data", leaf)     # NDArray -> jax.Array
        found.update(d.platform for d in leaf.devices())
    return sorted(found)


def run_phase(name, fn, cfg, counters):
    """Run one phase (no try/except: a phase that raises ends the run),
    check where its outputs live, print its line."""
    before = counters.snapshot()
    t0 = time.perf_counter()
    outputs, extra = fn(cfg)
    jax.block_until_ready(jax.tree_util.tree_map(
        lambda x: getattr(x, "_data", x), outputs))
    seconds = time.perf_counter() - t0
    where = platforms(outputs)
    if where != [cfg.platform]:
        raise SystemExit(f"phase {name}: output arrays live on {where}, "
                         f"expected only {cfg.platform!r}")
    mem = jax.devices()[0].memory_stats() or {}
    line = {"phase": name, "seconds": round(seconds, 3),
            **_delta(counters.snapshot(), before),
            "peak_bytes_in_use": mem.get("peak_bytes_in_use"),
            "output_platforms": where, **extra}
    print(json.dumps(line), flush=True)
    del outputs
    gc.collect()


def cross_entropy(softmax, labels):
    p = np.asarray(softmax, np.float32).reshape(-1, softmax.shape[-1])
    idx = np.asarray(labels).reshape(-1).astype(np.int64)
    return float(-np.log(np.maximum(p[np.arange(idx.size), idx], 1e-30))
                 .mean())


def close(got, want, what, rtol=2e-2):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    err = float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-6))
    assert np.isfinite(got).all() and err <= rtol, \
        f"{what}: max error {err:.4f} of the reference's range > {rtol}"
    return round(err, 5)


def seed_all():
    np.random.seed(SEED)
    mx.random.seed(SEED)


# -- train, symbolic: ResNet-50 through SPMDTrainer ---------------------------

def resnet_trainer(cfg, mesh, batch):
    seed_all()
    side = cfg.image
    sym = models.get_symbol(
        "resnet", num_layers=cfg.resnet_layers, num_classes=cfg.classes,
        image_shape=f"{side},{side},3", dtype="bfloat16")
    tr = SPMDTrainer(
        sym, optimizer="sgd",
        optimizer_params=dict(learning_rate=0.1, momentum=0.9,
                              rescale_grad=1.0 / batch),
        mesh=mesh, compute_dtype="bfloat16")
    tr.bind(data_shapes={"data": (batch, side, side, 3)},
            label_shapes={"softmax_label": (batch,)})
    return tr


def resnet_batch(cfg, batch):
    rng = np.random.RandomState(SEED)
    x = rng.rand(batch, cfg.image, cfg.image, 3).astype(np.float32)
    y = rng.randint(0, cfg.classes, (batch,)).astype(np.float32)
    return x, y


def phase_resnet_train(cfg):
    batch = cfg.resnet_batch
    mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
    tr = resnet_trainer(cfg, mesh, batch)
    x, y = resnet_batch(cfg, batch)
    resident = {
        "data": jax.device_put(x, tr._in_shardings["data"]),
        "softmax_label": jax.device_put(
            y, tr._in_shardings["softmax_label"])}
    losses = [cross_entropy(tr.step(resident)[0], y)]      # compile step
    # the same batch through the normal input path: one NDArrayIter
    # epoch is one batch, so two epochs are two steps
    it = NDArrayIter(x, y, batch_size=batch)
    for _ in range(2):
        it.reset()
        for b in it:
            outs = tr.step({"data": b.data[0],
                            "softmax_label": b.label[0]})
            losses.append(cross_entropy(outs[0], y))
    for _ in range(cfg.resnet_steps - 3):
        outs = tr.step(resident)
        losses.append(cross_entropy(outs[0], y))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], \
        f"cross-entropy on the fixed batch did not fall: {losses}"
    assert tr.retrace_guard.count == 1, \
        f"the step retraced: {tr.retrace_guard.count} programs"
    return (outs, tr.params), {
        "model": f"resnet{cfg.resnet_layers} {cfg.image}x{cfg.image} "
                 f"bs{batch} bf16",
        "steps": len(losses), "cross_entropy": [round(v, 4) for v in losses],
        "programs": tr.retrace_guard.count,
        "reader": "NDArrayIter (python, in-memory; no native reader)"}


# -- train, Module + fused step: the LSTM LM ----------------------------------

def lstm_steps(cfg, compute_dtype, steps):
    import bench_lstm
    seed_all()
    mod, batch = bench_lstm.build(
        batch_size=cfg.lstm_batch, seq_len=cfg.lstm_seq,
        num_hidden=cfg.lstm_hidden, num_layers=2, vocab=cfg.lstm_vocab)
    stepper = perf.module_stepper(mod, compute_dtype=compute_dtype)
    assert stepper is not None, "Module ineligible for the fused step"
    labels = batch.label[0].asnumpy()
    losses = []
    for _ in range(steps):
        outs = stepper.step(batch)
        losses.append(cross_entropy(outs[0], labels))
    assert all(np.isfinite(losses)), losses
    # the program that just ran, lowered again from the same body and the
    # same arguments: is the recurrent cell the Pallas kernel?
    fused = stepper._fused
    inputs = {n: _as_jax(v, dtype=mod._exec.arg_dict[n].dtype)
              for n, v in mod._input_dict(batch).items()}
    lowered = jax.jit(fused._step_body).lower(
        stepper._params, stepper._states, stepper._aux, inputs,
        jax.random.PRNGKey(0), jnp.float32(0.5), jnp.float32(1.0))
    pallas = "tpu_custom_call" in lowered.as_text()
    assert pallas or cfg.rehearse, \
        "the LSTM step holds no tpu_custom_call: the jnp cell ran"
    assert stepper.guard.count == 1, stepper.guard.count
    return (outs, stepper._params), {
        "cross_entropy": [round(v, 4) for v in losses],
        "pallas_cell_in_program": pallas}


def lstm_cell_errors(cfg):
    """One step of the compiled Pallas cell against the jnp cell (its f32
    matmul at full precision) on the same inputs: the tiled kernel at the
    model's width in both dtypes, the whole-array kernel at a width off
    the 128-lane tiling."""
    impl = "interpret" if cfg.rehearse else "pallas"
    rng = np.random.RandomState(SEED)
    n = cfg.lstm_batch
    outs, errors = [], {}
    for hid, dtype in ((cfg.lstm_hidden, jnp.float32),
                       (cfg.lstm_hidden, jnp.bfloat16),
                       (cfg.lstm_unaligned_hidden, jnp.float32)):
        xproj, h, c, w = (
            jnp.asarray(rng.normal(0, scale, shape), dtype)
            for shape, scale in (((n, 4 * hid), 1.0), ((n, hid), 0.5),
                                 ((n, hid), 1.0),
                                 ((4 * hid, hid), hid ** -0.5)))
        got = jax.jit(functools.partial(
            lstm_kernel.lstm_cell_fused, impl=impl))(xproj, h, c, w)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(functools.partial(
                lstm_kernel.lstm_cell_fused, impl="jnp"))(xproj, h, c, w)
        tag = f"n{n}_h{hid}_{jnp.dtype(dtype).name}"
        errors[tag] = {name: close(g, r, f"lstm cell {tag} {name}")
                       for name, g, r in zip("hc", got, want)}
        outs.append(got)
    return outs, errors


def phase_lstm_train(cfg):
    out16, bf16 = lstm_steps(cfg, "bfloat16", cfg.lstm_steps)
    out32, fp32 = lstm_steps(cfg, None, 1)
    cells, errors = lstm_cell_errors(cfg)
    return (out16, out32, cells), {
        "model": f"lstm 2x{cfg.lstm_hidden} bs{cfg.lstm_batch} "
                 f"T={cfg.lstm_seq} V={cfg.lstm_vocab}",
        "bf16": bf16, "fp32": fp32, "cell_max_error_vs_jnp": errors}


# -- attention kernels against their references -------------------------------

@mx.operator.register("chip_smoke_sqr")
class _SqrProp(mx.operator.CustomOpProp):
    def __init__(self):
        super().__init__(need_top_grad=True)

    def infer_shape(self, in_shape):
        return in_shape, [in_shape[0]], []

    def create_operator(self, ctx, shapes, dtypes):
        return _Sqr()


class _Sqr(mx.operator.CustomOp):
    def forward(self, is_train, req, in_data, out_data, aux):
        self.assign(out_data[0], req[0], in_data[0] * in_data[0])

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        self.assign(in_grad[0], req[0], 2 * in_data[0] * out_grad[0])


def has_kernel(fn, *args):
    return "tpu_custom_call" in jax.jit(fn).lower(*args).as_text()


def phase_attention(cfg):
    b, h, s, d = cfg.attn_shape
    force = cfg.rehearse        # off the chip: the kernel, interpreted
    scale = 1.0 / d ** 0.5
    rng = np.random.RandomState(SEED)
    q, k, v = (jnp.asarray(rng.normal(0, 1, (b, h, s, d)), jnp.bfloat16)
               for _ in range(3))
    errors, kernels = {}, {}

    def flash(q, k, v):
        return attn.flash_attention(q, k, v, causal=True, force_pallas=force)

    def loss(fn):
        return lambda q, k, v: fn(q, k, v).astype(jnp.float32).sum()

    out = flash(q, k, v)
    ref = attn._attn_reference(q, k, v, True, scale)
    errors["dense_fwd"] = close(out, ref, "dense forward")
    grads = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
    ref_grads = jax.jit(jax.grad(
        loss(lambda q, k, v: attn._attn_reference(q, k, v, True, scale)),
        argnums=(0, 1, 2)))(q, k, v)
    for name, g, r in zip("qkv", grads, ref_grads):
        errors[f"dense_d{name}"] = close(g, r, f"dense d{name}")
    kernels["dense"] = has_kernel(jax.grad(loss(flash)), q, k, v)

    lengths = jnp.asarray(rng.randint(s // 4, s + 1, (b,)), jnp.int32)
    bounds = np.sort(rng.randint(1, s - s // 8, (b, 3)), axis=1)
    seg = np.zeros((b, s), np.int32)            # 3 documents, a pad tail
    for i, (lo, mid, hi) in enumerate(bounds):
        seg[i, :lo], seg[i, lo:mid], seg[i, mid:hi] = 1, 2, 3
    seg = jnp.asarray(seg)
    masked = {}
    for name, kw in (("lengths", {"lengths": lengths}),
                     ("segment_ids", {"segment_ids": seg})):
        def fn(q, k, v, kw=kw):
            return attn.flash_attention(q, k, v, causal=True,
                                        force_pallas=force, **kw)
        masked[name] = fn(q, k, v)
        ref = attn._masked_reference(q, k, v, kw.get("lengths"),
                                     kw.get("segment_ids"), True, scale)
        errors[name] = close(masked[name], ref, f"masked {name}")
        kernels[name] = has_kernel(fn, q, k, v)
    pad_rows = np.asarray(masked["segment_ids"], np.float32)[
        np.asarray(seg == 0)[:, None, :].repeat(h, 1)]
    assert not pad_rows.any(), "fully-masked rows must be exact 0"
    assert cfg.rehearse or all(kernels.values()), \
        f"a kernel gave way to a reference: {kernels}"

    # one traced CustomOp: a host callback inside a compiled program
    ex = mx.sym.Custom(mx.sym.var("data"), op_type="chip_smoke_sqr",
                       name="sq").simple_bind(data=(8, 128))
    xv = rng.rand(8, 128).astype(np.float32)
    (y,) = ex.forward(is_train=True, data=xv)
    ex.backward(out_grads=mx.nd.array(np.ones((8, 128), np.float32)))
    np.testing.assert_allclose(y.asnumpy(), xv ** 2, rtol=1e-5)
    np.testing.assert_allclose(ex.grad_arrays[0].asnumpy(), 2 * xv,
                               rtol=1e-5)
    return (out, grads, masked, y, ex.grad_arrays[0]), {
        "shape": f"B{b} H{h} S{s} D{d} bf16 causal",
        "max_error_vs_reference": errors, "tpu_custom_call": kernels,
        "traced_custom_op": "ok"}


# -- serve: batched ResNet-50 + stateful LSTM decode --------------------------

def resnet_server(cfg):
    seed_all()
    side = cfg.image
    sym = models.get_symbol(
        "resnet", num_layers=cfg.resnet_layers, num_classes=cfg.classes,
        image_shape=f"{side},{side},3", dtype="bfloat16")
    mod = mx.mod.Module(sym, label_names=[], context=mx.tpu(0))
    mod.bind(data_shapes=[("data", (cfg.max_batch, side, side, 3))],
             label_shapes=None, for_training=False)
    mod.init_params(mx.init.Xavier())
    server = InferenceServer(
        mod.as_serving_backend(), name="chip-smoke",
        max_batch=cfg.max_batch, batch_wait=0.002, workers=1,
        capacity=cfg.requests, default_deadline=cfg.deadline)
    return mod, server


def decode_batcher(cfg, name):
    seed_all()
    hid, cap = cfg.decode_hidden, cfg.decode_capacity
    cell = mx.rnn.LSTMCell(hid, prefix="dec_")
    out, (nh, nc) = cell(mx.sym.Variable("data"),
                         [mx.sym.Variable("h"), mx.sym.Variable("c")])
    logits = mx.sym.FullyConnected(out, name="proj",
                                   num_hidden=cfg.lstm_vocab)
    mod = mx.mod.Module(mx.sym.Group([logits, nh, nc]),
                        data_names=["data", "h", "c"], label_names=[],
                        context=mx.tpu(0))
    mod.bind(data_shapes=[("data", (cap, hid)), ("h", (cap, hid)),
                          ("c", (cap, hid))],
             label_shapes=None, for_training=False)
    mod.init_params(mx.init.Xavier())
    return mod, InflightBatcher(mod.as_decode_backend(["h", "c"]),
                                name=name).warm_up()


def phase_serve(cfg):
    mod, server = resnet_server(cfg)
    server.warm_up()
    rng = np.random.RandomState(SEED)
    rows = [rng.rand(1, cfg.image, cfg.image, 3).astype(np.float32)
            for _ in range(cfg.requests)]
    t0 = time.perf_counter()
    pending = [server.submit({"data": x}) for x in rows]
    answers = [server.result(req) for req in pending]
    burst_s = time.perf_counter() - t0
    stats = server.stats()
    server.close()
    missed = stats.get("deadline_queued", 0) + stats.get(
        "deadline_inflight", 0)
    unwarmed = stats["batching"]["unwarmed_dispatch_signatures"]
    assert stats["completed"] == cfg.requests and missed == 0, stats
    assert unwarmed == 0, stats["batching"]
    assert all(np.isfinite(a[0]).all() and a[0].shape == (1, cfg.classes)
               for a in answers)
    served_outputs = mod.get_outputs()

    # stateful decode: every slot fed each step, sequence 0 leaves and a
    # new one joins its slot half way (benchmarks/bench_serving.py)
    cap, steps = cfg.decode_capacity, cfg.decode_steps
    tokens = rng.rand(cap + 1, steps, cfg.decode_hidden).astype(np.float32)
    dmod, b = decode_batcher(cfg, "chip-smoke-decode")
    slots = [b.join() for _ in range(cap)]
    traced = {0: [], cap: []}
    churn, in_slot0 = steps // 2, 0
    for t in range(steps):
        if t == churn:
            b.leave(slots[0])
            slots[0] = b.join()
            in_slot0 = cap
        feed = {slots[i]: {"data": tokens[i, t]} for i in range(1, cap)}
        feed[slots[0]] = {
            "data": tokens[in_slot0, t - churn if t >= churn else t]}
        traced[in_slot0].append(b.step(feed)[slots[0]][0])
    dstats = b.stats()
    for seq, n in ((0, churn), (cap, steps - churn)):
        _, solo = decode_batcher(cfg, f"chip-smoke-decode-solo{seq}")
        s = solo.join()
        for t in range(n):
            alone = solo.step({s: {"data": tokens[seq, t]}})[s][0]
            assert np.array_equal(alone, traced[seq][t]), \
                f"decode of sequence {seq} differs from sequential at {t}"
    assert int(dstats["retraced"]) == 0, dstats
    return (served_outputs, dmod.get_outputs()), {
        "model": f"resnet{cfg.resnet_layers} {cfg.image}x{cfg.image} fwd, "
                 f"max_batch {cfg.max_batch}",
        "requests": cfg.requests, "burst_seconds": round(burst_s, 3),
        "dispatches": stats["dispatches"], "deadline_misses": missed,
        "unwarmed_signatures": unwarmed,
        "warmup_compiles": stats.get("warmup_compiles", 0),
        "warmup_cache_hits": stats.get("warmup_cache_hits", 0),
        "decode": {"hidden": cfg.decode_hidden, "capacity": cap,
                   "tokens": dstats["tokens"], "steps": dstats["steps"],
                   "retraces": int(dstats["retraced"]),
                   "bitwise_vs_sequential": True}}


# -- --chips 4: data-parallel ZeRO-1 against the same steps on one device -----

def phase_four_chips(cfg):
    devs = jax.devices()
    if len(devs) < 4:
        raise SystemExit(f"--chips 4 needs 4 devices, jax.devices() has "
                         f"{len(devs)}")
    batch = 4 * cfg.per_chip_batch
    x, y = resnet_batch(cfg, batch)
    feed = {"data": x, "softmax_label": y}

    def three_steps(mesh):
        tr = resnet_trainer(cfg, mesh, batch)
        losses = [cross_entropy(tr.step(feed)[0], y) for _ in range(3)]
        assert tr.retrace_guard.count == 1, tr.retrace_guard.count
        return tr, losses

    one, losses_1 = three_steps(make_mesh({"data": 1}, devices=devs[:1]))
    bytes_1 = state_bytes_per_device(one.states)
    del one
    gc.collect()
    os.environ["MXTPU_ZERO"] = "1"
    four, losses_4 = three_steps(make_mesh({"data": 4}, devices=devs[:4]))
    bytes_4 = state_bytes_per_device(four.states)
    np.testing.assert_allclose(losses_4, losses_1, rtol=2e-2)
    assert losses_4[-1] < losses_4[0], losses_4
    ratio = bytes_4 / bytes_1
    assert 0.25 <= ratio <= 0.30, \
        f"ZeRO-1 optimizer state per chip is {ratio:.3f} of one device's"
    spread = {n: len(p.sharding.device_set) for n, p in four.params.items()}
    assert set(spread.values()) == {4}, \
        {n: c for n, c in spread.items() if c != 4}
    on = {d for leaf in jax.tree_util.tree_leaves(four.states)
          for d in leaf.sharding.device_set}
    assert on == set(devs[:4]), on
    return (four.params, four.states), {
        "model": f"resnet{cfg.resnet_layers} {cfg.image}x{cfg.image} bf16, "
                 f"{cfg.per_chip_batch}/chip x 4, MXTPU_ZERO=1",
        "cross_entropy_4_chips": [round(v, 4) for v in losses_4],
        "cross_entropy_1_device": [round(v, 4) for v in losses_1],
        "opt_state_bytes_per_chip": {"one_device": bytes_1,
                                     "four_chips_zero1": bytes_4,
                                     "ratio": round(ratio, 4)},
        "params_on_4_distinct_devices": len(spread)}


# -- sizes ---------------------------------------------------------------------

REAL = dict(
    resnet_layers=50, image=224, classes=1000, resnet_batch=256,
    resnet_steps=5, per_chip_batch=64,
    lstm_hidden=1024, lstm_batch=64, lstm_seq=256, lstm_vocab=10000,
    lstm_unaligned_hidden=650, lstm_steps=3, attn_shape=(4, 16, 2048, 64),
    max_batch=16, requests=64, deadline=60.0,
    decode_hidden=1024, decode_capacity=8, decode_steps=32)

TINY = dict(
    resnet_layers=18, image=32, classes=16, resnet_batch=8,
    resnet_steps=5, per_chip_batch=2,
    lstm_hidden=128, lstm_batch=8, lstm_seq=8, lstm_vocab=64,
    lstm_unaligned_hidden=24, lstm_steps=2, attn_shape=(2, 2, 256, 32),
    max_batch=4, requests=12, deadline=120.0,
    decode_hidden=128, decode_capacity=4, decode_steps=8)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the four-chip data-parallel ZeRO path "
                         "and the one-device steps it is compared with")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: tiny sizes, interpreted kernels, "
                         "any platform; never prints an ok line")
    args = ap.parse_args()

    first = jax.devices()[0]
    device = {"platform": first.platform, "kind": first.device_kind,
              "count": len(jax.devices())}
    if first.platform != "tpu" and not args.rehearse:
        raise SystemExit(f"no TPU: jax.devices() is {jax.devices()}")
    cfg = argparse.Namespace(**(TINY if args.rehearse else REAL),
                             rehearse=args.rehearse,
                             platform=first.platform)
    if args.rehearse:
        # off the chip the dispatcher picks the jnp cell; the rehearsal
        # wants the kernels' control flow, through the interpreter
        lstm_kernel.lstm_cell_fused = functools.partial(
            lstm_kernel.lstm_cell_fused, impl="interpret")
        lstm_kernel.lstm_recurrence = functools.partial(
            lstm_kernel.lstm_recurrence, impl="interpret")

    print(json.dumps({"start": device,
                      "jax": jax.__version__,
                      "jax_cache_dir": compiler.cache.jax_cache_dir()}),
          flush=True)
    counters = Counters()
    if args.chips == 4:
        run_phase("resnet50_4chips_zero1", phase_four_chips, cfg, counters)
    else:
        run_phase("resnet50_train", phase_resnet_train, cfg, counters)
        run_phase("lstm_train", phase_lstm_train, cfg, counters)
        run_phase("attention", phase_attention, cfg, counters)
        run_phase("serve", phase_serve, cfg, counters)
    if args.rehearse:
        print(json.dumps({"ok": False, "rehearsal": "passed",
                          "device": device}))
    else:
        print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
