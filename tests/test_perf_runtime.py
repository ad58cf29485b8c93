"""Shared step runtime (mxnet_tpu/perf): donation equivalence, retrace
guarding, packed-RNN layout hoisting, and PRNG gating.

The donation-equivalence contract: one training step with donated
buffers is BITWISE identical to the same step without donation, for
every front end (Module, Gluon Trainer, SPMDTrainer) — donation changes
buffer lifetime, never values. The compile-count contract: steps 2..N of
``Module.fit`` hit the trace cache (zero retraces).

All CPU, fake data, tiny shapes (docs/how_to/performance.md).
"""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, perf
from mxnet_tpu.gluon import nn
from mxnet_tpu.io import DataBatch, DataDesc, NDArrayIter
from mxnet_tpu.perf.step_runtime import CompileGuard, PackedRNNLayout


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------

def lstm_module(opt="sgd", opt_params=None, seed=7):
    """Micro version of the bench_lstm model (embed -> fused LSTM -> FC
    -> softmax) — exercises the packed-parameter piece layout."""
    data = mx.sym.var("data")
    embed = mx.sym.Embedding(data, input_dim=40, output_dim=16, name="embed")
    embed = mx.sym.SwapAxis(embed, dim1=0, dim2=1)
    stack = mx.rnn.FusedRNNCell(16, num_layers=2, mode="lstm",
                                prefix="lstm_")
    out, _ = stack.unroll(6, inputs=embed, merge_outputs=True, layout="TNC")
    pred = mx.sym.Reshape(out, shape=(-1, 16))
    pred = mx.sym.FullyConnected(pred, num_hidden=40, name="pred")
    label = mx.sym.Reshape(mx.sym.var("softmax_label"), shape=(-1,))
    net = mx.sym.SoftmaxOutput(pred, label, name="softmax")
    mod = mx.mod.Module(net)
    mod.bind(data_shapes=[DataDesc("data", (4, 6))],
             label_shapes=[DataDesc("softmax_label", (4, 6))])
    mx.random.seed(seed)
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(optimizer=opt, optimizer_params=dict(
        opt_params or {"learning_rate": 0.5, "momentum": 0.9}))
    return mod


def lstm_batch():
    rng = np.random.RandomState(0)
    return DataBatch(
        data=[mx.nd.array(rng.randint(0, 40, (4, 6)).astype(np.float32))],
        label=[mx.nd.array(rng.randint(0, 40, (4, 6)).astype(np.float32))])


def mlp_symbol():
    data = mx.sym.var("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu")
    fc2 = mx.sym.FullyConnected(act, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(fc2, mx.sym.var("softmax_label"),
                                name="softmax")


def params_of(mod):
    arg, aux = mod.get_params()
    return {n: v.asnumpy() for n, v in arg.items()}


# ---------------------------------------------------------------------------
# donation equivalence — Module / Gluon / SPMDTrainer
# ---------------------------------------------------------------------------

def _module_two_steps(donate):
    mod = lstm_module()
    stepper = perf.module_stepper(mod, donate=donate)
    assert stepper is not None
    for _ in range(2):
        stepper.step(lstm_batch())
    return params_of(mod)


def _gluon_two_steps(donate):
    mx.random.seed(11)
    np.random.seed(11)
    net = nn.Sequential(prefix="deq_")
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1, "momentum": 0.9})
    tr._donate_buffers = donate
    x = mx.nd.array(np.random.RandomState(3).rand(8, 12))
    y = mx.nd.array(np.random.RandomState(4).randint(0, 4, (8,)))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    for _ in range(2):
        with mx.autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        tr.step(8)
    assert tr._fused_apply not in (None, False)  # fused path taken
    return {k: v.data().asnumpy() for k, v in net.collect_params().items()}


def _spmd_two_steps(donate):
    import jax
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh

    rng = np.random.RandomState(0)
    x = rng.rand(8, 12).astype(np.float32)
    y = rng.randint(0, 4, (8,)).astype(np.float32)
    mx.random.seed(21)      # identical parameter init across runs
    mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
    tr = SPMDTrainer(mlp_symbol(), optimizer="sgd",
                     optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
                     mesh=mesh, donate_buffers=donate)
    tr.bind(data_shapes={"data": (8, 12)},
            label_shapes={"softmax_label": (8,)})
    for _ in range(2):
        tr.step({"data": x, "softmax_label": y})
    arg, _ = tr.get_params()
    return {n: v.asnumpy() for n, v in arg.items()}


@pytest.mark.parametrize("two_steps", [
    _module_two_steps, _gluon_two_steps, _spmd_two_steps],
    ids=["module", "gluon_trainer", "spmd_trainer"])
def test_donation_equivalence(two_steps):
    donated, undonated = two_steps(True), two_steps(False)
    assert donated.keys() == undonated.keys() and donated
    for n in donated:
        assert np.array_equal(donated[n], undonated[n]), n


# ---------------------------------------------------------------------------
# one step body, two front ends (SPMDTrainer, Module through module_stepper)
# ---------------------------------------------------------------------------

def two_block_symbol():
    """An MLP whose two hidden layers are blocks that ask for a checkpoint."""
    x = mx.sym.var("data")
    for k in range(2):
        with mx.AttrScope(__block__=f"block{k}", __remat__="block"):
            x = mx.sym.FullyConnected(x, num_hidden=16, name=f"fc{k}")
            x = mx.sym.Activation(x, act_type="tanh", name=f"act{k}")
    x = mx.sym.FullyConnected(x, num_hidden=4, name="head")
    return mx.sym.SoftmaxOutput(x, mx.sym.var("softmax_label"),
                                name="softmax")


def _spmd_front_end(symbol, **kw):
    """(the FusedStep, its (params, states, aux)) of a bound SPMDTrainer."""
    import jax
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh
    tr = SPMDTrainer(symbol, optimizer="sgd",
                     optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
                     mesh=make_mesh({"data": 1}, devices=jax.devices()[:1]),
                     **kw)
    tr.bind({"data": (8, 12)}, {"softmax_label": (8,)})
    return tr._fused, (tr.params, tr.states, tr.aux)


def _module_front_end(symbol, **kw):
    """The same of a Module through ``module_stepper``."""
    mod = mx.mod.Module(symbol)
    mod.bind(data_shapes=[DataDesc("data", (8, 12))],
             label_shapes=[DataDesc("softmax_label", (8,))])
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd", optimizer_params={
        "learning_rate": 0.1, "momentum": 0.9})
    stepper = perf.module_stepper(mod, **kw)
    assert stepper is not None
    return stepper._fused, (stepper._params, stepper._states, stepper._aux)


@pytest.mark.parametrize("front_end", [_spmd_front_end, _module_front_end],
                         ids=["spmd_trainer", "module"])
def test_blocks_are_checkpoints_under_every_front_end(front_end):
    """``__remat__="block"`` reaches the evaluator from the one place that
    builds it: the lowered step holds one checkpoint a block and the
    step body's scopes, whichever front end bound it."""
    import jax
    import jax.numpy as jnp
    fused, state = front_end(two_block_symbol(), compute_dtype="bfloat16")
    assert fused._opt_res.remat_blocks
    inputs = {"data": jnp.zeros((8, 12), jnp.float32),
              "softmax_label": jnp.zeros((8,), jnp.float32)}
    with fused._scoped():
        text = jax.jit(fused._step_body).lower(
            *state, inputs, jax.random.PRNGKey(0), jnp.float32(0.1),
            jnp.float32(1.0)).as_text(debug_info=True)
    # a checkpoint keeps its inside from being shared with the backward:
    # one barrier a block
    assert text.count("optimization_barrier") == 2
    assert "cast_params" in text and "optimizer_update" in text


def test_spmd_trainer_and_module_fit_take_the_same_steps():
    """One device, float32, SGD with momentum, the same symbol, weights
    and three batches: ``SPMDTrainer`` on a one-device mesh and
    ``Module.fit`` run one step body, so parameters and momentum come out
    equal bit for bit."""
    import jax
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh

    rng = np.random.RandomState(5)
    x = rng.rand(24, 12).astype(np.float32)
    y = rng.randint(0, 4, (24,)).astype(np.float32)
    opt = {"learning_rate": 0.1, "momentum": 0.9, "rescale_grad": 1.0 / 8}
    start = {"fc1_weight": rng.randn(16, 12), "fc1_bias": rng.randn(16),
             "fc2_weight": rng.randn(4, 16), "fc2_bias": rng.randn(4)}
    start = {n: v.astype(np.float32) * 0.3 for n, v in start.items()}

    mod = mx.mod.Module(mlp_symbol())
    mod.fit(NDArrayIter(x, y, batch_size=8), num_epoch=1, optimizer="sgd",
            optimizer_params=dict(opt), eval_metric="acc",
            arg_params={n: mx.nd.array(v) for n, v in start.items()},
            aux_params={})
    stepper = mod._fused_stepper
    assert stepper is not None and stepper.guard.count == 1

    tr = SPMDTrainer(mlp_symbol(), optimizer="sgd",
                     optimizer_params=dict(opt),
                     mesh=make_mesh({"data": 1}, devices=jax.devices()[:1]))
    tr.bind({"data": (8, 12)}, {"softmax_label": (8,)}, arg_params=start)
    tr.fit(NDArrayIter(x, y, batch_size=8), num_epoch=1)
    assert tr.retrace_guard.count == 1

    for n in start:
        assert np.array_equal(np.asarray(tr.params[n]),
                              np.asarray(stepper._params[n])), n
        # the trainer keeps the rule's own state, the stepper a list of
        # one state a leaf: the one body reads either
        momentum = np.asarray(tr.states[n])
        assert np.any(momentum != 0)
        assert np.array_equal(momentum, np.asarray(stepper._states[n][0])), n


# ---------------------------------------------------------------------------
# compile-count: Module.fit never retraces after the first step
# ---------------------------------------------------------------------------

def test_module_fit_zero_retraces_across_100_steps():
    rng = np.random.RandomState(0)
    n = 400                                 # 100 batches of 4
    it = NDArrayIter(rng.rand(n, 12).astype(np.float32),
                     rng.randint(0, 4, (n,)).astype(np.float32),
                     batch_size=4)
    mod = mx.mod.Module(mlp_symbol())
    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1},
            initializer=mx.init.Xavier(), eval_metric="acc")
    stepper = mod._fused_stepper
    assert stepper not in (None, False), "fit did not take the fused path"
    # one compile total: the 2nd and the 100th step hit the trace cache
    assert stepper.guard.count == 1, stepper.guard.count
    assert not stepper.guard.retraced

    # a second epoch over the same module must not retrace either
    it.reset()
    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1},
            initializer=None, allow_missing=True, force_init=True,
            eval_metric="acc")
    stepper2 = mod._fused_stepper
    assert stepper2 not in (None, False)
    assert stepper2.guard.count == 1


def test_fit_fused_matches_imperative_path():
    rng = np.random.RandomState(0)
    xs = rng.rand(32, 12).astype(np.float32)
    ys = rng.randint(0, 4, (32,)).astype(np.float32)

    def run(fused):
        if not fused:
            os.environ["MXTPU_FUSED_STEP"] = "0"
        try:
            it = NDArrayIter(xs, ys, batch_size=8)
            mx.random.seed(5)
            mod = mx.mod.Module(mlp_symbol())
            mod.fit(it, num_epoch=2, optimizer="sgd",
                    optimizer_params={"learning_rate": 0.1,
                                      "momentum": 0.9},
                    initializer=mx.init.Xavier(), eval_metric="acc")
            return params_of(mod), mod._fused_stepper
        finally:
            os.environ.pop("MXTPU_FUSED_STEP", None)

    fused_params, stepper = run(True)
    imp_params, no_stepper = run(False)
    assert stepper not in (None, False)
    assert no_stepper in (None, False)
    for n in fused_params:
        np.testing.assert_allclose(fused_params[n], imp_params[n],
                                   rtol=2e-5, atol=2e-6, err_msg=n)


def test_fused_optimizer_state_survives_checkpoint(tmp_path):
    batch = lstm_batch()
    mod = lstm_module()
    stepper = perf.module_stepper(mod)
    for _ in range(3):
        stepper.step(batch)
    states_file = str(tmp_path / "opt.states")
    mod.save_optimizer_states(states_file)     # forces the sync path
    import pickle
    states, opt = pickle.loads(open(states_file, "rb").read())
    # momentum state exists, is packed-shaped, and counters advanced
    assert states and all(v is not None for v in states.values())
    assert opt.num_update == 3
    packed = mod._exec.arg_dict["lstm_parameters"]
    idx = mod._param_names.index("lstm_parameters")
    assert states[idx].shape == packed.shape
    assert float(np.abs(states[idx].asnumpy()).max()) > 0


def test_reinit_optimizer_after_fused_training_keeps_progress():
    # init_optimizer(force_init=True) after fused steps must flush the
    # stepper's donated state first — not orphan it in dead buffers
    batch = lstm_batch()
    mod = lstm_module()
    stepper = perf.module_stepper(mod)
    for _ in range(2):
        stepper.step(batch)
    trained = {n: v._data for n, v in zip(
        ("pred_weight",), (mod._exec.arg_dict["pred_weight"],))}
    mod.init_optimizer(optimizer="adam",
                       optimizer_params={"learning_rate": 0.01},
                       force_init=True)
    arg, _ = mod.get_params()           # must not raise on deleted arrays
    assert np.isfinite(arg["pred_weight"].asnumpy()).all()
    # and training continues on the NEW optimizer through a fresh stepper
    assert mod._fused_stepper is None
    st2 = perf.module_stepper(mod)
    st2.step(batch)
    del trained


def test_imperative_update_between_fused_steps_is_not_lost():
    # fused steps -> one imperative forward_backward+update -> fused
    # again must follow the all-imperative trajectory (allclose)
    batch = lstm_batch()

    def mixed():
        mod = lstm_module()
        st = perf.module_stepper(mod)
        st.step(batch)
        st.step(batch)
        mod.forward_backward(batch)
        mod.update()
        mod._fused_train_step()(batch)      # back on the fused path
        return params_of(mod)

    def imperative():
        os.environ["MXTPU_FUSED_STEP"] = "0"
        try:
            mod = lstm_module()
            for _ in range(4):
                mod.forward_backward(batch)
                mod.update()
            return params_of(mod)
        finally:
            os.environ.pop("MXTPU_FUSED_STEP", None)

    a, b = mixed(), imperative()
    for n in a:
        np.testing.assert_allclose(a[n], b[n], rtol=2e-5, atol=2e-6,
                                   err_msg=n)


def test_borrow_optimizer_drops_stale_fused_step():
    batch = lstm_batch()
    mod = lstm_module()
    stepper = perf.module_stepper(mod)
    stepper.step(batch)
    other = lstm_module(opt="adam", opt_params={"learning_rate": 0.01})
    mod.borrow_optimizer(other)
    assert mod._fused_stepper is None   # old sgd-momentum trace dropped
    arg, _ = mod.get_params()           # synced before the drop
    assert np.isfinite(arg["pred_weight"].asnumpy()).all()


# ---------------------------------------------------------------------------
# packed-RNN parameter layout
# ---------------------------------------------------------------------------

def test_gluon_frozen_layer_mid_training_is_not_a_retrace():
    # freezing a layer changes the live parameter set: a legitimate new
    # program, which must not trip the guard even in strict mode
    mx.random.seed(13)
    np.random.seed(13)
    net = nn.Sequential(prefix="frz_")
    with net.name_scope():
        net.add(nn.Dense(8, activation="relu"), nn.Dense(4))
    net.initialize()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1, "momentum": 0.9})
    x = mx.nd.array(np.random.rand(4, 6))
    y = mx.nd.array(np.random.randint(0, 4, (4,)))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    def one_step():
        with mx.autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        tr.step(4)

    os.environ["MXTPU_RETRACE_STRICT"] = "1"
    try:
        one_step()
        first = list(net.collect_params().values())[0]
        first.grad_req = "null"         # staged fine-tuning: freeze
        one_step()                      # must not raise
        one_step()                      # same signature again: cached
    finally:
        os.environ.pop("MXTPU_RETRACE_STRICT", None)
    assert tr._fused_apply.guard.count == 2     # one per signature
    assert tr._fused_apply.guard.expected == 2


def test_packed_layout_input_size_inversion():
    from mxnet_tpu.ops.rnn_ops import rnn_param_size
    lo = PackedRNNLayout("p", 16, 3, "gru", True)
    total = rnn_param_size(3, 24, 16, "gru", True)
    assert lo._resolve_input_size(total) == 24
    bogus = PackedRNNLayout("p", 16, 3, "gru", True)
    with pytest.raises(mx.base.MXNetError):
        bogus._resolve_input_size(total + 1)


def test_packed_layout_roundtrip():
    import jax.numpy as jnp
    from mxnet_tpu.ops.rnn_ops import rnn_param_size
    for bi in (False, True):
        size = rnn_param_size(2, 8, 16, "lstm", bi)
        lo = PackedRNNLayout("p", 16, 2, "lstm", bi)
        flat = jnp.arange(size, dtype=jnp.float32)
        pieces = lo.split(flat)
        assert np.array_equal(np.asarray(lo.join(pieces)),
                              np.asarray(flat))


def test_plan_param_layouts_only_exclusive_rnn_params():
    # packed param consumed ONLY by the RNN op -> hoisted
    mod = lstm_module()
    layouts = perf.plan_param_layouts(mod._symbol)
    assert set(layouts) == {"lstm_parameters"}
    # a second consumer of the packed vector blocks the hoist
    data = mx.sym.var("data")
    p = mx.sym.var("rnn_parameters")
    rnn = mx.sym.RNN(data, p, mx.sym.var("state"), mx.sym.var("state_cell"),
                     state_size=8, num_layers=1, mode="lstm")
    net = rnn + mx.sym.sum(p)   # second consumer
    assert perf.plan_param_layouts(net) == {}


# ---------------------------------------------------------------------------
# PRNG gating (executor satellite) + retrace guard
# ---------------------------------------------------------------------------

def test_deterministic_graph_skips_key_split():
    from mxnet_tpu import random as mxrand
    mod = lstm_module()         # LSTM p=0: no sampling op in the graph
    assert mod._exec._needs_rng is False
    batch = lstm_batch()
    before = np.asarray(mxrand.current_key())
    mod.forward(batch, is_train=True)
    mod.backward()
    assert np.array_equal(np.asarray(mxrand.current_key()), before)


def test_random_graph_still_threads_keys():
    data = mx.sym.var("data")
    drop = mx.sym.Dropout(data, p=0.5)
    net = mx.sym.LinearRegressionOutput(drop, mx.sym.var("label"))
    mod = mx.mod.Module(net, label_names=["label"])
    mod.bind(data_shapes=[DataDesc("data", (4, 8))],
             label_shapes=[DataDesc("label", (4, 8))])
    mod.init_params(mx.init.Xavier())
    assert mod._exec._needs_rng is True
    from mxnet_tpu import random as mxrand
    rng = np.random.RandomState(0)
    batch = DataBatch(data=[mx.nd.array(rng.rand(4, 8))],
                      label=[mx.nd.array(rng.rand(4, 8))])
    before = np.asarray(mxrand.current_key())
    mod.forward(batch, is_train=True)
    after = np.asarray(mxrand.current_key())
    assert not np.array_equal(after, before)
    # two train forwards draw different masks
    out1 = mod.get_outputs()[0].asnumpy()
    mod.forward(batch, is_train=True)
    out2 = mod.get_outputs()[0].asnumpy()
    assert not np.array_equal(out1, out2)


def test_rnn_dropout_attr_controls_rng():
    from mxnet_tpu.ops.registry import OP_TABLE
    rnn = OP_TABLE["RNN"]
    assert rnn.uses_rng({"p": 0.0}) is False
    assert rnn.uses_rng({"p": 0.3}) is True
    assert rnn.uses_rng({}) is False


def test_compile_guard_warns_then_raises_in_strict_mode(caplog):
    guard = CompileGuard("t", expected=1)
    fn = guard.wrap(lambda x: x)
    fn(1)
    assert guard.count == 1 and not guard.retraced
    fn(2)                   # logs a warning, does not raise
    assert guard.retraced
    assert any("CompileGuard[t]" in r.message for r in caplog.records)
    os.environ["MXTPU_RETRACE_STRICT"] = "1"
    try:
        with pytest.raises(mx.base.MXNetError):
            fn(3)
    finally:
        os.environ.pop("MXTPU_RETRACE_STRICT", None)


# ---------------------------------------------------------------------------
# model.py fused updater apply
# ---------------------------------------------------------------------------

def test_update_params_fused_matches_imperative():
    rng = np.random.RandomState(0)
    batch = DataBatch(
        data=[mx.nd.array(rng.rand(8, 12))],
        label=[mx.nd.array(rng.randint(0, 4, (8,)).astype(np.float32))])

    def run(disable_fused):
        if disable_fused:
            os.environ["MXTPU_FUSED_STEP"] = "0"
        try:
            mx.random.seed(3)
            mod = mx.mod.Module(mlp_symbol())
            mod.bind(data_shapes=[DataDesc("data", (8, 12))],
                     label_shapes=[DataDesc("softmax_label", (8,))])
            mod.init_params(mx.init.Xavier())
            mod.init_optimizer(optimizer="adam",
                               optimizer_params={"learning_rate": 0.01})
            for _ in range(3):
                mod.forward(batch, is_train=True)
                mod.backward()
                mod.update()
            return params_of(mod)
        finally:
            os.environ.pop("MXTPU_FUSED_STEP", None)

    fused, imperative = run(False), run(True)
    for n in fused:
        np.testing.assert_allclose(fused[n], imperative[n],
                                   rtol=2e-5, atol=2e-6, err_msg=n)
