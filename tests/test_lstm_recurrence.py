"""The LSTM layer's backward written as a whole (ops/pallas/lstm.py
``lstm_recurrence``) against autodiff of the plain scanned cell, its shape
in the jaxpr, and its counter. CPU: the kernels run through the Pallas
interpreter, the ``jnp`` cell as it runs off the chip."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from mxnet_tpu import profiler
from mxnet_tpu.ops import rnn_ops
from mxnet_tpu.ops.pallas import lstm

COUNTER = "rnn.whole_backward_layers"


def _plain_recurrence(xproj, h0, c0, w, reverse):
    def body(carry, xp):
        h, c = lstm._cell_jnp(xp, *carry, w)
        return (h, c), h

    (hT, cT), hs = lax.scan(body, (h0, c0), xproj, reverse=reverse)
    return hs, hT, cT


def _weighted(fn, weights):
    """A scalar that gives every output of ``fn`` a cotangent of its own."""
    def loss(*args):
        return sum((out.astype(jnp.float32) * w).sum()
                   for out, w in zip(fn(*args), weights))
    return loss


def _close(got, want, tol):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(got, want, rtol=tol,
                               atol=tol * np.abs(want).max())


# float32 throughout; bf16 throughout; the LM cell's own mix (bf16 weight
# under float32 states and projection)
DTYPES = {
    "f32": (np.float32, np.float32, 1e-5),
    "bf16": (jnp.bfloat16, jnp.bfloat16, 2e-2),
    "bf16-weight": (np.float32, jnp.bfloat16, 2e-2),
}

# grid: one kernel walks the time steps, forward and backward (W_hh held in
# VMEM); step: what runs where W_hh does not fit there, lax.scan of the
# per-step kernel (H=128: blocks of hidden units, H=100: whole arrays) under
# the jnp walk backwards; jnp: off the chip
PATHS = [(128, "grid", "f32"), (100, "grid", "f32"), (100, "grid", "bf16"),
         (128, "grid", "bf16-weight"), (128, "step", "f32"),
         (100, "step", "bf16"), (100, "jnp", "f32"), (100, "jnp", "bf16"),
         (100, "jnp", "bf16-weight")]


@pytest.mark.parametrize("hd,path,dtype", PATHS,
                         ids=[f"h{h}-{p}-{d}" for h, p, d in PATHS])
@pytest.mark.parametrize("reverse", [False, True], ids=["forward", "reverse"])
def test_recurrence_backward_matches_autodiff(monkeypatch, reverse, hd, path,
                                              dtype):
    """Non-zero h0 / c0, cotangents on every step's h and on hT / cT, and
    5 time steps recomputed 2 at a time (2 + 2 + 1)."""
    act, wdt, tol = DTYPES[dtype]
    t, n = 5, 8
    monkeypatch.setattr(lstm, "_PREACT_BYTES", 2 * n * 4 * hd * 4)
    assert lstm._chunks(t, n, 4 * hd) == [(0, 2), (2, 4), (4, 5)]
    if path == "step":
        monkeypatch.setattr(lstm, "_VMEM_LIMIT", 0)
    impl = "jnp" if path == "jnp" else "interpret"
    rng = np.random.RandomState(11)

    def arr(shape, scale, dt):
        return jnp.asarray(rng.normal(0, scale, shape).astype(np.float32)
                           ).astype(dt)

    args = (arr((t, n, 4 * hd), 1.0, act), arr((n, hd), 0.7, act),
            arr((n, hd), 0.7, act), arr((4 * hd, hd), 2.0 / np.sqrt(hd), wdt))
    weights = [arr(s, 1.0, np.float32)
               for s in [(t, n, hd), (n, hd), (n, hd)]]
    whole = functools.partial(lstm.lstm_recurrence, reverse=reverse,
                              impl=impl)
    plain = functools.partial(_plain_recurrence, reverse=reverse)
    def outputs_and_grads(fn):
        return jax.jit(lambda *a: (fn(*a), jax.grad(
            _weighted(fn, weights), argnums=(0, 1, 2, 3))(*a)))(*args)

    (outs, got), (plain_outs, want) = map(outputs_and_grads, (whole, plain))
    for o, p in zip(outs, plain_outs):
        _close(o, p, tol)
    for g, w, a in zip(got, want, args):
        assert g.dtype == a.dtype and g.shape == a.shape
        _close(g, w, tol)


def _layers_by_hand(data, params, state, state_cell, hd, num_layers):
    """The RNN op's bidirectional LSTM layers as scans of ``lstm_cell_fused``
    (differentiated step by step, through the cell's own VJP)."""
    pieces = rnn_ops._unpack(params, num_layers, data.shape[-1], hd, "lstm",
                             True)
    x, hy, cy = data, [], []
    for layer in range(num_layers):
        outs = []
        for d in range(2):
            w_i2h, w_h2h, b_i2h, b_h2h = pieces[layer][d]
            xproj = x @ w_i2h.T + (b_i2h + b_h2h)

            def body(carry, xp, w_h2h=w_h2h):
                h, c = lstm.lstm_cell_fused(xp, *carry, w_h2h, impl="jnp")
                return (h, c), h

            (hT, cT), out = lax.scan(
                body, (state[2 * layer + d], state_cell[2 * layer + d]),
                xproj, reverse=(d == 1))
            outs.append(out)
            hy.append(hT)
            cy.append(cT)
        x = jnp.concatenate(outs, axis=-1)
    return x, jnp.stack(hy), jnp.stack(cy)


def test_rnn_op_two_bidirectional_layers_match_the_per_step_vjp():
    t, n, insize, hd, layers = 6, 3, 5, 12, 2
    rng = np.random.RandomState(12)

    def arr(shape, scale=1.0):
        return jnp.asarray(rng.normal(0, scale, shape).astype(np.float32))

    psize = rnn_ops.rnn_param_size(layers, insize, hd, "lstm", True)
    args = (arr((t, n, insize)), arr((psize,), 0.3),
            arr((2 * layers, n, hd), 0.5), arr((2 * layers, n, hd), 0.5))
    weights = [arr((t, n, 2 * hd)), arr((2 * layers, n, hd)),
               arr((2 * layers, n, hd))]

    def op(data, params, state, state_cell):
        return rnn_ops._rnn(None, data, params, state, state_cell,
                            state_size=hd, num_layers=layers, mode="lstm",
                            bidirectional=True, state_outputs=True)

    by_hand = functools.partial(_layers_by_hand, hd=hd, num_layers=layers)

    def outputs_and_grads(fn):
        return jax.jit(lambda *a: (fn(*a), jax.grad(
            _weighted(fn, weights), argnums=(0, 1, 2, 3))(*a)))(*args)

    (outs, got), (hand_outs, want) = map(outputs_and_grads, (op, by_hand))
    for g, w in zip(outs + got, hand_outs + want):
        _close(g, w, 1e-5)


# -- the mechanism, as the jaxpr shows it --------------------------------------

LOOPS = ("scan", "while", "pallas_call")   # a kernel's grid walks the steps


def _sub_jaxprs(eqn):
    for value in eqn.params.values():
        for v in (value if isinstance(value, (list, tuple)) else (value,)):
            if hasattr(v, "jaxpr"):       # ClosedJaxpr
                v = v.jaxpr
            if hasattr(v, "eqns"):
                yield v


def _walk(jaxpr, loops=()):
    """(equation, the loops around it) of every equation."""
    for eqn in jaxpr.eqns:
        yield eqn, loops
        inner = loops + (eqn,) if eqn.primitive.name in LOOPS else loops
        for sub in _sub_jaxprs(eqn):
            yield from _walk(sub, inner)


@pytest.mark.parametrize("impl", ["interpret", "jnp"])
def test_backward_walk_holds_one_matmul_and_the_weight_stays_bf16(
        monkeypatch, impl):
    """The backward of one layer, bf16 operands: the walk over the time
    steps (the kernel's grid on the chip, a scan off it) holds ONE
    dot_general, ``dgates_t @ W_hh``; the gate recomputation and ``dW_hh``
    are one dot_general each outside it; nothing widens ``W_hh``."""
    t, n, insize, hd = 6, 8, 64, 128
    monkeypatch.setattr(lstm, "lstm_recurrence", functools.partial(
        lstm.lstm_recurrence, impl=impl))
    bf = jnp.bfloat16
    shapes = [((t, n, insize), bf), ((n, hd), bf), ((n, hd), bf),
              ((4 * hd, insize), bf), ((4 * hd, hd), bf),
              ((4 * hd,), jnp.float32), ((4 * hd,), jnp.float32)]
    outs, backward = jax.vjp(
        lambda *a: rnn_ops._run_direction(*a, "lstm", hd),
        *[jnp.zeros(s, d) for s, d in shapes])
    found = list(_walk(jax.make_jaxpr(backward)(outs).jaxpr))

    walks = [e for e, _ in found if e.primitive.name in LOOPS]
    assert [e.primitive.name for e in walks] == \
        ["pallas_call" if impl == "interpret" else "scan"]
    dots = [(e, ls) for e, ls in found if e.primitive.name == "dot_general"]
    (dh,) = [e for e, ls in dots if ls]
    assert [v.aval.shape for v in dh.invars] == [(n, 4 * hd), (hd, 4 * hd)]
    assert [v.aval.dtype for v in dh.invars] == [bf, bf]
    assert dh.outvars[0].aval.dtype == jnp.float32

    outside = {e.outvars[0].aval.shape: e for e, ls in dots if not ls}
    # the gates of all steps; dW_hh over all T x N rows; dW_ih; dx
    assert sorted(outside) == sorted([(t * n, 4 * hd), (4 * hd, hd),
                                      (4 * hd, insize), (t * n, insize)])
    assert len(dots) == 5
    dw = outside[(4 * hd, hd)]
    assert [v.aval.shape for v in dw.invars] == [(t * n, 4 * hd),
                                                 (t * n, hd)]
    assert [v.aval.dtype for v in dw.invars] == [bf, bf]
    assert dw.outvars[0].aval.dtype == jnp.float32

    widened = [e for e, _ in found
               if e.primitive.name == "convert_element_type"
               and e.invars[0].aval.shape in ((4 * hd, hd), (hd, 4 * hd))
               and e.params["new_dtype"] == jnp.float32]
    assert not widened


def test_counter_grows_while_tracing_only():
    t, n, insize, hd, layers = 4, 2, 3, 8, 2
    psize = rnn_ops.rnn_param_size(layers, insize, hd, "lstm", True)
    args = (jnp.ones((t, n, insize)), jnp.full((psize,), 0.1),
            jnp.zeros((2 * layers, n, hd)), jnp.zeros((2 * layers, n, hd)))

    @jax.jit
    def grads(*a):
        return jax.grad(lambda *b: rnn_ops._rnn_impl(
            None, *b, hd, layers, "lstm", True, 0.0, True)[0].sum(),
            argnums=1)(*a)

    def counted():
        return profiler.counters().get(COUNTER, 0)

    before = counted()
    grads(*args)
    assert counted() - before == 2 * layers     # layers x directions
    grads(*args)                                # warm: nothing is traced
    assert counted() - before == 2 * layers
    # a layer that is only run forward builds no backward
    jax.jit(lambda *a: rnn_ops._rnn_impl(
        None, *a, hd, layers, "lstm", True, 0.0, False)[0])(*args)
    assert counted() - before == 2 * layers
