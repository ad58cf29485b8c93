"""The routed layer's row kernels (``ops/pallas/moe_rows.py``) through the
Pallas interpreter, against the ``jnp`` formulation they replace on the chip:
the gather bit for bit, the weighted gather-sum to one rounding, the layer's
gradients, a non-finite row in a slot that is not held, and the counter.

Widths are one (8, 128) tile of 32-bit words (2048 bfloat16 lanes, 1024
float32), the least the kernels take; 128 tokens. The interpreter runs on the
CPU, whose compiler fuses a multiply and the add after it into one rounding:
where a weight is not a power of two a float32 sum can differ from the
formulation's in its last bit, so the float32 comparisons that must be exact
take weights that are powers of two (each product exact) and the others one
ulp."""
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ops.pallas import moe_rows
from mxnet_tpu.parallel import moe

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from test_decoder_lm import _plain_return, _same_with_gradients  # noqa: E402

T = 128
WIDTH = {"bfloat16": 2048, "float32": 1024}
GROUPS = 5          # four held experts and the rest


def _plan(dtype):
    return moe_rows.row_plan(T, WIDTH[dtype], jnp.dtype(dtype),
                             impl="interpret")


def _routing(k, held, seed=0):
    """(take, put, held (k, T)): a sort of the k T slots by a random expert,
    the absent ones last; ``held`` of "none", "some", "all" says which slots
    lie on held experts."""
    most = {"none": 0, "some": 3, "all": 4}[held]
    local = jax.random.randint(jax.random.PRNGKey(seed), (k * T,), 0, 4,
                               jnp.int32)
    local = jnp.where(local < most, local, GROUPS - 1)
    take = jnp.argsort(local, stable=True).astype(jnp.int32)
    return take, jnp.argsort(take).astype(jnp.int32), \
        (local < GROUPS - 1).reshape(k, T)


def _rows(n, dtype, seed):
    return jax.random.normal(jax.random.PRNGKey(seed), (n, WIDTH[dtype]),
                             jnp.float32).astype(dtype)


# the kernels' entry points are jitted: the tests share their shapes (bf16,
# k = 8) so that each variant is compiled once in the process


def test_gather_is_the_token_rows_bit_for_bit():
    """The dispatch through ``rows_to_slots`` is ``x[take % T]``, every
    bit, whichever slots are held."""
    x = _rows(T, "bfloat16", 1)
    for held in ("none", "some", "all"):
        take, put, _ = _routing(8, held, seed=8)
        rows = moe._rows_by_slot(x, take, put, 8, _plan("bfloat16"))
        assert rows.dtype == x.dtype
        assert np.array_equal(np.asarray(rows.astype(jnp.float32)),
                              np.asarray(x[take % T].astype(jnp.float32)))


@pytest.mark.parametrize("dtype, k", [("bfloat16", 8), ("float32", 4)])
def test_gather_sum_is_the_permute_and_weighted_return(dtype, k):
    """``slots_to_rows`` against ``_weighted_return(_permute_rows(...))``
    with held experts covering none, some and all of the slots: exactly
    where every product is exact, within one rounding of the rows' dtype
    for any weights (float32: the interpreter's fused multiply-add), and
    exactly zero for a token none of whose slots is held. Without weights it
    is the dispatch's transpose, ``g[put]`` summed over the k slots:
    exact."""
    out = _rows(k * T, dtype, 2)
    exponents = jax.random.randint(jax.random.PRNGKey(3), (k, T), -3, 3,
                                   jnp.int32)
    exact = jnp.ldexp(jnp.ones((k, T), jnp.float32), exponents)
    any_weights = jax.random.uniform(jax.random.PRNGKey(4), (k, T),
                                     jnp.float32) + 0.25
    plan = _plan(dtype)

    def both(weights, take, put, mask):
        per_slot = moe._permute_rows(out, put, take).reshape(k, T, -1)
        return [np.asarray(a.astype(jnp.float32)) for a in (
            moe_rows.slots_to_rows(out, take, k, plan, weights, mask),
            moe._weighted_return(per_slot, weights, mask))]

    for held in ("none", "some", "all"):
        take, put, mask = _routing(k, held, seed=k)
        got, want = both(exact, take, put, mask)
        assert np.array_equal(got, want)
        assert not want[~np.asarray(mask).any(0)].any()
        got, want = both(any_weights, take, put, mask)
        np.testing.assert_allclose(
            got, want, atol=1e-6,
            rtol=2.0 ** -7 if dtype == "bfloat16" else 2.0 ** -22)
        plain = moe_rows.slots_to_rows(out, take, k, plan)
        assert np.array_equal(
            np.asarray(plain.astype(jnp.float32)),
            np.asarray(out[put].reshape(k, T, -1).sum(0).astype(jnp.float32)))


def _through_the_kernels(monkeypatch):
    monkeypatch.setattr(moe_rows, "row_plan", functools.partial(
        moe_rows.row_plan, impl="interpret"))


def _plain_layer(x, router_w, w_gate, w_up, w_down, *, num_experts, top_k,
                 expert_offset):
    """``held_experts_apply`` as plain ``jnp``: gathers, grouped matmuls and
    ``_plain_return``, all differentiated by JAX."""
    t, d = x.shape
    held = w_gate.shape[0]
    weights, chosen = moe.scored_topk_router(x, router_w, top_k)
    local = chosen.T.reshape(-1) - expert_offset
    local = jnp.where((local >= 0) & (local < held), local, held)
    take = jnp.argsort(local, stable=True)
    put = jnp.argsort(take)
    counts = jnp.sum(local[:, None] == jnp.arange(held)[None, :], axis=0,
                     dtype=jnp.int32)
    groups = counts.at[-1].add(t * top_k - jnp.sum(counts))
    rows = x[take % t]
    gate = jax.lax.ragged_dot(rows, w_gate, groups)
    up = jax.lax.ragged_dot(rows, w_up, groups)
    out = jax.lax.ragged_dot(jax.nn.silu(gate) * up, w_down, groups)
    return _plain_return(out[put].reshape(top_k, t, d), weights.T,
                         (local < held).reshape(top_k, t))


@pytest.mark.parametrize("k, offset", [(2, 2)])
def test_layer_gradients_through_the_kernels_match_plain_jnp(
        monkeypatch, k, offset):
    """The layer's output and the gradients of the tokens, the router and
    the three expert stacks, through the kernels (float32, so a rounding
    cannot hide a wrong row), against JAX's gradient of the plain
    formulation."""
    _through_the_kernels(monkeypatch)
    d, f, held = WIDTH["float32"], 16, 4
    keys = jax.random.split(jax.random.PRNGKey(k), 5)
    shapes = ((T, d), (8, d), (held, d, f), (held, d, f), (held, f, d))
    args = tuple(scale * jax.random.normal(key, shape, jnp.float32)
                 for key, shape, scale in zip(keys, shapes,
                                              (1, 0.1, 0.05, 0.05, 0.05)))
    kw = dict(num_experts=8, top_k=k, expert_offset=offset)
    _same_with_gradients(lambda *a: moe.held_experts_apply(*a, **kw)[0],
                         lambda *a: _plain_layer(*a, **kw), *args, tol=5e-5)


def test_a_nan_in_a_slot_not_held_reaches_nothing():
    """The return through the kernels, with every row of a slot that is not
    held NaN: the output, the rows' cotangent and the weights' are finite,
    and the two cotangents are zero in those slots."""
    k, dtype = 8, "bfloat16"
    take, put, mask = _routing(k, "some", seed=7)
    held_rows = np.asarray(mask).reshape(-1)[np.asarray(take)]
    out = jnp.where(held_rows[:, None], _rows(k * T, dtype, 8),
                    jnp.nan).astype(dtype)
    weights = jax.random.uniform(jax.random.PRNGKey(9), (k, T),
                                 jnp.float32) + 0.5
    y, vjp = jax.vjp(lambda o, w: moe._return_by_kernel(
        o, w, mask, take, put, _plan(dtype)), out, weights)
    d_out, d_weights = vjp(_rows(T, dtype, 10))
    for a in (y, d_out, d_weights):
        assert np.isfinite(np.asarray(a, np.float32)).all()
    assert not np.asarray(d_out, np.float32)[~held_rows].any()
    assert not np.asarray(d_weights)[~np.asarray(mask)].any()


def test_the_counter_counts_the_kernels_traced(monkeypatch):
    """``moe.row_kernel_calls`` grows by one each time a row kernel's call is
    traced: once a shape and variant in a process (the gather and the
    gather-sum forward, their weighted and plain transposes backward), and
    not at all where the layer keeps ``jnp``: off the chip."""
    d, f, held = WIDTH["float32"], 16, 4
    args = tuple(jnp.ones(shape, jnp.float32) for shape in (
        (T, d), (8, d), (held, d, f), (held, d, f), (held, f, d)))

    def traced():
        jax.clear_caches()           # a process that has traced nothing yet
        before = mx.profiler.counters().get("moe.row_kernel_calls", 0)
        jax.eval_shape(jax.grad(lambda *a: jnp.sum(moe.held_experts_apply(
            *a, num_experts=8, top_k=2)[0])), *args)
        return mx.profiler.counters().get("moe.row_kernel_calls", 0) - before

    assert traced() == 0
    _through_the_kernels(monkeypatch)
    assert traced() == 4


@pytest.mark.parametrize("rows, width, dtype, taken", [
    (128, 2048, "bfloat16", True), (128, 1024, "float32", True),
    (16384, 2048, "bfloat16", True), (128, 1024, "bfloat16", False),
    (128, 2048 + 128, "bfloat16", False), (120, 2048, "bfloat16", False),
    (128, 2048, "float16", False), (32768, 2048, "bfloat16", False)])
def test_the_plan_takes_whole_tiles_that_fit(rows, width, dtype, taken):
    """The kernels take bfloat16 or float32 rows a whole (8, 128) tile of
    32-bit words wide, rows a multiple of 128, a source that fits the VMEM
    the gather holds; off the chip the layer keeps ``jnp`` whatever the
    shape."""
    assert moe_rows.row_plan(rows, width, jnp.dtype(dtype)) is None
    plan = moe_rows.row_plan(rows, width, jnp.dtype(dtype), impl="pallas")
    assert (plan is not None) == taken
