"""``RotaryEmbedding`` through ``ops/pallas/rotary.py``'s kernel (the Pallas
interpreter on the CPU) against the ``jnp`` formulation the op had before the
kernel, kept here as the reference and differentiated by ``jax``: the four
shape families of the decoder cells at CPU-small sizes, the shapes that fall
back, the counter. What the chip's compiler makes of the kernel at the cells'
own shapes is ``test_tpu_compile.py``'s."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ops.attention_ops import rotary_frequencies
from mxnet_tpu.ops.pallas import rotary
from mxnet_tpu.ops.registry import get_op

_YARN = dict(rope_type="yarn", factor=32.0, original_max_position=16,
             attention_factor=1.2)
# (B, S, heads * head_dim), the op's attributes
_FAMILIES = {
    "head128-whole": ((1, 64, 4 * 128), dict(head_dim=128)),
    "head128-half-yarn": ((1, 64, 3 * 128),
                          dict(head_dim=128, rotary_dim=64, theta=5e5,
                               **_YARN)),
    "head128-whole-two-rows": ((2, 32, 2 * 128),
                               dict(head_dim=128, theta=1e6)),
    "head256-quarter": ((1, 32, 2 * 256), dict(head_dim=256, rotary_dim=64)),
    "head128-whole-two-copies": ((1, 64, 2 * 128),
                                 dict(head_dim=128, theta=1e6, copies=2)),
}


def _reference(data, head_dim, rotary_dim=0, theta=10000.0,
               rope_type="default", factor=1.0, original_max_position=0,
               beta_fast=32.0, beta_slow=1.0, attention_factor=1.0, copies=1):
    """The op as it was written before PR 36, through the four-dimensional
    view; ``jax`` differentiates it."""
    b, s, e = data.shape
    r = rotary_dim or head_dim
    inv = rotary_frequencies(r, theta, rope_type, factor,
                             original_max_position, beta_fast, beta_slow)
    position = (jnp.arange(s) % (s // copies)).astype(jnp.float32)
    angle = position[:, None] * inv[None, :]
    cos = (jnp.cos(angle) * attention_factor)[None, :, None, :]
    sin = (jnp.sin(angle) * attention_factor)[None, :, None, :]
    x = data.reshape(b, s, e // head_dim, head_dim).astype(jnp.float32)
    x1, x2, rest = x[..., :r // 2], x[..., r // 2:r], x[..., r:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)
    return out.reshape(b, s, e).astype(data.dtype)


def _through_the_kernel(monkeypatch):
    """Send ``RotaryEmbedding`` down the chip's path on the CPU."""
    monkeypatch.setattr(rotary, "kernel_plan", functools.partial(
        rotary.kernel_plan, impl="interpret"))


def _kernel_calls():
    return mx.profiler.counters().get("rotary.kernel_calls", 0)


def _operands(shape, dtype, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal(shape), dtype),
            jnp.asarray(rng.standard_normal(shape), dtype))


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("family", list(_FAMILIES))
def test_kernel_is_the_op_and_its_gradient_the_negative_rotation(
        family, dtype, monkeypatch):
    shape, attrs = _FAMILIES[family]
    dtype = jnp.dtype(dtype)
    x, g = _operands(shape, dtype)
    op = get_op("RotaryEmbedding").fn
    want, want_vjp = jax.vjp(lambda x: _reference(x, **attrs), x)
    want_dx, = want_vjp(g)
    # one unit in the last place of the dtype at the value's own size, and
    # of float32 at the products' (a sum that cancels keeps their rounding)
    eps = float(jnp.finfo(dtype).eps)

    def close(got, ref):
        assert got.dtype == ref.dtype and got.shape == ref.shape
        got, ref = (np.asarray(a, np.float64) for a in (got, ref))
        np.testing.assert_array_less(
            np.abs(got - ref), eps * np.abs(ref) + 1e-6)

    # off the chip: the jnp formulation, differentiated by jax
    before = _kernel_calls()
    out, vjp = jax.vjp(lambda x: op(x, **attrs), x)
    close(out, want)
    close(vjp(g)[0], want_dx)
    assert _kernel_calls() == before

    _through_the_kernel(monkeypatch)
    out, vjp = jax.vjp(lambda x: op(x, **attrs), x)
    close(out, want)
    close(vjp(g)[0], want_dx)
    # once forward, once for the cotangent
    assert _kernel_calls() == before + 2


def test_no_residual_is_kept_for_the_backward(monkeypatch):
    _through_the_kernel(monkeypatch)
    shape, attrs = _FAMILIES["head128-half-yarn"]
    x, _ = _operands(shape, jnp.bfloat16)
    op = get_op("RotaryEmbedding").fn
    _, vjp = jax.vjp(lambda x: op(x, **attrs), x)
    assert not jax.tree_util.tree_leaves(vjp)


@pytest.mark.parametrize("case, shape, attrs", [
    # LFM2's: a head of 64 fills half of every row of lanes
    ("head-of-64-lanes-two-rows", (2, 32, 4 * 64),
     dict(head_dim=64, theta=1e6)),
    ("head-of-96-lanes", (1, 32, 2 * 96), dict(head_dim=96)),
    ("rows-no-tile-divides", (1, 36, 256), dict(head_dim=128)),
    ("copy-no-tile-divides", (1, 40, 256), dict(head_dim=128, copies=2)),
])
def test_a_shape_the_kernel_does_not_take_falls_back(case, shape, attrs,
                                                     monkeypatch, recwarn):
    _through_the_kernel(monkeypatch)
    x, g = _operands(shape, jnp.bfloat16, seed=1)
    op = get_op("RotaryEmbedding").fn
    before = _kernel_calls()
    out, vjp = jax.vjp(lambda x: op(x, **attrs), x)
    want, want_vjp = jax.vjp(lambda x: _reference(x, **attrs), x)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(want, np.float32))
    np.testing.assert_array_equal(np.asarray(vjp(g)[0], np.float32),
                                  np.asarray(want_vjp(g)[0], np.float32))
    assert _kernel_calls() == before        # and it says nothing
    assert not recwarn.list


def test_the_table_block_follows_the_position_not_the_row(monkeypatch):
    """Two rows of a batch and two copies of a document read the same rows
    of the table: the second half of the rows is rotated like the first."""
    _through_the_kernel(monkeypatch)
    op = get_op("RotaryEmbedding").fn
    attrs = dict(head_dim=128, theta=100.0)
    x, _ = _operands((1, 16, 256), jnp.float32, seed=2)
    want = op(x, **attrs)
    twice = jnp.concatenate([x, x], axis=1)
    as_copies = op(twice, copies=2, **attrs)
    as_rows = op(twice.reshape(2, 16, 256), **attrs)
    for got in (as_copies[:, :16], as_copies[:, 16:],
                as_rows[:1], as_rows[1:]):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_a_decoder_trains_alike_through_the_kernel(monkeypatch):
    """Laguna's rehearsal graph at a head size the kernel takes (yarn on half
    a head on the full layer, whole heads on the sliding one), blocks as
    checkpoints: loss and gradient through the kernel are those through
    ``jnp``, and every rotation went through the kernel in all three
    stages."""
    import json
    import os
    from mxnet_tpu import models
    from mxnet_tpu.executor import build_graph_eval
    with open(os.path.join(os.path.dirname(__file__), os.pardir, "perfbench",
                           "configs", "laguna-xs2.json")) as f:
        cfg = json.load(f)
    cfg.update(cfg["rehearse"])
    cfg.update(num_hidden_layers=2, compute_dtype="float32", head_dim=128,
               num_key_value_heads=1,
               layer_types=["full_attention", "sliding_attention"],
               num_attention_heads_per_layer=[2, 3],
               mlp_layer_types=["dense", "dense"])
    sym = models.get_symbol("decoder_lm", cfg=cfg)
    shapes = dict(zip(sym.list_arguments(), sym.infer_shape(
        data=(1, 32), softmax_label=(1, 32))[0]))
    rng = np.random.default_rng(0)
    args = {n: jnp.asarray(0.1 * rng.standard_normal(shape), jnp.float32)
            for n, shape in shapes.items()}
    args["data"] = jnp.asarray(rng.integers(0, 96, (1, 32)), jnp.float32)
    args["softmax_label"] = args["data"]
    params = {n: v for n, v in args.items()
              if n not in ("data", "softmax_label")}

    def loss_and_grad():
        fn = build_graph_eval(sym, remat_blocks=True)
        return jax.value_and_grad(
            lambda p: fn(dict(args, **p), {}, None, True)[0][0][0])(params)

    before = _kernel_calls()
    want, want_grad = loss_and_grad()
    assert _kernel_calls() == before
    _through_the_kernel(monkeypatch)
    got, got_grad = loss_and_grad()
    # q and k of two layers: first forward, the checkpoint's forward again,
    # backward
    assert _kernel_calls() == before + 3 * 4
    np.testing.assert_allclose(got, want, rtol=1e-6)
    for name in want_grad:
        np.testing.assert_allclose(got_grad[name], want_grad[name],
                                   rtol=1e-4, atol=1e-7, err_msg=name)
