"""The decoder's ops and model (``models/decoder_lm.py``) at a small size on
the CPU: every new op against a ``jax.numpy`` one-liner, forward and
gradient; the windowed grouped-query attention (plain path, and the kernel
with its blockwise backward through the Pallas interpreter) against
explicit-mask softmax; the routed layer's shares against the whole layer;
recomputation by block; and the whole tiny model, every layer kind, through
``SPMDTrainer.fit`` against the benchmark's plain reference."""
import functools
import json
import math
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import models
from mxnet_tpu.ops.registry import get_op

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _rand(*shape, seed=0, scale=1.0):
    return scale * jax.random.normal(jax.random.PRNGKey(seed), shape,
                                     jnp.float32)


def _close(a, b, tol=2e-5):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=tol,
                               atol=tol)


def _same_with_gradients(fn, ref, *args, tol=2e-5):
    """Value and every argument's gradient (under a random cotangent) of
    ``fn`` against ``ref``, each traced once."""
    idx = tuple(range(len(args)))
    ct = _rand(*jax.eval_shape(ref, *args).shape, seed=99)

    def both(f):
        return jax.jit(lambda *a: (f(*a), jax.grad(
            lambda *b: jnp.sum(f(*b) * ct), idx)(*a)))(*args)

    (got, got_grads), (want, want_grads) = both(fn), both(ref)
    _close(got, want, tol)
    for g, w in zip(got_grads, want_grads):
        _close(g, w, tol)


# -- one op, one one-liner ------------------------------------------------------

def test_rms_norm():
    x, g = _rand(3, 5, 16), 1.0 + _rand(16, seed=1, scale=0.1)
    _same_with_gradients(
        lambda x, g: get_op("RMSNorm").fn(x, g, eps=1e-6),
        lambda x, g: x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
        * g, x, g)


def test_gated_ffn_and_silu():
    x = _rand(7, 16)
    w1, w3, w2 = _rand(24, 16, seed=1), _rand(24, 16, seed=2), \
        _rand(16, 24, seed=3)
    _same_with_gradients(
        lambda *a: get_op("GatedFFN").fn(*a, num_hidden=24),
        lambda x, w1, w3, w2: ((x @ w1.T) * jax.nn.sigmoid(x @ w1.T)
                               * (x @ w3.T)) @ w2.T, x, w1, w3, w2)
    _close(get_op("Activation").fn(x, act_type="silu"),
           x * jax.nn.sigmoid(x))


def test_token_cross_entropy_returns_the_mean_loss_only():
    logits = _rand(2, 6, 11)
    labels = jnp.asarray(np.random.default_rng(0).integers(0, 11, (2, 6)),
                         jnp.float32)
    fn = lambda z: get_op("TokenCrossEntropy").fn(z, labels)      # noqa: E731
    ref = lambda z: -jnp.mean(jnp.take_along_axis(                # noqa: E731
        jax.nn.log_softmax(z), labels.astype(jnp.int32)[..., None],
        -1)).reshape(1)
    _same_with_gradients(fn, ref, logits)
    assert fn(logits).shape == (1,)
    # computed in float32 from bfloat16 logits
    low = fn(logits.astype(jnp.bfloat16))
    assert low.dtype == jnp.float32
    _close(low, ref(logits.astype(jnp.bfloat16).astype(jnp.float32)))


def _rotate_by_hand(x, head_dim, r, inv, factor=1.0):
    b, s, e = x.shape
    x = np.asarray(x, np.float64).reshape(b, s, e // head_dim, head_dim)
    out = x.copy()
    for pos in range(s):
        for i in range(r // 2):
            c = math.cos(pos * inv[i]) * factor
            sn = math.sin(pos * inv[i]) * factor
            a, bb = x[:, pos, :, i], x[:, pos, :, i + r // 2]
            out[:, pos, :, i] = a * c - bb * sn
            out[:, pos, :, i + r // 2] = bb * c + a * sn
    return out.reshape(b, s, e)


def test_rotary_embedding_whole_head_and_partial_yarn():
    x = _rand(2, 9, 3 * 8)
    inv = [10000.0 ** (-2 * i / 8) for i in range(4)]
    _close(get_op("RotaryEmbedding").fn(x, head_dim=8),
           _rotate_by_hand(x, 8, 8, inv), 1e-5)
    # YaRN on half of each head: theta 500000, factor 64 over 16 positions
    attrs = dict(head_dim=8, rotary_dim=4, theta=500000.0, rope_type="yarn",
                 factor=64.0, original_max_position=16, beta_fast=64.0,
                 beta_slow=1.0, attention_factor=1.25)
    base = [500000.0 ** (-2 * i / 4) for i in range(2)]

    def dim_of(turns):
        return 4 * math.log(16 / (turns * 2 * math.pi)) \
            / (2 * math.log(500000.0))

    low = max(math.floor(dim_of(64.0)), 0)
    high = min(math.ceil(dim_of(1.0)), 3)
    inv = []
    for i, f in enumerate(base):
        ramp = min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
        inv.append(f / 64.0 * ramp + f * (1 - ramp))
    got = get_op("RotaryEmbedding").fn(x, **attrs)
    _close(got, _rotate_by_hand(x, 8, 4, inv, 1.25), 1e-5)
    # the dims past rotary_dim pass
    _close(np.asarray(got).reshape(2, 9, 3, 8)[..., 4:],
           np.asarray(x).reshape(2, 9, 3, 8)[..., 4:])
    # a rotation: its transpose is its inverse, so gradients are checked by
    # the norm it keeps (attention_factor 1)
    plain = get_op("RotaryEmbedding").fn(x, head_dim=8)
    _close(jnp.sum(plain ** 2), jnp.sum(x ** 2), 1e-4)


def _attention_by_mask(q, k, v, gate, heads, kv, window):
    """Explicit-mask softmax, the key/value heads repeated."""
    b, s, _ = q.shape
    d = q.shape[-1] // heads
    qh = q.reshape(b, s, heads, d)
    kh = jnp.repeat(k.reshape(b, s, kv, d), heads // kv, axis=2)
    vh = jnp.repeat(v.reshape(b, s, kv, d), heads // kv, axis=2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", qh, kh) / math.sqrt(d)
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = j <= i
    if window:
        seen &= j > i - window
    p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), -1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, vh)
    return (out * jax.nn.sigmoid(gate)[..., None]).reshape(b, s, heads * d)


@pytest.mark.parametrize("group,window", [(6, 0), (8, 5), (6, 7), (8, 0)])
def test_grouped_query_attention_op(group, window):
    kv, d, s = 2, 8, 24
    heads = kv * group
    q, k = _rand(2, s, heads * d), _rand(2, s, kv * d, seed=1)
    v, gate = _rand(2, s, kv * d, seed=2), _rand(2, s, heads, seed=3)
    _same_with_gradients(
        lambda *a: get_op("GroupedQueryAttention").fn(
            *a, num_heads=heads, num_kv_heads=kv, window=window, gated=True),
        lambda *a: _attention_by_mask(*a, heads, kv, window), q, k, v, gate)


# (group, window, block): the last two a window inside one block and a
# window over three blocks
_BANDS = [(6, 16, 16), (8, 24, 16), (6, 0, 16), (8, 40, 32), (6, 5, 16),
          (4, 33, 16)]


def _band_inputs(group, kv=2, d=8, s=64):
    return (_rand(2, kv * group, s, d), _rand(2, kv, s, d, seed=1),
            _rand(2, kv, s, d, seed=2))


@pytest.mark.parametrize("group,window,block", _BANDS)
def test_band_kernel_and_blockwise_backward_in_the_interpreter(group, window,
                                                               block):
    """What the chip runs: the flash kernel over the band and the blockwise
    backward over the same band, reading the row logsumexp the kernel handed
    it, against plain softmax differentiated by JAX."""
    from mxnet_tpu.ops.pallas.attention import (gqa_attention_reference,
                                                grouped_query_attention)
    q, k, v = _band_inputs(group)
    with jax.enable_x64(False):
        _same_with_gradients(
            lambda *a: grouped_query_attention(
                *a, causal=True, window=window, block=block,
                force_pallas=True),
            lambda *a: gqa_attention_reference(*a, True, window), q, k, v)


@pytest.mark.parametrize("group,window,block", _BANDS)
def test_band_kernel_hands_over_the_row_logsumexp(group, window, block):
    """The kernel's second output against a plain masked ``logsumexp`` of the
    scaled scores over each row's band, (B, H, S) in float32."""
    from mxnet_tpu.ops.pallas.attention import _gqa_pallas
    q, k, v = _band_inputs(group)
    b, h, s, d = q.shape
    scale = 1.0 / math.sqrt(d)
    with jax.enable_x64(False):
        out, lse = _gqa_pallas(q, k, v, True, window, scale, block, block,
                               interpret=True)
    assert out.shape == q.shape
    assert lse.shape == (b, h, s) and lse.dtype == jnp.float32
    sc = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, group, axis=1)) \
        * scale
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = (j <= i) & ((j > i - window) if window else True)
    _close(lse, jax.nn.logsumexp(jnp.where(seen, sc, -jnp.inf), axis=-1))


def _count_eqns(jaxpr, name):
    """Equations of primitive ``name`` in ``jaxpr`` and every jaxpr inside
    its equations' parameters."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == name
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _count_eqns(sub, name)
    return n


def _kept_since(before):
    """What the ``remat.*`` counters grew by since ``before``."""
    now = mx.profiler.counters()
    return {n: now.get(n, 0) - before.get(n, 0)
            for n in ("remat.kept_values", "remat.kept_bytes")}


@pytest.mark.parametrize("policy,kernels", [("executor", 1), ("plain", 2)])
def test_a_checkpoint_with_the_executors_policy_runs_the_kernel_once(
        policy, kernels):
    """Forward and backward of the attention under ``jax.checkpoint``: a
    plain one runs the kernel again in the backward for the residuals; the
    block checkpoint's policy keeps ``out`` and ``lse``, so one call is all
    the step holds, and says what it kept."""
    from mxnet_tpu.executor import _keep_named_residuals
    from mxnet_tpu.ops.pallas.attention import grouped_query_attention
    q, k, v = _band_inputs(6)

    def loss(q, k, v):
        return jnp.sum(grouped_query_attention(
            q, k, v, window=16, block=16, force_pallas=True) ** 2)

    before = mx.profiler.counters()
    with jax.enable_x64(False):
        traced = jax.make_jaxpr(jax.grad(jax.checkpoint(
            loss, policy=_keep_named_residuals if policy == "executor"
            else None), (0, 1, 2)))(q, k, v)
    assert _count_eqns(traced.jaxpr, "pallas_call") == kernels
    # out (B, H, S, d) and lse (B, H, S), float32 here
    assert _kept_since(before) == ({"remat.kept_values": 2,
                     "remat.kept_bytes": 4 * (q.size + q.size // q.shape[-1])}
                    if policy == "executor" else
                    {"remat.kept_values": 0, "remat.kept_bytes": 0})


def _tiles_by_the_mask(n, tile, causal, window, block_length=0):
    """``(live, whole)`` (n, n) bool from the mask written out pair by pair
    (``_band_mask`` over every position): a tile with a pair that sees, and
    a tile whose every pair sees."""
    from mxnet_tpu.ops.pallas.attention import _band_mask
    pos = jnp.arange(n * tile)
    mask = _band_mask(pos[:, None], pos[None, :], causal, window,
                      block_length, n * tile // 2)
    mask = np.ones((n * tile,) * 2, bool) if mask is None \
        else np.asarray(mask)
    tiles = mask.reshape(n, tile, n, tile)
    return tiles.any(axis=(1, 3)), tiles.all(axis=(1, 3))


def _table_against_the_mask(n, tile, causal, window, block_length=0):
    """The host-built schedule against the mask itself: one visit a live
    tile and no other, a query tile's visits one run by rising key tile with
    its ends flagged, the cut flag exactly on the tiles that hold a live and
    a dead pair. Returns the number of visits."""
    from mxnet_tpu.ops.pallas.attention import (_CLOSES, _CUT, _OPENS,
                                                _visit_table)
    q_tile, k_tile, flags = _visit_table(n, n, tile, tile, causal, window,
                                         block_length)
    live, whole = _tiles_by_the_mask(n, tile, causal, window, block_length)
    assert sorted(zip(q_tile.tolist(), k_tile.tolist())) \
        == [tuple(x) for x in np.argwhere(live).tolist()]
    assert len(set(zip(q_tile.tolist(), k_tile.tolist()))) == len(q_tile)
    order = np.lexsort((k_tile, q_tile))
    assert (order == np.arange(len(order))).all()
    first = np.r_[True, q_tile[1:] != q_tile[:-1]]
    last = np.r_[q_tile[1:] != q_tile[:-1], True]
    assert ((flags & _OPENS != 0) == first).all()
    assert ((flags & _CLOSES != 0) == last).all()
    assert ((flags & _CUT != 0) == ~whole[q_tile, k_tile]).all()
    return len(q_tile)


# (causal, window, tile, visits of 16 tiles): the decoder cells' 8,192
# positions in tiles of 512, and small tiles the window lies inside, on and
# across
@pytest.mark.parametrize("causal,window,tile,visits", [
    (True, 0, 512, 136), (True, 512, 512, 31), (False, 0, 512, 256),
    (True, 0, 8, 136), (True, 8, 8, 31), (True, 3, 8, 31), (True, 20, 8, 58),
    (True, 10, 8, 45)])
def test_the_table_visits_a_bands_live_tiles_and_no_other(causal, window,
                                                          tile, visits):
    from mxnet_tpu.ops.pallas.attention import _CUT, _visit_table
    if tile <= 8:       # the mask written out: 128 x 128 pairs
        assert _table_against_the_mask(16, tile, causal, window) == visits
    q_tile, k_tile, flags = _visit_table(16, 16, tile, tile, causal, window)
    assert len(q_tile) == visits
    cut = int((flags & _CUT != 0).sum())
    if not causal:
        assert cut == 0
    elif not window:
        # only the diagonal tile of a causal walk needs its mask
        assert cut == 16 and (q_tile == k_tile)[flags & _CUT != 0].all()
    elif window == tile:
        assert cut == visits        # both of a window's tiles hold an edge


def test_the_table_takes_tiles_that_are_not_square():
    """A query tile of 4 over key tiles of 8, window 6: against the mask
    written out."""
    from mxnet_tpu.ops.pallas.attention import _CUT, _band_mask, _visit_table
    q_tile, k_tile, flags = _visit_table(8, 4, 4, 8, True, 6)
    pos = jnp.arange(32)
    tiles = np.asarray(_band_mask(pos[:, None], pos[None, :], True, 6)) \
        .reshape(8, 4, 4, 8)
    assert sorted(zip(q_tile.tolist(), k_tile.tolist())) \
        == [tuple(x) for x in np.argwhere(tiles.any(axis=(1, 3))).tolist()]
    assert ((flags & _CUT != 0)
            == ~tiles.all(axis=(1, 3))[q_tile, k_tile]).all()


def _the_kernels_grid(q, k, v, **mask):
    """Grid of the ``pallas_call`` the forward builds."""
    from mxnet_tpu.ops.pallas.attention import _gqa_pallas
    with jax.enable_x64(False):
        traced = jax.make_jaxpr(lambda *a: _gqa_pallas(
            *a, mask.get("causal", True), mask.get("window", 0), 1.0,
            mask["block"], mask["block"], True,
            mask.get("block_length", 0)))(q, k, v)
    call, = [e for e in traced.jaxpr.eqns if e.primitive.name == "pallas_call"]
    return tuple(call.params["grid_mapping"].grid)


@pytest.mark.parametrize("mask,visits", [
    ({"window": 0}, 136), ({"window": 512}, 31), ({"causal": False}, 256),
    ({"block_length": 4}, 80)])
def test_the_kernels_grid_has_no_dead_step(mask, visits):
    """16 tiles of 512 under each mask, 2 x 12 heads: the grid is (heads,
    the table's visits), one step a live tile, and the trace counts both."""
    struct = jax.ShapeDtypeStruct((2, 12, 8192, 64), jnp.bfloat16)
    keys = jax.ShapeDtypeStruct((2, 2, 8192, 64), jnp.bfloat16)
    before = mx.profiler.counters()
    assert _the_kernels_grid(struct, keys, keys, block=512, **mask) \
        == (24, visits)
    now = mx.profiler.counters()
    assert [now[n] - before.get(n, 0) for n in (
        "attention.kernel_grid_steps", "attention.kernel_live_tiles")] \
        == [24 * visits] * 2


# (head size, group, mask): the three cells' heads through the interpreter,
# 128 positions (two halves of 64 under the block-diffusion mask)
@pytest.mark.parametrize("d,group,mask", [
    (128, 8, {"block_length": 4}), (128, 6, {"window": 0}),
    (128, 8, {"window": 32}), (128, 8, {"window": 40}),
    (64, 4, {"window": 0}), (64, 4, {"window": 32}),
    (64, 4, {"block_length": 4}), (128, 6, {"block_length": 32})])
@pytest.mark.parametrize("tiles", [(32, 32), (16, 32)])
def test_kernel_and_logsumexp_at_the_cells_head_sizes(d, group, mask, tiles):
    """Output and row logsumexp against the plain reference, in square tiles
    and with a query tile half a key tile."""
    from mxnet_tpu.ops.pallas import attention
    q, k, v = _band_inputs(group, kv=1, d=d, s=128)
    window, block_length = mask.get("window", 0), mask.get("block_length", 0)
    scale = 1.0 / math.sqrt(d)
    with jax.enable_x64(False):
        out, lse = attention._gqa_pallas(q, k, v, True, window, scale,
                                         *tiles, True, block_length)
    _close(out, attention.gqa_attention_reference(
        q, k, v, True, window, scale, block_length))
    sc = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, group, axis=1)) \
        * scale
    pos = jnp.arange(128)
    seen = attention._band_mask(pos[:, None], pos[None, :], True, window,
                                block_length, 64)
    _close(lse, jax.nn.logsumexp(jnp.where(seen, sc, -jnp.inf), axis=-1))


# -- the routed layer ---------------------------------------------------------

def _moe_inputs(t=40, d=16, e=16, f=8, fs=8):
    return dict(
        x=_rand(t, d), router=_rand(e, d, seed=1),
        gate=_rand(e, d, f, seed=2, scale=0.3),
        up=_rand(e, d, f, seed=3, scale=0.3),
        down=_rand(e, f, d, seed=4, scale=0.3),
        shared=(_rand(fs, d, seed=5, scale=0.3),
                _rand(fs, d, seed=6, scale=0.3),
                _rand(d, fs, seed=7, scale=0.3)))


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def _whole_layer(m, top_k, scale):
    """The uncut layer, every token through every expert it chose."""
    s = jax.nn.sigmoid(m["x"] @ m["router"].T)
    top, idx = jax.lax.top_k(s, top_k)
    w = scale * top / top.sum(-1, keepdims=True)
    y = 0.0
    for e in range(m["router"].shape[0]):
        weight = jnp.sum(jnp.where(idx == e, w, 0.0), -1, keepdims=True)
        y = y + weight * _swiglu(m["x"], m["gate"][e], m["up"][e],
                                 m["down"][e])
    sg, su, sd = m["shared"]
    return y + _swiglu(m["x"], sg.T, su.T, sd.T)


def _share(m, offset, held, top_k, scale, shared=True):
    extra = m["shared"] if shared else ()
    return get_op("MoEFFN").fn(
        m["x"], m["router"], m["gate"][offset:offset + held],
        m["up"][offset:offset + held], m["down"][offset:offset + held],
        jnp.zeros(3), *extra, num_experts=m["router"].shape[0],
        hidden_size=m["gate"].shape[-1], top_k=top_k, experts_held=held,
        expert_offset=offset, routed_scale=scale,
        shared_hidden_size=m["shared"][0].shape[0] if shared else 0,
        _is_train=True)


def test_the_shares_of_all_chips_add_up_to_the_whole_layer():
    """Offsets 0, E/4, ...: every share's routed part, the shared expert
    counted once, is the uncut reference's layer output; and every
    token-choice is counted by exactly one share."""
    m = _moe_inputs()
    total, choices = 0.0, 0.0
    for k, offset in enumerate(range(0, 16, 4)):
        y, stats = _share(m, offset, 4, 4, 2.5, shared=(k == 0))
        total = total + y
        choices += float(stats[0])
        assert float(stats[2]) == 0.0
    _close(total, _whole_layer(m, 4, 2.5), 1e-5)
    assert choices == 40 * 4


def test_moe_ffn_gradients_against_the_masked_sum():
    m = _moe_inputs()

    def fn(x, router, gate, up, down):
        mm = dict(m, x=x, router=router, gate=gate, up=up, down=down)
        return _share(mm, 0, 16, 4, 2.5)[0]

    def ref(x, router, gate, up, down):
        return _whole_layer(dict(m, x=x, router=router, gate=gate, up=up,
                                 down=down), 4, 2.5)

    _same_with_gradients(fn, ref, m["x"], m["router"], m["gate"], m["up"],
                         m["down"], tol=5e-5)


def test_nothing_is_dropped_when_every_token_chooses_one_expert():
    m = _moe_inputs()
    # expert 2's score is the largest for every token, top_k 1
    m["x"] = jnp.abs(m["x"])
    m["router"] = jnp.zeros_like(m["router"]).at[2].set(1.0)
    y, stats = _share(m, 0, 4, 1, 1.0)
    assert [float(v) for v in stats] == [40.0, 40.0, 0.0]
    _close(y, _whole_layer(m, 1, 1.0), 1e-5)
    # a share that does not hold it adds the shared expert alone
    y, stats = _share(m, 4, 4, 1, 1.0, shared=False)
    assert [float(v) for v in stats] == [0.0, 0.0, 0.0]
    _close(y, jnp.zeros_like(y))


@pytest.mark.parametrize("offset, on_held", [(0, 40), (4, 0), (12, 0)])
def test_the_grouped_matmul_does_the_same_work_wherever_the_routing_goes(
        monkeypatch, offset, on_held):
    """Every choice lies in a group, held or not (a step's time must not
    hang on the routing), and an absent expert's rows, which ride in the
    last held expert's group, reach neither the output nor its gradient."""
    m = _moe_inputs()
    m["x"] = jnp.abs(m["x"])
    m["router"] = jnp.zeros_like(m["router"]).at[3].set(1.0)   # all choose 3
    seen = []
    ragged_dot = jax.lax.ragged_dot

    def counting(lhs, rhs, group_sizes, **kw):
        seen.append((lhs.shape[0], int(jnp.sum(group_sizes))))
        return ragged_dot(lhs, rhs, group_sizes, **kw)

    monkeypatch.setattr(jax.lax, "ragged_dot", counting)
    y, stats = _share(m, offset, 4, 1, 1.0, shared=False)
    assert seen == [(40, 40)] * 3 and float(stats[0]) == on_held
    monkeypatch.undo()

    def loss(gate, up, down):
        mm = dict(m, gate=gate, up=up, down=down)
        return jnp.sum(_share(mm, offset, 4, 1, 1.0, shared=False)[0] ** 2)

    grads = jax.grad(loss, argnums=(0, 1, 2))(m["gate"], m["up"], m["down"])
    for g in grads:
        # only expert 3 was chosen: no other expert's weights move
        moved = {int(e) for e in np.nonzero(np.abs(np.asarray(g)).reshape(
            g.shape[0], -1).sum(1))[0]}
        assert moved == ({3} if on_held else set())
    if not on_held:
        _close(y, jnp.zeros_like(y))


def _plain_return(per_slot, weights, held):
    """What ``_weighted_return`` computes, as autodiff sees plain ``jnp``."""
    terms = jnp.where(held[..., None], per_slot, 0).astype(jnp.float32)
    return jnp.sum(terms * weights[..., None], axis=0).astype(per_slot.dtype)


@pytest.mark.parametrize("top_k", [1, 4, 8])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_weighted_return_against_plain_jnp(dtype, top_k):
    """Value and the cotangents of rows and weights (none for the mask)
    against the select, the float32 product and the sum written out; the
    rows of absent slots hold large finite garbage, and add exactly nothing
    to the output, to the weights' cotangent or to their own."""
    from mxnet_tpu.parallel.moe import _weighted_return
    t, d = 24, 16
    held = jax.random.bernoulli(jax.random.PRNGKey(5), 0.6, (top_k, t))
    rows = jnp.where(held[..., None], _rand(top_k, t, d, seed=1),
                     3e38).astype(dtype)
    weights = jnp.abs(_rand(top_k, t, seed=2)) + 0.5
    ct = _rand(t, d, seed=3).astype(dtype)
    # bf16 rounds once, to 8 bits; float32 differs by the order of a sum
    tol = 2e-5 if dtype == "float32" else 2.0 ** -8

    def both(fn):
        y, vjp = jax.vjp(fn, rows, weights, held)
        return (y,) + vjp(ct)

    got, want = both(jax.jit(_weighted_return)), both(_plain_return)
    assert got[0].dtype == got[1].dtype == rows.dtype
    assert got[2].dtype == jnp.float32
    assert got[3].dtype == jax.dtypes.float0      # the mask has no cotangent
    for g, w in zip(got[:3], want[:3]):
        assert np.isfinite(np.asarray(g, np.float32)).all()
        _close(np.asarray(g, np.float32), np.asarray(w, np.float32), tol)
    absent = ~np.asarray(held)
    assert not np.asarray(got[1], np.float32)[absent].any()
    assert not np.asarray(got[2])[absent].any()
    # tokens none of whose slots is held get exactly zero
    nobody = ~np.asarray(held).any(0)
    assert not np.asarray(got[0], np.float32)[nobody].any()


def _held_experts_token_major(x, router_w, w_gate, w_up, w_down, *,
                              num_experts, top_k, expert_offset=0,
                              routed_scale=1.0):
    """``held_experts_apply`` as it was before its slots went choice-major:
    slot ``t * k + j``, the absent rows selected to zero after the third
    matmul, the return as autodiff differentiates it."""
    from mxnet_tpu.parallel.moe import sigmoid_topk_router
    t, d = x.shape
    held = w_gate.shape[0]
    weights, chosen = sigmoid_topk_router(x, router_w, top_k, routed_scale)
    local = chosen.reshape(-1) - expert_offset
    local = jnp.where((local >= 0) & (local < held), local, held)
    take = jnp.argsort(local, stable=True)
    put = jnp.argsort(take)
    counts = jnp.sum(local[:, None] == jnp.arange(held)[None, :], axis=0,
                     dtype=jnp.int32)
    groups = counts.at[-1].add(t * top_k - jnp.sum(counts))
    rows = x[take // top_k]
    gate = jax.lax.ragged_dot(rows, w_gate, groups)
    up = jax.lax.ragged_dot(rows, w_up, groups)
    out = jax.lax.ragged_dot(jax.nn.silu(gate) * up, w_down, groups)
    out = jnp.where((jnp.arange(t * top_k) < jnp.sum(counts))[:, None],
                    out, 0)
    per_slot = out[put].reshape(t, top_k, d)
    y = jnp.sum(per_slot.astype(jnp.float32) * weights[..., None], axis=1)
    return y.astype(x.dtype), counts


@pytest.mark.parametrize("top_k, offset, held", [
    (1, 0, 16), (4, 0, 4), (4, 8, 4), (8, 4, 8)])
def test_choice_major_slots_give_the_layer_it_was(top_k, offset, held):
    """The same inputs through the layer as it was (token-major slots,
    plain autodiff) and as it is: the output, the count of choices on each
    held expert, and the gradients of the tokens, the router and the three
    expert stacks."""
    from mxnet_tpu.parallel.moe import held_experts_apply
    m = _moe_inputs()
    kw = dict(num_experts=16, top_k=top_k, expert_offset=offset,
              routed_scale=2.5)
    stacks = [m[n][offset:offset + held] for n in ("gate", "up", "down")]
    args = (m["x"], m["router"], *stacks)
    assert np.array_equal(held_experts_apply(*args, **kw)[1],
                          _held_experts_token_major(*args, **kw)[1])
    _same_with_gradients(lambda *a: held_experts_apply(*a, **kw)[0],
                         lambda *a: _held_experts_token_major(*a, **kw)[0],
                         *args, tol=5e-5)


def _fused_returns_traced(cfg):
    """What ``moe.fused_return_layers`` grows by while ``cfg``'s training
    step, its blocks checkpoints, is traced."""
    before = mx.profiler.counters().get("moe.fused_return_layers", 0)
    _trace_tiny_graph(cfg, remat_blocks=True, is_train=True)
    return mx.profiler.counters().get("moe.fused_return_layers", 0) - before


def test_the_counter_says_how_many_routed_layers_return_through_the_op():
    """+ 1 for each routed layer while a training step is traced (the
    blocks checkpoints, as the decoder cells bind them), nothing for a
    dense model."""
    five = dict(num_hidden_layers=5,
                layer_types=["full_attention"] + ["sliding_attention"] * 3
                + ["full_attention"],
                num_attention_heads_per_layer=[12, 16, 16, 16, 12])
    assert _fused_returns_traced(_tiny(
        mlp_layer_types=["dense"] + ["sparse"] * 4, **five)) == 4
    assert _fused_returns_traced(_tiny(
        mlp_layer_types=["dense"] * 5, **five)) == 0


# -- the model ------------------------------------------------------------------

def _tiny(**over):
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "laguna-xs2.json")) as f:
        cfg = json.load(f)
    cfg.update(cfg["rehearse"])
    # every kind of layer once: full and dense, sliding and sparse, full
    # and sparse
    cfg.update(num_hidden_layers=3, compute_dtype="float32",
               layer_types=["full_attention", "sliding_attention",
                            "full_attention"],
               num_attention_heads_per_layer=[12, 16, 12],
               mlp_layer_types=["dense", "sparse", "sparse"])
    cfg.update(over)
    return cfg


def test_symbol_follows_the_per_layer_lists():
    cfg = _tiny()
    plan = models.decoder_lm.layer_plan(cfg)
    assert [p["attention"] for p in plan] == [
        "full_attention", "sliding_attention", "full_attention"]
    assert [p["heads"] for p in plan] == [12, 16, 12]
    assert [p["window"] for p in plan] == [0, 16, 0]
    assert [p["mlp"] for p in plan] == ["dense", "sparse", "sparse"]
    assert plan[0]["rope"]["rope_type"] == "yarn"
    sym = models.get_symbol("decoder_lm", cfg=cfg)
    assert sym.list_auxiliary_states() == [
        f"layer{k}_moe_stats" for k in range(1, 3)]
    shapes = dict(zip(sym.list_arguments(), sym.infer_shape(
        data=(2, 32), softmax_label=(2, 32))[0]))
    assert shapes["layer1_q_weight"] == (16 * 16, 64)
    assert shapes["layer0_q_weight"] == (12 * 16, 64)
    assert shapes["layer0_gate_weight"] == (12, 64)
    assert shapes["layer1_moe_router_weight"] == (16, 64)
    assert shapes["layer1_moe_expert_gate_weight"] == (4, 64, 32)
    assert shapes["layer0_mlp_down_weight"] == (64, 128)
    # every node of a layer carries its block and the ask to recompute it
    blocks = {n.scope_attrs.get("__block__") for n in sym._topo_nodes()
              if not n.is_variable}
    assert blocks == {None, "loss_head"} | {f"layer{k}" for k in range(3)}


def _through_the_kernel(monkeypatch):
    """Send ``GroupedQueryAttention`` down the chip's path on the CPU: the
    kernel and its backward through the Pallas interpreter."""
    from mxnet_tpu.ops.pallas import attention
    monkeypatch.setattr(attention, "grouped_query_attention", functools.partial(
        attention.grouped_query_attention, force_pallas=True))


def _tiny_graph(cfg):
    """The symbol with one document of 32 tokens: arguments and auxiliary
    states to call its graph with."""
    sym = models.get_symbol("decoder_lm", cfg=cfg)
    shapes = dict(zip(sym.list_arguments(), sym.infer_shape(
        data=(1, 32), softmax_label=(1, 32))[0]))
    rng = np.random.default_rng(0)
    args = {n: jnp.asarray(0.1 * rng.standard_normal(shape), jnp.float32)
            for n, shape in shapes.items()}
    args["data"] = jnp.asarray(rng.integers(0, 96, (1, 32)), jnp.float32)
    args["softmax_label"] = args["data"]
    aux = {n: jnp.zeros(3) for n in sym.list_auxiliary_states()}
    return sym, args, aux


def _trace_tiny_graph(cfg, remat_blocks, is_train):
    """Trace the tiny graph's loss, in training its gradient, once (x64
    off, as the chip runs): what the trace-time counters count."""
    from mxnet_tpu.executor import build_graph_eval
    sym, args, aux = _tiny_graph(cfg)
    fn = build_graph_eval(sym, remat_blocks=remat_blocks)

    def f(p):
        return fn(dict(args, **p), aux, None, is_train)[0][0][0]

    params = {n: v for n, v in args.items()
              if n not in ("data", "softmax_label")}
    with jax.enable_x64(False):
        jax.make_jaxpr(jax.grad(f) if is_train else f)(params)


@pytest.mark.parametrize("case,kept", [
    ("kernel, checkpoints, training", 4), ("kernel, checkpoints, inference", 0),
    ("kernel, no checkpoints, training", 0),
    ("plain path, checkpoints, training", 0)])
def test_counters_say_what_the_block_checkpoints_keep(monkeypatch, case,
                                                      kept):
    """``remat.kept_values`` / ``remat.kept_bytes`` grow while a training
    step whose blocks are checkpoints is traced, by ``out`` and ``lse`` of
    every attention that ran the kernel, and at no other time."""
    path, blocks, mode = case.split(", ")
    if path == "kernel":
        _through_the_kernel(monkeypatch)
    cfg = _tiny(num_hidden_layers=2)
    before = mx.profiler.counters()
    _trace_tiny_graph(cfg, blocks == "checkpoints", mode == "training")
    # a layer keeps out (1, H, 32, 16) and lse (1, H, 32) in float32
    heads = sum(cfg["num_attention_heads_per_layer"][:2])
    assert _kept_since(before) == {
        "remat.kept_values": kept,
        "remat.kept_bytes": 4 * heads * 32 * (16 + 1) if kept else 0}


@pytest.mark.parametrize("path", ["plain", "kernel"])
def test_blocks_are_checkpoints_where_the_model_asks(monkeypatch, path):
    from mxnet_tpu import compiler
    from mxnet_tpu.executor import build_graph_eval
    if path == "kernel":
        # the checkpoints then keep the attention's out and lse
        _through_the_kernel(monkeypatch)
    cfg = _tiny(num_hidden_layers=2)
    sym = models.get_symbol("decoder_lm", cfg=cfg)
    assert compiler.optimize(sym, for_training=True).remat_blocks
    assert "rematblocks=1" in compiler.optimize(sym).transform_sig
    plain = models.get_symbol("decoder_lm", cfg=dict(cfg, recompute=None))
    assert not compiler.optimize(plain, for_training=True).remat_blocks
    assert not compiler.optimize(sym, for_training=False).remat_blocks

    _, args, aux = _tiny_graph(cfg)

    def loss(remat):
        fn = build_graph_eval(sym, remat_blocks=remat)

        def f(p):
            outs, ups = fn(dict(args, **p), aux, None, True)
            return outs[0][0], ups
        params = {n: v for n, v in args.items()
                  if n not in ("data", "softmax_label")}
        step = jax.jit(jax.value_and_grad(f, has_aux=True))
        return step(params), step.lower(params).as_text()

    ((a, ups_a), ga), text_a = loss(True)
    ((b, ups_b), gb), text_b = loss(False)
    assert float(a) == pytest.approx(float(b), rel=1e-6)
    for n in ga:
        _close(ga[n], gb[n], 1e-5)
    _close(ups_a["layer1_moe_stats"], ups_b["layer1_moe_stats"])
    # a checkpoint keeps its inside from being shared with the backward
    # (the routed layer's return holds one of its own either way)
    assert text_a.count("optimization_barrier") > \
        text_b.count("optimization_barrier")


def test_tiny_model_trains_through_fit_like_the_reference():
    """Every layer kind once, 16 experts of which 4 held, through
    ``SPMDTrainer.fit`` fed by ``PrefetchingIter(NDArrayIter)``: loss of
    each step, the first gradient and three Adam steps against the
    benchmark's plain reference."""
    from perfbench import compare, feed
    from perfbench import run as harness
    cell = harness.load_cell("laguna-xs2.train-fed-seq8k", rehearse=True)
    cfg = _tiny(**{k: v for k, v in cell["cfg"].items()
                   if k in ("flops_seq_len", "reference")})
    traffic = cell["traffic_params"]
    model = harness.load_module("models", "laguna-xs2")
    driver = harness.load_module("drivers", "train_fit")
    program = model.Program(cfg, traffic, 7, jax.devices())
    batches = model.make_batches(cfg, traffic, 7)
    window = feed.Window(feed.inner_iterator(
        traffic, batches, program.input_shardings(), program.input_names))
    record = driver.checked_steps(program, window, batches, 3)
    # the first boundary read publishes nothing; the one after two more
    # steps adds what the device's counters grew by to the program's own
    counters = program.routed_counters()
    by_node = program.trainer.aux_counters()
    assert sorted(by_node) == ["layer1_moe", "layer2_moe"]
    assert set(by_node["layer1_moe"]) == set(model.ROUTED)
    before = mx.profiler.counters().get("moe.assignments_held", 0)
    assert set(program.counters()) == {"step_programs"}
    assert mx.profiler.counters().get("moe.assignments_held", 0) == before
    window.arm(batches=2)
    program.fit(window)
    program.sync()
    program.counters()
    assert mx.profiler.counters()["moe.assignments_held"] - before == \
        program.routed_counters()["moe.assignments_held"] \
        - counters["moe.assignments_held"] > 0
    program.close()
    ref = model.reference(cfg, traffic, 7, devices=jax.devices())
    numbers, _ = compare.gaps(record, ref)
    assert numbers["loss_gap"] < 1e-5
    assert numbers["grad_gap_worst"] < 2e-3
    assert numbers["delta_gap_worst"] < 2e-3
    # four batches ran (three checked and one more): every routed layer
    # counted its held choices, none left out
    tokens = 4 * model.items_per_batch(cfg, traffic)
    assert 0 < counters["moe.assignments_held"] <= 2 * 4 * tokens
    assert counters["moe.overflow"] == 0
    assert counters["moe.load_max"] * 4 >= counters["moe.assignments_held"]
