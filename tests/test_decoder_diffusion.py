"""What a block-diffusion sparse decoder (SDAR-30B-A3B) adds to
``models/decoder_lm.py`` and its ops, at a small size on the CPU: the mask
against its three-line definition; the kernel's walk of the mask's live
tiles, and the kernel and its backward through the interpreter against the
plain reference; the two dependences that make it block diffusion; the 2 L
pass against L / B separate passes; positions that wrap; the softmax router
and the eight shares of a routed layer; the weighted loss; and the whole tiny
model against the benchmark's plain reference."""
import hashlib
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import models
from mxnet_tpu.base import MXNetError
from mxnet_tpu.executor import build_graph_eval
from mxnet_tpu.ops.pallas import attention
from mxnet_tpu.ops.registry import get_op
from mxnet_tpu.parallel import moe
from mxnet_tpu.symbol.symbol import NameManager, Symbol

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import run as harness  # noqa: E402


def _rand(*shape, seed=0, scale=1.0):
    return scale * jax.random.normal(jax.random.PRNGKey(seed), shape,
                                     jnp.float32)


def _close(a, b, tol=2e-5):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=tol,
                               atol=tol)


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "sdar-30b-a3b.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def model():
    return harness.load_module("models", "sdar-30b-a3b")


def _tiny(cfg, **over):
    return dict(cfg, **dict(cfg["rehearse"], compute_dtype="float32",
                            **over))


# -- the mask and the walk of its live tiles ----------------------------------

def _see(q, k, length, block):
    """The three lines of ISSUE 33, one pair at a time."""
    def blk(i):
        return (i % length) // block

    def noisy(i):
        return i < length

    return (noisy(q) and noisy(k) and blk(q) == blk(k)) \
        or (noisy(q) and not noisy(k) and blk(q) > blk(k)) \
        or (not noisy(q) and not noisy(k) and blk(q) >= blk(k))


def _mask_by_a_double_loop(length, block):
    return np.array([[_see(q, k, length, block) for k in range(2 * length)]
                     for q in range(2 * length)])


@pytest.mark.parametrize("length,block", [(16, 4), (32, 16), (24, 8),
                                          (12, 1)])
def test_mask_against_its_three_line_definition(length, block, model):
    pos = jnp.arange(2 * length)
    want = _mask_by_a_double_loop(length, block)
    got = attention._band_mask(pos[:, None], pos[None, :], True, 0, block,
                               length)
    assert (np.asarray(got) == want).all()
    # the benchmark's reference writes the same three lines again
    assert (np.asarray(model.see(pos[:, None], pos[None, :], length, block))
            == want).all()
    # L (L + B) live pairs for L tokens: twice a causal pass of L
    assert want.sum() == length * (length + block)
    # the planted fault lets a noisy block see its own clean tokens
    leak = np.asarray(model.see(pos[:, None], pos[None, :], length, block,
                                "leak"))
    assert (leak & ~want).sum() == length * block and (want & ~leak).sum() == 0
    # and the other takes the clean blocks before its own away
    blind = np.asarray(model.see(pos[:, None], pos[None, :], length, block,
                                 "blind"))
    assert (want & ~blind).sum() == length * (length - block) // 2
    assert (blind & ~want).sum() == 0


@pytest.mark.parametrize("tiles,visits", [(1, 3), (2, 8), (4, 24),
                                          (16, 288)])
def test_the_walk_visits_the_masks_live_tiles_and_no_other(tiles, visits):
    """``tiles`` tiles of 512 a half, blocks of 4: the schedule's table holds
    every (query tile, key tile) with a live pair once and none without one;
    288 visits for 32 query tiles at L = 8,192 where a causal walk of 2 L
    makes 528 and a dense one 1,024. Noisy tile i reads its own tile and
    then the clean tiles n .. n + i, clean tile n + i reads n .. n + i; the
    mask cuts a noisy tile's two diagonal tiles and a clean tile's one."""
    tile, block, n = 512, 4, tiles
    q_tile, k_tile, flags = attention._visit_table(
        2 * n, 2 * n, tile, tile, True, 0, block)
    assert len(q_tile) == visits
    walks = {i: k_tile[q_tile == i].tolist() for i in range(2 * n)}
    assert walks == {**{i: [i] + list(range(n, n + i + 1)) for i in range(n)},
                     **{n + i: list(range(n, n + i + 1)) for i in range(n)}}
    cut = flags & attention._CUT != 0
    assert (cut == ((k_tile == q_tile) | (k_tile == q_tile + n))).all()
    assert int(cut.sum()) == 3 * n
    if n <= 4:
        # the same walk in tiles of 8, against the mask written out
        assert _table_against_the_mask(n, 8, 4) == visits
    causal = attention._visit_table(2 * n, 2 * n, tile, tile, True, 0)
    assert len(causal[0]) == n * (2 * n + 1)           # 528 at n = 16


def _table_against_the_mask(n, tile, block):
    """The table of two halves of ``n`` tiles against the mask written out
    pair by pair: the visits are its live tiles, the cut flag is true exactly
    on tiles with both a live and a dead pair. Returns the visits."""
    q_tile, k_tile, flags = attention._visit_table(
        2 * n, 2 * n, tile, tile, True, 0, block)
    tiles = _mask_by_a_double_loop(n * tile, block).reshape(
        2 * n, tile, 2 * n, tile)
    assert sorted(zip(q_tile.tolist(), k_tile.tolist())) == [
        tuple(x) for x in np.argwhere(tiles.any(axis=(1, 3))).tolist()]
    assert ((flags & attention._CUT != 0)
            == ~tiles.all(axis=(1, 3))[q_tile, k_tile]).all()
    first = np.r_[True, q_tile[1:] != q_tile[:-1]]
    assert ((flags & attention._OPENS != 0) == first).all()
    assert ((flags & attention._CLOSES != 0) == np.r_[first[1:], True]).all()
    return len(q_tile)


@pytest.mark.parametrize("tile,block,visits", [(16, 16, 6), (16, 8, 8),
                                               (8, 1, 24), (12, 4, 8)])
def test_a_block_that_fills_its_tile_is_not_cut(tile, block, visits):
    """Blocks as large as the tile: the noisy diagonal tile is whole and a
    noisy tile's own clean tile is dead, so it is neither cut nor visited;
    smaller blocks cut both."""
    n = 2 if tile != 8 else 4
    assert _table_against_the_mask(n, tile, block) == visits
    flags = attention._visit_table(2 * n, 2 * n, tile, tile, True, 0,
                                   block)[2]
    assert bool((flags & attention._CUT).any()) == (block < tile)


@pytest.mark.parametrize("tile,n,window,block", [
    (16, 8, 0, 0), (16, 8, 16, 0), (16, 8, 40, 0), (16, 8, 5, 0),
    (16, 8, 0, 4), (16, 8, 0, 16), (16, 2, 0, 4), (512, 32, 0, 4)])
def test_the_backwards_closed_form_is_a_view_of_the_table(tile, n, window,
                                                          block):
    """The backward's loops take bounds and key tiles from arithmetic on the
    loop counters (``_run``: what XLA compiles into updates in place), and
    that arithmetic is held to the table tile by tile, with numbers and
    traced alike."""
    q_tile, k_tile, _ = attention._visit_table(n, n, tile, tile, True, window,
                                               block)
    attention._checked_run(tile, n, True, window, block)
    for i in {0, 1, n // 2 - 1, n // 2, n - 1}:
        lo, hi, key_of = jax.jit(
            lambda i: (lambda lo, hi, key_of: (lo, hi, key_of(
                jnp.arange(n + 1))))(*attention._run(
                    i, tile, n, True, window, block)))(jnp.int32(i))
        assert np.asarray(key_of)[int(lo):int(hi)].tolist() \
            == k_tile[q_tile == i].tolist()


def test_forward_and_backward_walk_the_one_table(monkeypatch):
    """The kernel's grid reads ``_visit_table`` and the backward's closed
    form is checked against it: with query tile 1's last visit taken out of
    the table the kernel's output changes in exactly the rows that lost
    keys, and the backward refuses to walk what the table no longer says."""
    q, k, v, ct = _qkv(32, 4, 1)       # two halves of two tiles of 16
    real = attention._visit_table

    def shortened(*a):
        q_tile, k_tile, flags = (x.copy() for x in real(*a))
        drop = np.flatnonzero(q_tile == 1)[-1]
        flags[drop - 1] |= attention._CLOSES
        return tuple(np.delete(x, drop) for x in (q_tile, k_tile, flags))

    def run():
        return jax.vjp(lambda *a: attention.grouped_query_attention(
            *a, block=16, force_pallas=True, block_length=4), q, k, v)

    want, vjp = run()
    vjp(ct)
    monkeypatch.setattr(attention, "_visit_table", shortened)
    got, vjp = run()
    # query tile 1 (rows 16..31, noisy) lost its clean tile 3 (keys 48..63),
    # of which its first block of 4 saw nothing
    rows = np.zeros(64, bool)
    rows[20:32] = True
    moved = np.abs(np.asarray(got - want)).max(axis=(0, 1, 3)) > 1e-6
    assert (moved == rows).all()
    with pytest.raises(NotImplementedError, match="query tile 1 of 4"):
        vjp(ct)


def _qkv(length, heads, kv, d=16, seed=0):
    s = 2 * length
    return (_rand(1, heads, s, d, seed=seed),
            _rand(1, kv, s, d, seed=seed + 1),
            _rand(1, kv, s, d, seed=seed + 2),
            _rand(1, heads, s, d, seed=seed + 3))


@pytest.mark.parametrize("tiles", [1, 4])
@pytest.mark.parametrize("group", [4, 8])
@pytest.mark.parametrize("block", [4, 16])
def test_kernel_and_backward_against_the_plain_reference(block, group, tiles):
    """The kernel and the blockwise backward through the Pallas interpreter,
    tiles of 16 (so blocks of 16 fill a tile and blocks of 4 cut it), one
    tile a half and several."""
    q, k, v, ct = _qkv(16 * tiles, group, 1)

    def kernel(q, k, v):
        return attention.grouped_query_attention(
            q, k, v, block=16, force_pallas=True, block_length=block)

    def plain(q, k, v):
        return attention.gqa_attention_reference(q, k, v, True, 0, None,
                                                 block)

    _close(kernel(q, k, v), plain(q, k, v), 5e-6)
    got = jax.grad(lambda *a: jnp.sum(kernel(*a) * ct), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * ct), (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        _close(g, w, 2e-5)


def test_reference_is_the_masked_softmax_written_out():
    q, k, v, _ = _qkv(8, 2, 1, d=4)
    mask = _mask_by_a_double_loop(8, 4)
    s = jnp.einsum("hqd,kd->hqk", q[0], k[0, 0]) / 2.0
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    _close(attention.gqa_attention_reference(q, k, v, True, 0, None, 4)[0],
           jnp.einsum("hqk,kd->hqd", p, v[0, 0]), 1e-6)


def test_the_kernel_path_counts_its_layers():
    q, k, v, ct = _qkv(16, 4, 2)
    before = mx.profiler.counters().get("attention.block_diffusion_layers", 0)
    jax.grad(lambda q: jnp.sum(attention.grouped_query_attention(
        q, k, v, block=16, force_pallas=True, block_length=4) * ct))(q)
    assert mx.profiler.counters()["attention.block_diffusion_layers"] \
        == before + 1
    # the band paths count nothing
    jax.grad(lambda q: jnp.sum(attention.grouped_query_attention(
        q, k, v, block=16, force_pallas=True) * ct))(q)
    assert mx.profiler.counters()["attention.block_diffusion_layers"] \
        == before + 1


@pytest.mark.parametrize("kwargs,said", [
    ({"block_length": 4, "window": 8}, "window=0"),
    ({"block_length": 4, "causal": False}, "causal=True"),
    ({"block_length": 3}, "divides"),
    ({"block_length": 32}, "divides"),
])
def test_the_mask_refuses_what_it_is_not_built_for(kwargs, said):
    q, k, v, _ = _qkv(16, 4, 2)
    with pytest.raises(ValueError, match=said):
        attention.grouped_query_attention(q, k, v, block=16, **kwargs)
    if "causal" not in kwargs and "window" not in kwargs:
        op = get_op("GroupedQueryAttention").fn
        flat = [x.transpose(0, 2, 1, 3).reshape(1, 32, -1) for x in (q, k, v)]
        with pytest.raises(MXNetError, match="GroupedQueryAttention"):
            op(*flat, num_heads=4, num_kv_heads=2, **kwargs)


def test_an_odd_length_has_no_two_halves():
    q, k, v = (_rand(1, 2, 15, 8, seed=s) for s in range(3))
    with pytest.raises(ValueError, match="even length"):
        attention.grouped_query_attention(q, k, v, block_length=1)


# -- positions that wrap ------------------------------------------------------

@pytest.mark.parametrize("rotary_dim", [0, 8])
def test_rotary_positions_wrap_at_the_documents_length(rotary_dim):
    op = get_op("RotaryEmbedding").fn
    x = _rand(2, 24, 32)
    attrs = dict(head_dim=16, rotary_dim=rotary_dim, theta=1e6)
    whole = op(x, copies=2, **attrs)
    _close(whole[:, :12], op(x[:, :12], **attrs), 1e-6)
    _close(whole[:, 12:], op(x[:, 12:], **attrs), 1e-6)
    # without it the second half stands at positions 12 .. 23
    assert float(jnp.max(jnp.abs(op(x, **attrs)[:, 12:] - whole[:, 12:]))) \
        > 1e-2
    _close(op(x, copies=1, **attrs), op(x, **attrs), 0)
    # three copies of 8; and 24 positions are no five copies of anything
    thirds = op(x, copies=3, **attrs)
    _close(thirds[:, 16:], op(x[:, 16:], **attrs), 1e-6)
    with pytest.raises(MXNetError, match="5 copies"):
        op(x, copies=5, **attrs)


# -- the softmax router and the shares of a routed layer ----------------------

def _router_by_hand(x, router_w, k, scale=1.0):
    p = jax.nn.softmax(x @ router_w.T, axis=-1)
    top, idx = jax.lax.top_k(p, k)
    return scale * top / jnp.sum(top, -1, keepdims=True), idx


def test_softmax_router_weights_and_gradients_against_jnp():
    x, router_w = _rand(12, 16), _rand(8, 16, seed=1)
    w, idx = moe.scored_topk_router(x, router_w, 3, 1.5, score_func="softmax")
    want_w, want_idx = _router_by_hand(x, router_w, 3, 1.5)
    assert (np.asarray(idx) == np.asarray(want_idx)).all()
    _close(w, want_w, 1e-6)
    _close(jnp.sum(w, -1), 1.5 * jnp.ones(12), 1e-6)
    # the weights are a softmax over the chosen logits
    chosen = jnp.take_along_axis(x @ router_w.T, idx, axis=-1)
    _close(w, 1.5 * jax.nn.softmax(chosen, axis=-1), 1e-6)
    ct = _rand(12, 3, seed=5)
    for arg in (0, 1):
        got = jax.grad(lambda *a: jnp.sum(moe.scored_topk_router(
            *a, 3, 1.5, score_func="softmax")[0] * ct), arg)(x, router_w)
        want = jax.grad(lambda *a: jnp.sum(
            _router_by_hand(*a, 3, 1.5)[0] * ct), arg)(x, router_w)
        _close(got, want, 1e-5)
    # the sigmoid router is what it was, and is the default
    plain = moe.sigmoid_topk_router(x, router_w, 3)[0]
    _close(plain, moe.scored_topk_router(x, router_w, 3,
                                         score_func="sigmoid")[0], 0)
    assert float(jnp.max(jnp.abs(plain - w / 1.5))) > 1e-3
    with pytest.raises(MXNetError, match="score_func"):
        moe.scored_topk_router(x, router_w, 3, score_func="tanh")


def _whole_layer(x, router_w, gate, up, down, k):
    """Every expert over every token, weighted by the router's choice."""
    w, idx = _router_by_hand(x, router_w, k)
    weight = jnp.zeros((x.shape[0], router_w.shape[0])).at[
        jnp.arange(x.shape[0])[:, None], idx].add(w)
    out = jnp.zeros_like(x)
    for e in range(router_w.shape[0]):
        out = out + weight[:, e:e + 1] * (
            (jax.nn.silu(x @ gate[e]) * (x @ up[e])) @ down[e])
    return out


def test_the_eight_shares_of_a_routed_layer_add_up_to_the_whole():
    """16 experts, 4 a token, two held a chip: the parts that the eight
    chips of the deployment compute add up to the uncut reference layer,
    through the op and with gradients."""
    t, d, f, e, k, held = 24, 16, 8, 16, 4, 2
    x, router_w = _rand(t, d), _rand(e, d, seed=1)
    gate, up = _rand(e, d, f, seed=2, scale=0.3), _rand(e, d, f, seed=3,
                                                        scale=0.3)
    down = _rand(e, f, d, seed=4, scale=0.3)
    op = get_op("MoEFFN").fn

    def shares(x, router_w, gate, up, down):
        total, counted = 0.0, 0
        for c in range(e // held):
            own = slice(c * held, (c + 1) * held)
            y, stats = op(x, router_w, gate[own], up[own], down[own],
                          jnp.zeros(3), num_experts=e, hidden_size=f, top_k=k,
                          experts_held=held, expert_offset=c * held,
                          score_func="softmax", _is_train=True)
            total, counted = total + y, counted + stats[0]
        return total, counted

    total, counted = shares(x, router_w, gate, up, down)
    assert int(counted) == t * k            # every choice lands on one chip
    _close(total, _whole_layer(x, router_w, gate, up, down, k), 2e-5)
    ct = _rand(t, d, seed=9)
    args = (x, router_w, gate, up, down)
    got = jax.grad(lambda *a: jnp.sum(shares(*a)[0] * ct),
                   tuple(range(5)))(*args)
    want = jax.grad(lambda *a: jnp.sum(_whole_layer(*a, k) * ct),
                    tuple(range(5)))(*args)
    for g, w in zip(got, want):
        _close(g, w, 5e-5)


def test_moe_ffn_scores_as_the_configuration_says(cfg):
    def node(c):
        return [n for n in models.get_symbol(
            "decoder_lm", cfg=c)._topo_nodes()
            if not n.is_variable and n.op.name == "MoEFFN"][0]

    tiny = _tiny(cfg)
    assert node(tiny).attrs["score_func"] == "softmax"
    plain = {k: v for k, v in tiny.items() if k != "score_func"}
    assert node(plain).attrs["score_func"] == "sigmoid"
    assert node(tiny).attrs["top_k"] == 2
    assert node(cfg).attrs["experts_held"] == 16


# -- the weighted loss --------------------------------------------------------

def test_weighted_loss_its_zero_weight_rows_and_its_counter():
    op = get_op("TokenCrossEntropy").fn
    logits, label = _rand(2, 6, 10), jnp.asarray(
        np.random.default_rng(0).integers(0, 10, (2, 6)), jnp.float32)
    weight = jnp.asarray([[0, 2.0, 0, 1.25, 0, 0], [10.0, 0, 0, 0, 1.0, 0]])
    loss, stats = op(logits, label, weight, jnp.asarray([7.0]),
                     weighted=True, _is_train=True)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, label.astype(jnp.int32)[..., None],
                                 axis=-1)[..., 0]
    _close(loss, (-jnp.sum(weight * picked) / 12).reshape(1), 1e-6)
    assert loss.shape == (1,) and loss.dtype == jnp.float32
    assert float(stats[0]) == 7.0 + 4       # the positions with w > 0
    # outside training the counter stands
    assert float(op(logits, label, weight, jnp.asarray([7.0]), weighted=True,
                    _is_train=False)[1][0]) == 7.0
    grad = jax.grad(lambda z: op(z, label, weight, jnp.zeros(1),
                                 weighted=True)[0][0])(logits)
    dead = np.asarray(weight) == 0
    assert float(jnp.max(jnp.abs(grad[dead]))) == 0.0
    assert float(jnp.min(jnp.max(jnp.abs(grad[~dead]), axis=-1))) > 0
    # all weights 1: the plain mean, which is what the op gives without them
    _close(op(logits, label, jnp.ones((2, 6)), jnp.zeros(1),
              weighted=True)[0], op(logits, label), 1e-6)
    # bfloat16 logits are widened before the log-softmax
    _close(op(logits.astype(jnp.bfloat16), label, weight, jnp.zeros(1),
              weighted=True)[0], loss, 2e-2)


def test_the_weight_and_the_counter_are_inputs_the_attribute_adds():
    z = mx.sym.var("z")
    plain = mx.sym.TokenCrossEntropy(z, mx.sym.var("y"), name="loss")
    assert plain.list_arguments() == ["z", "y"]
    assert plain.list_auxiliary_states() == []
    weighted = mx.sym.TokenCrossEntropy(z, mx.sym.var("y"), mx.sym.var("w"),
                                        weighted=True, name="loss")
    assert weighted.list_arguments() == ["z", "y", "w"]
    assert weighted.list_auxiliary_states() == ["loss_stats"]
    args, outs, aux = weighted.infer_shape(z=(2, 6, 10))
    assert args == [(2, 6, 10), (2, 6), (2, 6)] and aux == [(1,)]
    assert outs == [(1,)]
    assert get_op("TokenCrossEntropy").aux_counters \
        == {3: ("loss.weighted_tokens",)}


# -- the model ----------------------------------------------------------------

def _batch(model, tiny, length, rows=2, seed=5):
    traffic = {"per_chip_batch": rows, "chips": 1, "seq_len": length,
               "zipf_exponent": 1.0, "distinct_batches": 1}
    return model.make_batches(tiny, traffic, seed)[0]


def _graph(tiny, model, length):
    sym = models.get_symbol("decoder_lm", cfg=tiny)
    params = jax.device_get(model.init_params(tiny, 3))
    aux = {n: jnp.zeros(1 if n == "loss_stats" else 3)
           for n in sym.list_auxiliary_states()}
    return sym, params, aux


def _hidden(sym):
    """The stream after the last layer, all 2 L rows: what the slice of the
    noisy half reads."""
    node = [n for n in sym._topo_nodes() if n.name == "noisy_half"][0]
    return Symbol([node.inputs[0]])


def test_batches_are_a_noisy_and_a_clean_copy_with_weights(cfg, model):
    tiny = _tiny(cfg)
    data, label = _batch(model, tiny, 64, rows=3)
    assert data.shape == (3, 128) and label.shape == (3, 2, 64)
    assert data.dtype == label.dtype == np.float32
    xt, x0, target, weight = data[:, :64], data[:, 64:], label[:, 0], \
        label[:, 1]
    assert (x0 == target).all() and x0.max() < 95 and x0.min() >= 0
    masked = xt == 95
    assert (xt[~masked] == x0[~masked]).all()
    assert ((weight > 0) == masked).all()
    # one t a block: a block's masked positions carry one weight, 1 / t_b
    # with t_b in [0.1, 1]
    for row in range(3):
        for b in range(16):
            w = weight[row, 4 * b:4 * b + 4]
            assert len(set(w[w > 0])) <= 1
    assert weight[masked].min() >= 1.0 and weight[masked].max() <= 10.0
    assert 0.3 < masked.mean() < 0.8
    # the same seed gives the same batch, another seed another
    again = _batch(model, tiny, 64, rows=3)
    assert (again[0] == data).all() and (again[1] == label).all()
    assert (_batch(model, tiny, 64, rows=3, seed=6)[0] != data).any()
    # a large seed is a seed
    _batch(model, tiny, 64, seed=2 ** 31 + 11)
    with pytest.raises(SystemExit, match="block_length"):
        model.make_batches(tiny, {"per_chip_batch": 1, "chips": 1,
                                  "seq_len": 64, "zipf_exponent": 1.0,
                                  "distinct_batches": 1, "block_length": 8},
                           5)


def test_symbol_of_the_cut_configuration(cfg, model):
    sym = models.get_symbol("decoder_lm", cfg=cfg)
    shapes = dict(zip(sym.list_arguments(), sym.infer_shape(
        data=(2, 128), softmax_label=(2, 2, 64))[0]))
    assert shapes.pop("data") == (2, 128)
    assert shapes.pop("softmax_label") == (2, 2, 64)
    assert shapes == model.param_shapes(cfg)
    assert shapes["layer0_q_weight"] == (4096, 2048)
    assert shapes["layer0_k_weight"] == (512, 2048)
    assert shapes["layer3_moe_expert_gate_weight"] == (16, 2048, 768)
    assert shapes["lm_head_weight"] == (18992, 2048)
    assert sym.list_auxiliary_states() == [
        f"layer{k}_moe_stats" for k in range(4)] + ["loss_stats"]
    by_name = {n.name: n for n in sym._topo_nodes() if not n.is_variable}
    assert by_name["layer2_attn"].attrs["block_length"] == 4
    assert by_name["layer2_attn"].attrs["num_kv_heads"] == 4
    # the document's length is the shape's: no key of the graph carries it
    assert by_name["layer2_q_rope"].attrs["copies"] == 2
    assert by_name["noisy_half"].attrs["num_outputs"] == 2
    assert by_name["layer2_k_rope"].attrs["theta"] == 1e6
    assert by_name["loss"].attrs["weighted"] is True
    assert by_name["noisy_half"].scope_attrs["__block__"] == "loss_head"
    assert all(n.attrs["score_func"] == "softmax" for n in by_name.values()
               if n.op.name == "MoEFFN")


def test_parameter_counts_published_and_cut(cfg, model):
    def count(shapes):
        return sum(int(np.prod(s)) for s in shapes.values())

    assert count(model.param_shapes(cfg)) == 456_346_624
    whole = model.uncut(cfg)
    assert (whole["num_hidden_layers"], whole["num_experts_held"],
            whole["vocab_size"]) == (48, 128, 151936)
    assert abs(count(model.param_shapes(whole)) - 30.5e9) / 30.5e9 < 0.01
    # the published widths stand; the cut keys are the three
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts_held",
                              "vocab_size"]
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["moe_intermediate_size"], cfg["num_experts"],
            cfg["num_experts_per_tok"], cfg["intermediate_size"],
            cfg["max_position_embeddings"], cfg["rope_theta"],
            cfg["rms_norm_eps"], cfg["norm_topk_prob"],
            cfg["tie_word_embeddings"]) \
        == (2048, 32, 4, 128, 768, 128, 8, 6144, 32768, 1000000, 1e-6, True,
            False)
    for key in ("objective", "block_length", "noise", "mask_token_id",
                "qk_norm", "score_func", "optimizer", "init", "data"):
        assert len(cfg["assumed"][key]) > 40
    assert "eight chips" in cfg["deployment"]


@pytest.mark.parametrize("bad,said", [
    ({"objective": "masked_lm"}, "unknown objective"),
    ({"block_length": 0}, "no length of a block"),
    ({"block_length": -4}, "no length of a block"),
    ({"layer_types": ["conv", "full_attention"], "conv_L_cache": 3},
     "full attention"),
])
def test_decoder_lm_refuses_an_objective_it_cannot_build(cfg, bad, said):
    with pytest.raises(MXNetError, match=said):
        models.get_symbol("decoder_lm", cfg=_tiny(cfg, **bad))


def test_the_objective_needs_its_block_length_and_nothing_else(cfg):
    """The document's length is half of what ``data`` holds and the mask
    token is the feed's: the graph asks for neither. A block length that
    does not fit the length is refused where the shape is known."""
    tiny = _tiny(cfg)
    short = {k: v for k, v in tiny.items() if k != "block_length"}
    with pytest.raises(MXNetError, match="'block_length'"):
        models.get_symbol("decoder_lm", cfg=short)
    bare = {k: v for k, v in tiny.items()
            if k not in ("mask_token_id", "seq_len")}
    sym = models.get_symbol("decoder_lm", cfg=bare)
    for length in (16, 40):
        out = sym.infer_shape(data=(2, 2 * length),
                              softmax_label=(2, 2, length))[1]
        assert out == [(1,)]
    with pytest.raises(MXNetError, match="GroupedQueryAttention"):
        models.get_symbol("decoder_lm", cfg=dict(bare, block_length=5)) \
            .infer_shape(data=(2, 32), softmax_label=(2, 2, 16))


def test_the_model_against_the_benchmarks_plain_reference(cfg, model):
    """``decoder_lm`` with the objective against ``forward_loss`` of
    ``perfbench/models/sdar-30b-a3b.py`` on the same seeded weights and the
    same noisy batch: the loss and every gradient leaf."""
    tiny = _tiny(cfg)
    sym, params, aux = _graph(tiny, model, 16)
    data, label = map(jnp.asarray, _batch(model, tiny, 16))
    run = build_graph_eval(sym)

    def loss(p):
        return run(dict(p, data=data, softmax_label=label), aux, None,
                   True)[0][0][0]

    value, grads = jax.jit(jax.value_and_grad(loss))(params)
    with jax.default_matmul_precision("highest"):
        want, want_grads = jax.jit(jax.value_and_grad(
            lambda p: model.forward_loss(tiny, p, data, label,
                                         lambda x: x)))(params)
    _close(value, want, 1e-5)
    assert set(grads) == set(want_grads) == set(model.param_shapes(tiny))
    for name in grads:
        assert float(jnp.linalg.norm(want_grads[name])) > 0, name
        _close(grads[name], want_grads[name], 5e-5)
    # the device's counter: the masked positions of the batch
    _, ups = run(dict(params, data=data, softmax_label=label), aux, None,
                 True)
    assert float(ups["loss_stats"][0]) == float((label[:, 1] > 0).sum())


def test_what_a_block_depends_on(cfg, model):
    """The two properties that make it block diffusion, on the stream after
    the last layer: changing x_t inside block b moves the noisy rows of
    block b only; changing x_0 inside block b moves the noisy rows of the
    blocks after b and the clean rows of the blocks from b on."""
    tiny, length, block, b = _tiny(cfg), 16, 4, 1
    sym, params, aux = _graph(tiny, model, length)
    run = jax.jit(lambda data: build_graph_eval(_hidden(sym))(
        dict(params, data=data), aux, None, False)[0][0])
    data = jnp.asarray(_batch(model, tiny, length, rows=1)[0])
    base = run(data)
    assert base.shape == (1, 2 * length, tiny["hidden_size"])

    def moved(other):
        return np.asarray(jnp.max(jnp.abs(run(other) - base), axis=(0, 2))
                          > 1e-6)

    blk = np.arange(2 * length) % length // block
    noisy = np.arange(2 * length) < length
    at = b * block + 1
    new_id = (data[0, at] + 1) % 90
    assert (moved(data.at[0, at].set(new_id))
            == (noisy & (blk == b))).all()
    assert (moved(data.at[0, length + at].set(new_id))
            == ((noisy & (blk > b)) | (~noisy & (blk >= b)))).all()


def test_one_pass_of_two_halves_against_a_pass_a_block(cfg, model):
    """The 2 L pass against L / B separate passes of ``[clean blocks before
    b ; noisy block b]`` under a plain block-causal mask (the clean half of
    a pass of that length, whose noisy half no clean row sees): block b's
    logits are equal."""
    tiny, length, block = _tiny(cfg), 16, 4
    sym, params, aux = _graph(tiny, model, length)
    data = jnp.asarray(_batch(model, tiny, length, rows=1)[0])
    logits = build_graph_eval(sym.get_internals()["lm_head_output"])(
        dict(params, data=data), aux, None, False)[0][0]
    assert logits.shape == (1, length, tiny["vocab_size"])
    xt, x0 = data[:, :length], data[:, length:]
    for b in range(length // block):
        n = (b + 1) * block
        rows = jnp.concatenate([x0[:, :b * block], xt[:, b * block:n]], 1)
        part = models.get_symbol("decoder_lm", cfg=tiny)
        hidden = build_graph_eval(_hidden(part))(
            dict(params, data=jnp.concatenate([jnp.zeros_like(rows), rows],
                                              1)), aux, None, False)[0][0]
        own = hidden[:, n + b * block:]                  # the clean half's
        own = own * jax.lax.rsqrt(jnp.mean(jnp.square(own), -1,
                                           keepdims=True)
                                  + tiny["rms_norm_eps"]) \
            * params["final_norm_gamma"]
        _close(own @ params["lm_head_weight"].T,
               logits[:, b * block:n], 2e-5)


_GRAPHS_BEFORE = {
    # sha256 of (nodes with op and arity, arguments, auxiliary states,
    # inferred shapes) at the commit before the objective existed
    ("laguna-xs2", "cut"): (144, "34080bfe4f247280"),
    ("laguna-xs2", "rehearse"): (60, "34daaaea2cc7987f"),
    ("lfm2-8b-a1b", "cut"): (105, "53f710a2e5834b89"),
    ("lfm2-8b-a1b", "rehearse"): (54, "ef5b7e25c980766e"),
}


@pytest.mark.parametrize("name,size", list(_GRAPHS_BEFORE))
def test_a_configuration_without_the_key_builds_the_graph_it_built(name,
                                                                   size):
    with open(os.path.join(ROOT, "perfbench", "configs",
                           name + ".json")) as f:
        cfg = json.load(f)
    if size == "rehearse":
        cfg = dict(cfg, **cfg["rehearse"])
    with NameManager():      # the anonymous nodes numbered from 0, as in
        sym = models.get_symbol("decoder_lm", cfg=cfg)   # a fresh process
    nodes = [(n.name, "null" if n.is_variable else n.op.name, len(n.inputs))
             for n in sym._topo_nodes()]
    shapes = sym.infer_shape(data=(2, 64), softmax_label=(2, 64))
    text = json.dumps([nodes, sym.list_arguments(),
                       sym.list_auxiliary_states(), shapes], sort_keys=True)
    assert (len(nodes), hashlib.sha256(text.encode()).hexdigest()[:16]) \
        == _GRAPHS_BEFORE[(name, size)]
    for node in sym._topo_nodes():
        if node.is_variable:
            continue
        assert not node.attrs.get("block_length")
        assert node.attrs.get("copies", 1) == 1
        assert not node.attrs.get("weighted")
        assert node.attrs.get("score_func", "sigmoid") == "sigmoid"


def test_the_unweighted_loss_keeps_no_counter():
    """``SPMDTrainer.aux_counters`` names a node only for a counter it
    keeps: the older decoders' model files sum every node's routed
    counters."""
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh
    sym = mx.sym.TokenCrossEntropy(
        mx.sym.FullyConnected(mx.sym.var("data"), num_hidden=5, name="fc"),
        mx.sym.var("softmax_label"), name="loss")
    tr = SPMDTrainer(sym, optimizer=mx.optimizer.SGD(learning_rate=0.1),
                     mesh=make_mesh({"data": 1}, devices=jax.devices()[:1]))
    tr.bind(data_shapes={"data": (4, 3)},
            label_shapes={"softmax_label": (4,)})
    assert tr.aux_counters() == {}
