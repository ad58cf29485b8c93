"""What a hybrid convolution-attention sparse decoder (LFM2-8B-A1B) adds to
``models/decoder_lm.py`` and its ops, at a small size on the CPU: the gated
short convolution against an explicit tap loop; q/k norm before the rotary
embedding; grouped-query attention at head size 64 through the kernel's
interpreter; the routed layer's selection bias (in the choice, never in the
weights, no gradient, untouched by the optimizer) and its four shares against
the whole layer; the tied head; and the whole tiny model through
``SPMDTrainer.fit`` against the benchmark's plain reference."""
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
from mxnet_tpu.ops.registry import get_op

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import run as harness  # noqa: E402

CELL = "lfm2-8b-a1b.train-fed-2x8k"


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


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "lfm2-8b-a1b.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def model():
    return harness.load_module("models", "lfm2-8b-a1b")


def _tiny(cfg, **over):
    return dict(cfg, **dict(cfg["rehearse"], compute_dtype="float32",
                            **over))


# -- the gated short convolution ------------------------------------------------

def _short_conv_by_taps(x, w_in, w_conv, w_out):
    """Row by row, position by position, tap by tap."""
    rows, s, d = x.shape
    taps = w_conv.shape[1]
    out = []
    for r in range(rows):
        bch = x[r] @ w_in.T
        b, c, h = bch[:, :d], bch[:, d:2 * d], bch[:, 2 * d:]
        g = b * h
        y = [sum(w_conv[:, k] * g[t - (taps - 1 - k)] for k in range(taps)
                 if t - (taps - 1 - k) >= 0) for t in range(s)]
        out.append((c * jnp.stack(y)) @ w_out.T)
    return jnp.stack(out)


def _short_conv_inputs(rows=2, s=9, d=8, taps=3):
    return (_rand(rows, s, d), _rand(3 * d, d, seed=1, scale=0.4),
            _rand(d, taps, seed=2), _rand(d, d, seed=3, scale=0.4))


@pytest.mark.parametrize("taps", [3, 4])
def test_short_conv_against_an_explicit_tap_loop(taps):
    args = _short_conv_inputs(taps=taps)
    _same_with_gradients(
        lambda *a: get_op("ShortConv").fn(*a, kernel=taps),
        _short_conv_by_taps, *args)


def test_short_conv_starts_every_row_from_zeros_and_is_causal():
    x, w_in, w_conv, w_out = _short_conv_inputs()
    fn = lambda x: get_op("ShortConv").fn(x, w_in, w_conv, w_out,  # noqa: E731
                                          kernel=3)
    whole = fn(x)
    # row 1's first outputs are what it gives alone: nothing of row 0
    _close(whole[1], fn(x[1:])[0], 1e-6)
    other_row0 = fn(x.at[0].set(_rand(9, 8, seed=7)))
    _close(whole[1], other_row0[1], 1e-6)
    # an output at t does not move when inputs after t do
    later = fn(x.at[:, 5:].set(_rand(2, 4, 8, seed=8)))
    _close(whole[:, :5], later[:, :5], 1e-6)
    assert float(jnp.max(jnp.abs(whole[:, 5:] - later[:, 5:]))) > 1e-3
    # and through jax.grad: positions after t get no gradient from y_t
    grad = jax.grad(lambda x: jnp.sum(fn(x)[:, 4]))(x)
    assert float(jnp.max(jnp.abs(grad[:, 5:]))) == 0.0
    assert float(jnp.max(jnp.abs(grad[:, 2:5]))) > 0.0


def test_short_conv_is_reachable_from_sym_nd_and_gluon():
    x, w_in, w_conv, w_out = _short_conv_inputs()
    want = _short_conv_by_taps(x, w_in, w_conv, w_out)
    nd = [mx.nd.array(np.asarray(a)) for a in (x, w_in, w_conv, w_out)]
    _close(mx.nd.ShortConv(*nd, kernel=3).asnumpy(), want, 1e-5)
    sym = mx.sym.ShortConv(mx.sym.var("data"), kernel=3, name="mix")
    assert sym.list_arguments() == ["data", "mix_in_weight",
                                    "mix_conv_weight", "mix_out_weight"]
    assert sym.infer_shape(data=(2, 9, 8))[0] == [(2, 9, 8), (24, 8), (8, 3),
                                                  (8, 8)]

    class Mixer(mx.gluon.HybridBlock):
        def hybrid_forward(self, F, x, w_in, w_conv, w_out):
            return F.ShortConv(x, w_in, w_conv, w_out, kernel=3)

    _close(Mixer()(*nd).asnumpy(), want, 1e-5)
    with pytest.raises(MXNetError, match="ShortConv"):
        get_op("ShortConv").fn(x, w_in, w_conv[:, :2], w_out, kernel=3)


# -- attention: q/k norm, head size 64 ----------------------------------------

def test_qk_norm_comes_before_the_rotary_embedding(cfg, model):
    """The graph's rotated q and k against the reference's: RMSNorm over
    each head with one gain (head_dim,), then the rotation."""
    tiny = _tiny(cfg)
    d = tiny["head_dim"]
    inner = models.get_symbol("decoder_lm", cfg=tiny).get_internals()
    sym = mx.sym.Group([inner["layer1_q_rope_output"],
                        inner["layer1_k_rope_output"],
                        inner["layer1_q_output"], inner["layer1_k_output"]])
    from mxnet_tpu.executor import build_graph_eval
    shapes = dict(zip(sym.list_arguments(),
                      sym.infer_shape(data=(2, 12))[0]))
    assert shapes["layer1_q_norm_gamma"] == shapes["layer1_k_norm_gamma"] \
        == (d,)
    args = {n: _rand(*s, seed=k, scale=0.3) for k, (n, s) in
            enumerate(shapes.items())}
    for n in args:
        if n.endswith("gamma"):
            args[n] = 1.0 + args[n]
    args["data"] = jnp.asarray(
        np.random.default_rng(0).integers(0, 96, (2, 12)), jnp.float32)
    (q_rot, k_rot, q, k), _ = jax.jit(
        lambda a: build_graph_eval(sym)(a, {}, None, False))(args)
    for rot, raw, gain in ((q_rot, q, "layer1_q_norm_gamma"),
                           (k_rot, k, "layer1_k_norm_gamma")):
        for row in range(2):
            heads = raw[row].reshape(12, -1, d)
            want = model._rotate(model._rms(heads, args[gain],
                                            tiny["norm_eps"]),
                                 tiny["rope_theta"])
            _close(rot[row].reshape(12, -1, d), want, 1e-5)
    # norm after the rotation would not be this: the gain differs by dim
    assert float(jnp.max(jnp.abs(args["layer1_q_norm_gamma"] - 1))) > 0.1


@pytest.mark.parametrize("heads,kv", [(4, 1), (8, 2)])
def test_band_kernel_at_head_size_64_in_the_interpreter(heads, kv):
    """Half a lane tile a head, a group of 4: the kernel and the blockwise
    backward reading its logsumexp, against plain softmax differentiated by
    JAX."""
    from mxnet_tpu.ops.pallas.attention import (gqa_attention_reference,
                                                grouped_query_attention)
    q, k, v = (_rand(2, heads, 64, 64), _rand(2, kv, 64, 64, seed=1),
               _rand(2, kv, 64, 64, seed=2))
    with jax.enable_x64(False):
        _same_with_gradients(
            lambda *a: grouped_query_attention(*a, causal=True, block=32,
                                               force_pallas=True),
            lambda *a: gqa_attention_reference(*a, True, 0), q, k, v)


def test_the_cells_attention_layer_steps_over_its_live_tiles_alone():
    """Two documents of 8,192 tokens, 32 heads of 64 over 8: the kernel's
    grid is 64 heads x the 136 live tiles of a causal walk of 16 (256 steps
    a head before PR 34), 16 of them cut by the mask."""
    from mxnet_tpu.ops.pallas.attention import (_CUT, _gqa_pallas,
                                                _visit_table)
    q = jax.ShapeDtypeStruct((2, 32, 8192, 64), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((2, 8, 8192, 64), jnp.bfloat16)
    with jax.enable_x64(False):
        traced = jax.make_jaxpr(lambda *a: _gqa_pallas(
            *a, True, 0, 0.125, 512, 512, False))(q, k, k)
    call, = [e for e in traced.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert tuple(call.params["grid_mapping"].grid) == (64, 136)
    flags = _visit_table(16, 16, 512, 512, True, 0)[2]
    assert int((flags & _CUT != 0).sum()) == 16


# -- the routed layer: a bias in the selection ------------------------------------

def _moe_inputs(t=48, d=16, e=32, f=8, fs=8):
    return dict(
        x=_rand(t, d), router=_rand(e, d, seed=1),
        gate=_rand(e, d, f, seed=2, scale=0.3),
        up=_rand(e, d, f, seed=3, scale=0.3),
        down=_rand(e, f, d, seed=4, scale=0.3),
        bias=_rand(e, seed=8, scale=0.5),
        shared=(_rand(fs, d, seed=5, scale=0.3),
                _rand(fs, d, seed=6, scale=0.3),
                _rand(d, fs, seed=7, scale=0.3)))


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def _whole_layer(m, top_k, scale, bias, eps, shared):
    """The uncut layer: the ``top_k`` largest ``s + bias``, weighted by the
    unbiased ``s / (sum + eps)``, every token through every expert it
    chose."""
    s = jax.nn.sigmoid(m["x"] @ m["router"].T)
    _, idx = jax.lax.top_k(s + (m["bias"] if bias else 0.0), top_k)
    top = jnp.take_along_axis(s, idx, -1)
    w = scale * top / (top.sum(-1, keepdims=True) + eps)
    y = 0.0
    for e in range(m["router"].shape[0]):
        weight = jnp.sum(jnp.where(idx == e, w, 0.0), -1, keepdims=True)
        y = y + weight * _swiglu(m["x"], m["gate"][e], m["up"][e],
                                 m["down"][e])
    if shared:
        sg, su, sd = m["shared"]
        y = y + _swiglu(m["x"], sg.T, su.T, sd.T)
    return y


def _share(m, offset, held, top_k, scale, bias, eps, shared):
    extra = ((m["bias"],) if bias else ()) + (m["shared"] if shared else ())
    return get_op("MoEFFN").fn(
        m["x"], m["router"], m["gate"][offset:offset + held],
        m["up"][offset:offset + held], m["down"][offset:offset + held],
        jnp.zeros(3), *extra, num_experts=m["router"].shape[0],
        hidden_size=m["gate"].shape[-1], top_k=top_k, experts_held=held,
        expert_offset=offset, routed_scale=scale,
        shared_hidden_size=m["shared"][0].shape[0] if shared else 0,
        use_expert_bias=bias, renorm_eps=eps, _is_train=True)


@pytest.mark.parametrize("experts,held,bias,eps,shared", [
    (32, 8, True, 1e-6, False),     # LFM2-8B-A1B: four chips, no shared
    (32, 8, True, 0.0, True),       # the bias ahead of a shared expert
    (16, 4, False, 1e-6, False)])   # the family's denominator alone
def test_the_shares_of_all_chips_add_up_to_the_whole_layer(experts, held,
                                                           bias, eps, shared):
    """Every share's routed part, the shared expert counted once, is the
    uncut layer's output; every token-choice is counted by one share."""
    m = _moe_inputs(e=experts)
    total, choices = 0.0, 0.0
    for k, offset in enumerate(range(0, experts, held)):
        y, stats = _share(m, offset, held, 4, 1.0, bias, eps,
                          shared and k == 0)
        total = total + y
        choices += float(stats[0])
    _close(total, _whole_layer(m, 4, 1.0, bias, eps, shared), 1e-5)
    assert choices == 48 * 4


def test_the_bias_enters_the_choice_and_never_the_weights():
    from mxnet_tpu.parallel.moe import sigmoid_topk_router
    m = _moe_inputs(e=8)
    # a bias that dwarfs every score: experts 5 and 2 are always chosen
    bias = jnp.zeros(8).at[5].set(40.0).at[2].set(30.0)
    w, idx = sigmoid_topk_router(m["x"], m["router"], 2, 1.0, bias, 1e-6)
    assert set(np.asarray(idx).ravel()) == {2, 5}
    s = jax.nn.sigmoid(m["x"] @ m["router"].T)
    top = jnp.stack([s[:, 5], s[:, 2]], -1)
    order = jnp.argsort(idx, axis=-1)[:, ::-1]           # 5 first
    _close(jnp.take_along_axis(w, order, -1),
           top / (top.sum(-1, keepdims=True) + 1e-6), 1e-6)
    # had the bias reached the weights they would be 40 : 30, near 0.57
    assert float(jnp.max(w)) <= 1.0 and float(jnp.min(w)) < 0.45
    # without the bias the choice follows the scores
    _, plain = sigmoid_topk_router(m["x"], m["router"], 2)
    _close(plain, jax.lax.top_k(s, 2)[1])
    # no gradient reaches it, through the router or through the layer
    g = jax.grad(lambda b: jnp.sum(sigmoid_topk_router(
        m["x"], m["router"], 2, 1.0, b, 1e-6)[0] ** 2))(bias)
    assert float(jnp.max(jnp.abs(g))) == 0.0
    m["bias"] = bias
    g = jax.grad(lambda b: jnp.sum(_share(
        dict(m, bias=b), 0, 8, 2, 1.0, True, 1e-6, False)[0] ** 2))(bias)
    assert float(jnp.max(jnp.abs(g))) == 0.0


def test_moe_ffn_with_a_bias_gradients_against_the_masked_sum():
    m = _moe_inputs(e=8)

    def fn(x, router, gate, up, down):
        mm = dict(m, x=x, router=router, gate=gate, up=up, down=down)
        return _share(mm, 0, 8, 4, 1.0, True, 1e-6, False)[0]

    def ref(x, router, gate, up, down):
        return _whole_layer(dict(m, x=x, router=router, gate=gate, up=up,
                                 down=down), 4, 1.0, True, 1e-6, False)

    _same_with_gradients(fn, ref, m["x"], m["router"], m["gate"], m["up"],
                         m["down"], tol=5e-5)


def test_the_bias_is_an_auxiliary_state_after_stats():
    sym = mx.sym.MoEFFN(mx.sym.var("z"), num_experts=8, hidden_size=4,
                        top_k=2, use_expert_bias=True, shared_hidden_size=4,
                        name="moe")
    assert sym.list_auxiliary_states() == ["moe_stats", "moe_expert_bias"]
    assert sym.list_arguments()[-3:] == [
        "moe_shared_gate_weight", "moe_shared_up_weight",
        "moe_shared_down_weight"]
    args, _, aux = sym.infer_shape(z=(6, 16))
    assert aux == [(3,), (8,)] and args[-1] == (16, 4)
    plain = mx.sym.MoEFFN(mx.sym.var("z"), num_experts=8, hidden_size=4,
                          top_k=2, shared_hidden_size=4, name="moe")
    assert plain.list_auxiliary_states() == ["moe_stats"]
    assert plain.list_arguments() == [a for a in sym.list_arguments()]


# -- the model -------------------------------------------------------------------

def test_layer_plan_admits_conv_and_refuses_what_it_does_not_know(cfg):
    plan = models.decoder_lm.layer_plan(cfg)
    assert [p["attention"] for p in plan] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    assert [p["mlp"] for p in plan] == ["dense"] + ["sparse"] * 4
    assert plan[1]["rope"] == {"rope_theta": 1000000} and plan[1]["heads"] \
        == 32 and plan[1]["window"] == 0
    # the MLP kinds from num_dense_layers where the list is absent
    whole = {k: v for k, v in dict(cfg, **cfg["published"]).items()
             if k != "mlp_layer_types"}
    plan = models.decoder_lm.layer_plan(whole)
    assert [p["mlp"] for p in plan] == ["dense"] * 2 + ["sparse"] * 22
    assert [p["attention"] for p in plan].count("conv") == 18
    with pytest.raises(MXNetError, match="unknown layer type 'linear'"):
        models.decoder_lm.layer_plan(dict(cfg, layer_types=["linear"] * 5))
    with pytest.raises(MXNetError, match="norm_topk_prob"):
        models.get_symbol("decoder_lm", cfg=_tiny(cfg, norm_topk_prob=False))
    # norm_topk_prob says only that the weights are renormalised: a config
    # that carries it without norm_topk_eps (Qwen3-MoE's) gets no epsilon
    plain = {k: v for k, v in _tiny(cfg).items() if k != "norm_topk_eps"}
    moe = [n for n in models.get_symbol("decoder_lm", cfg=plain)._topo_nodes()
           if not n.is_variable and n.op.name == "MoEFFN"][0]
    assert plain["norm_topk_prob"] is True and moe.attrs["renorm_eps"] == 0.0


def test_symbol_of_the_cut_configuration(cfg, model):
    """From the file's lists: a conv layer has no attention node, the head
    has no weight of its own, the routed layers carry their bias, and the
    arguments are the benchmark's ``param_shapes``."""
    sym = models.get_symbol("decoder_lm", cfg=cfg)
    shapes = dict(zip(sym.list_arguments(), sym.infer_shape(
        data=(2, 64), softmax_label=(2, 64))[0]))
    del shapes["data"], shapes["softmax_label"]
    assert shapes == model.param_shapes(cfg)
    assert "lm_head_weight" not in shapes
    assert shapes["layer1_q_weight"] == (2048, 2048)
    assert shapes["layer1_k_weight"] == (512, 2048)
    assert shapes["layer0_conv_conv_weight"] == (2048, 3)
    assert sym.list_auxiliary_states() == [
        name for k in range(1, 5)
        for name in (f"layer{k}_moe_stats", f"layer{k}_moe_expert_bias")]
    ops = [(n.scope_attrs.get("__block__"), n.op.name)
           for n in sym._topo_nodes() if not n.is_variable]
    assert ("layer0", "ShortConv") in ops
    assert ("layer0", "GroupedQueryAttention") not in ops
    assert ("layer1", "GroupedQueryAttention") in ops
    assert ("layer1", "ShortConv") not in ops
    moe = [n for n in sym._topo_nodes()
           if not n.is_variable and n.op.name == "MoEFFN"][0]
    assert moe.attrs["use_expert_bias"] is True
    assert moe.attrs["renorm_eps"] == 1e-6 and moe.attrs["top_k"] == 4
    assert moe.attrs["experts_held"] == 8
    assert moe.attrs["shared_hidden_size"] == 0


def test_parameter_counts_published_and_cut(cfg, model):
    def count(shapes):
        return sum(int(np.prod(s)) for s in shapes.values())

    assert count(model.param_shapes(cfg)) == 507_820_160
    whole = model.uncut(cfg)
    assert (whole["num_hidden_layers"], whole["num_dense_layers"],
            whole["num_experts_held"], whole["vocab_size"]) \
        == (24, 2, 32, 65536)
    tied = count(model.param_shapes(whole))
    assert abs(tied - 8.34e9) / 8.34e9 < 0.002
    assert abs(tied - 8.3e9) / 8.3e9 < 0.01
    # an untied head would add the vocabulary again: 8.47 B
    assert abs(tied + 65536 * 2048 - 8.47e9) / 8.47e9 < 0.002
    # the published widths stand; the cut keys are the four
    assert cfg["reduced"] == ["num_hidden_layers", "num_dense_layers",
                              "num_experts_held", "vocab_size"]
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_experts"],
            cfg["num_experts_per_tok"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["conv_L_cache"]) \
        == (2048, 7168, 1792, 32, 4, 32, 8, 3)
    assert cfg["published"]["layer_types"][1:6] == cfg["layer_types"]


def test_flops_per_item_lands_on_the_hand_count(cfg, model):
    """ISSUE 31: 216,268,800 multiply-adds a token forward at 8,192
    positions without the taps (3 a channel in each of the four conv
    layers: 24,576 more), 1.298 GFLOP a token trained."""
    got = model.flops_per_item(cfg)
    assert got == 3 * 2 * (216_268_800 + 4 * 3 * 2048)
    assert abs(got - 1.298e9) / 1.298e9 < 0.001

    def layers(n):
        return model.flops_per_item(dict(cfg, num_hidden_layers=n)) / 6

    assert layers(0) == 2048 * 16384                          # the head
    conv = 4 * 2048 * 2048 + 3 * 2048
    routed = 2048 * 32 + 3 * 2048 * 1792                      # one expert
    assert layers(1) - layers(0) == conv + 3 * 2048 * 7168
    assert layers(2) - layers(1) == 2 * 2048 * 2048 + 2 * 2048 * 512 \
        + 2 * 2048 * 4096 + routed
    assert layers(3) - layers(2) == conv + routed
    assert model.items_per_batch(cfg, {"per_chip_batch": 2, "chips": 1,
                                       "seq_len": 8192}) == 16384


def _tiny_graph(tiny, model, rows=2, s=16):
    sym = models.get_symbol("decoder_lm", cfg=tiny)
    params = jax.device_get(model.init_params(tiny, 3))
    aux = {n: jnp.zeros(3) for n in sym.list_auxiliary_states()}
    aux.update(model.init_buffers(tiny, 3))
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 96, (rows, s)),
                      jnp.float32)
    return sym, params, aux, ids


def test_the_tied_heads_gradient_is_the_sum_of_both_uses(cfg, model):
    """``embed_weight`` feeds the gather and the head: its gradient is the
    gradient an untied model gives its embedding plus the one it gives its
    head, at equal matrices."""
    from mxnet_tpu.executor import build_graph_eval
    tiny = _tiny(cfg)
    sym, params, aux, ids = _tiny_graph(tiny, model)
    untied = models.get_symbol("decoder_lm",
                               cfg=dict(tiny, tie_word_embeddings=False))
    assert "lm_head_weight" in untied.list_arguments()
    assert "lm_head_weight" not in sym.list_arguments()

    def loss(graph, p):
        return build_graph_eval(graph)(
            dict(p, data=ids, softmax_label=ids), aux, None, True)[0][0][0]

    value, tied_grad = jax.jit(jax.value_and_grad(
        lambda p: loss(sym, p)))(params)
    both = dict(params, lm_head_weight=params["embed_weight"])
    same_value, split = jax.jit(jax.value_and_grad(
        lambda p: loss(untied, p)))(both)
    assert float(jnp.linalg.norm(split["lm_head_weight"])) > 0
    assert float(jnp.linalg.norm(split["embed_weight"])) > 0
    _close(tied_grad["embed_weight"],
           split["embed_weight"] + split["lm_head_weight"], 1e-5)
    _close(value, same_value, 1e-6)


def test_tiny_model_trains_through_fit_like_the_reference(model):
    """One conv and one attention layer, 8 experts of which 4 held, through
    ``SPMDTrainer.fit`` fed by ``PrefetchingIter(NDArrayIter)``: loss of
    each step, the first gradient and three Adam steps against the
    benchmark's plain reference; the selection bias comes out of the steps
    bit-equal, the counters count the routed layer."""
    from perfbench import compare, feed
    cell = harness.load_cell(CELL, rehearse=True)
    tiny, traffic = dict(cell["cfg"], compute_dtype="float32"), \
        cell["traffic_params"]
    driver = harness.load_module("drivers", "train_fit")
    program = model.Program(tiny, traffic, 7, jax.devices())
    bias_before = np.asarray(program.trainer.aux["layer1_moe_expert_bias"])
    assert np.abs(bias_before).max() > 0
    batches = model.make_batches(tiny, traffic, 7)
    window = feed.Window(feed.inner_iterator(
        traffic, batches, program.input_shardings(), program.input_names))
    record = driver.checked_steps(program, window, batches, 3)
    assert "layer1_moe_expert_bias" not in program.trainer.params
    assert "layer1_moe_expert_bias" not in program.trainer.states
    np.testing.assert_array_equal(
        np.asarray(program.trainer.aux["layer1_moe_expert_bias"]),
        bias_before)
    np.testing.assert_array_equal(
        bias_before,
        np.asarray(model.init_buffers(tiny, 7)["layer1_moe_expert_bias"]))
    by_node = program.trainer.aux_counters()
    assert sorted(by_node) == ["layer1_moe"]
    counters = program.routed_counters()
    tokens = 4 * model.items_per_batch(tiny, traffic)
    assert 0 < counters["moe.assignments_held"] <= 4 * tokens
    assert counters["moe.overflow"] == 0
    scopes = set(mx.profiler.op_scopes("spmd-step").values())
    for part in ("in_proj", "conv", "out_proj"):
        assert any(f"layer0/ShortConv/layer0_conv/{part}/" in s
                   for s in scopes), part
    program.close()
    ref = model.reference(tiny, traffic, 7, devices=jax.devices())
    numbers, _ = compare.gaps(record, ref)
    assert numbers["loss_gap"] < 1e-5
    assert numbers["grad_gap_worst"] < 2e-3
    assert numbers["delta_gap_worst"] < 2e-3
    # the bias left out of the selection is another model
    bad = model.reference(tiny, traffic, 7, fault="bias_out",
                          devices=jax.devices())
    assert compare.gaps(bad, ref)[0]["grad_gap_worst"] > 0.02
