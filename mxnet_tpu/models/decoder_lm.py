"""A decoder-only language model in the Symbol language, built from a
configuration's per-layer lists.

One definition for the family of pre-norm decoders that published
``config.json`` files describe: every layer is RMSNorm -> token mixer ->
residual -> RMSNorm -> MLP -> residual, and what differs by layer comes from
lists in the configuration: the mixer's kind (``layer_types``: full or
sliding-window attention, or ``conv``, a gated short convolution of
``conv_L_cache`` taps), the number of query heads
(``num_attention_heads_per_layer``) over ``num_key_value_heads`` key/value
heads, the rotary embedding of each kind (``rope_parameters``: partial,
YaRN; or a top-level ``rope_theta``), an RMSNorm over each head of q and k
before it (``qk_norm``), a per-head output gate (``gating``), and the MLP
kind (``mlp_layer_types``, or ``num_dense_layers`` leading dense ones: a
dense SwiGLU or routed SwiGLU experts, with a shared one and a bias in the
selection, ``use_expert_bias``, where the configuration has them). With
``tie_word_embeddings`` the head reads the embedding's matrix. A new
architecture of the family is a configuration, not another model file.

The graph is made of registered ops only (``RMSNorm``, ``FullyConnected``,
``RotaryEmbedding``, ``GroupedQueryAttention``, ``ShortConv``, ``GatedFFN``,
``MoEFFN``, ``TokenCrossEntropy``), so ``SPMDTrainer`` / ``Module`` train it
like any other symbol. Each layer is made under
``mx.AttrScope(__block__="layer<k>")``: its instructions read ``layer<k>/<Op>/<node>/...`` in a device trace, and with
``recompute="layer"`` in the configuration each layer (and the loss head) is a
``jax.checkpoint`` boundary in a training step.

A routed layer is built as ONE CHIP's share of an expert-parallel deployment:
``num_experts_held`` of the ``num_experts`` experts, numbered from
``expert_offset`` (``MoEFFN`` routes over all of them and computes its own
experts' part); all of them when the key is absent.
"""
from __future__ import annotations

from .. import symbol as sym
from ..base import MXNetError
from ..symbol.symbol import AttrScope

__all__ = ["get_symbol", "layer_plan"]


def layer_plan(cfg):
    """Per layer, what the configuration's lists say: ``[{"attention":
    "full_attention" | "sliding_attention" | "conv", "heads": H, "window":
    W or 0, "rope": {...}, "mlp": "dense" | "sparse"}, ...]`` for the first
    ``num_hidden_layers`` layers. Without ``mlp_layer_types`` the first
    ``num_dense_layers`` are dense and the rest sparse (all dense when that
    key is absent too); without ``rope_parameters`` every attention layer
    rotates whole heads at the top-level ``rope_theta``."""
    n = int(cfg["num_hidden_layers"])
    kinds = cfg.get("layer_types") or ["full_attention"] * n
    heads = cfg.get("num_attention_heads_per_layer") \
        or [cfg["num_attention_heads"]] * n
    dense = int(cfg.get("num_dense_layers", n))
    mlps = cfg.get("mlp_layer_types") \
        or ["dense"] * min(dense, n) + ["sparse"] * max(n - dense, 0)
    if min(len(kinds), len(heads), len(mlps)) < n:
        raise MXNetError(
            f"decoder_lm: {n} layers asked for, but the per-layer lists "
            f"hold {len(kinds)}, {len(heads)} and {len(mlps)} entries")
    ropes = cfg.get("rope_parameters") or {}
    whole_head = {"rope_theta": cfg["rope_theta"]} \
        if "rope_theta" in cfg else {}
    plan = []
    for k in range(n):
        sliding = kinds[k] == "sliding_attention"
        if kinds[k] not in ("full_attention", "sliding_attention", "conv"):
            raise MXNetError(f"decoder_lm: unknown layer type {kinds[k]!r}")
        if mlps[k] not in ("dense", "sparse"):
            raise MXNetError(f"decoder_lm: unknown MLP type {mlps[k]!r}")
        plan.append({
            "attention": kinds[k], "heads": int(heads[k]),
            "window": int(cfg["sliding_window"]) if sliding else 0,
            "rope": dict(ropes.get(kinds[k]) or whole_head),
            "mlp": mlps[k]})
    return plan


def _rotary(x, name, head_dim, rope):
    rotary_dim = int(round(head_dim * rope.get("partial_rotary_factor", 1)))
    attrs = dict(head_dim=head_dim, rotary_dim=rotary_dim,
                 theta=float(rope.get("rope_theta", 10000.0)),
                 rope_type=rope.get("rope_type", "default"))
    if attrs["rope_type"] == "yarn":
        attrs.update(
            factor=float(rope["factor"]),
            original_max_position=int(
                rope["original_max_position_embeddings"]),
            beta_fast=float(rope.get("beta_fast", 32)),
            beta_slow=float(rope.get("beta_slow", 1)),
            attention_factor=float(rope.get("attention_factor", 1.0)))
    return sym.RotaryEmbedding(x, name=name, **attrs)


def _linear(x, width, name, **weight):
    return sym.FullyConnected(x, num_hidden=width, no_bias=True,
                              flatten=False, name=name, **weight)


def _head_norm(x, name, head_dim, eps):
    """RMSNorm over each head of (B, S, heads * head_dim) with one learned
    gain (head_dim,) for all heads: the q/k norm of the families that have
    one, applied before the rotary embedding."""
    heads = sym.reshape(x, shape=(0, 0, -1, head_dim))
    return sym.reshape(sym.RMSNorm(heads, eps=eps, name=name),
                       shape=(0, 0, -1))


def _attention(u, layer, p, cfg, hd, kv, eps):
    """The attention mixer of one layer over the normed stream ``u``: q, k
    (each under ``qk_norm`` where asked, then rotated), v, the per-head gate
    where asked, grouped-query attention and the output projection."""
    heads, d = layer["heads"], int(cfg["hidden_size"])
    gated = bool(cfg.get("gating", False))
    q, key = _linear(u, heads * hd, p + "q"), _linear(u, kv * hd, p + "k")
    if cfg.get("qk_norm"):
        q = _head_norm(q, p + "q_norm", hd, eps)
        key = _head_norm(key, p + "k_norm", hd, eps)
    ins = [_rotary(q, p + "q_rope", hd, layer["rope"]),
           _rotary(key, p + "k_rope", hd, layer["rope"]),
           _linear(u, kv * hd, p + "v")]
    if gated:
        ins.append(_linear(u, heads, p + "gate"))
    attn = sym.GroupedQueryAttention(
        *ins, num_heads=heads, num_kv_heads=kv, window=layer["window"],
        causal=True, gated=gated, name=p + "attn")
    return _linear(attn, d, p + "o")


def _routed(z, p, cfg):
    experts = int(cfg["num_experts"])
    if cfg.get("norm_topk_prob") is False:
        raise MXNetError("decoder_lm: norm_topk_prob false (weights not "
                         "renormalised over the chosen experts) is not built")
    return sym.MoEFFN(
        z, num_experts=experts,
        hidden_size=int(cfg["moe_intermediate_size"]),
        top_k=int(cfg["num_experts_per_tok"]),
        experts_held=int(cfg.get("num_experts_held", experts)),
        expert_offset=int(cfg.get("expert_offset", 0)),
        routed_scale=float(cfg.get(
            "moe_routed_scaling_factor",
            cfg.get("routed_scaling_factor", 1.0))),
        shared_hidden_size=int(
            cfg.get("shared_expert_intermediate_size", 0)),
        use_expert_bias=bool(cfg.get("use_expert_bias", False)),
        renorm_eps=float(cfg.get("norm_topk_eps", 0.0)),
        name=p + "moe")


def get_symbol(cfg=None, **kwargs):
    """The training symbol of the configuration ``cfg`` (a dict with the
    keys of a published ``config.json``; ``kwargs`` override). Inputs:
    ``data`` (rows, seq_len) token ids and ``softmax_label`` (rows, seq_len)
    next-token ids. Output: the mean next-token cross-entropy, shape (1,)."""
    cfg = dict(cfg or {}, **kwargs)
    d = int(cfg["hidden_size"])
    hd = int(cfg.get("head_dim") or d // int(cfg["num_attention_heads"]))
    kv = int(cfg["num_key_value_heads"])
    eps = float(cfg.get("rms_norm_eps", cfg.get("norm_eps", 1e-6)))
    vocab = int(cfg["vocab_size"])
    remat = {"__remat__": "block"} if cfg.get("recompute") == "layer" else {}

    tied = {}
    if cfg.get("tie_word_embeddings"):
        # one variable for the embedding and the head: its gradient is the
        # sum of both uses
        tied["weight"] = sym.var("embed_weight")
    x = sym.Embedding(sym.var("data"), input_dim=vocab, output_dim=d,
                      name="embed", **tied)
    for k, layer in enumerate(layer_plan(cfg)):
        p = f"layer{k}_"
        with AttrScope(__block__=f"layer{k}", **remat):
            if layer["attention"] == "conv":
                u = sym.RMSNorm(x, eps=eps, name=p + "conv_norm")
                x = x + sym.ShortConv(u, kernel=int(cfg["conv_L_cache"]),
                                      name=p + "conv")
            else:
                u = sym.RMSNorm(x, eps=eps, name=p + "attn_norm")
                x = x + _attention(u, layer, p, cfg, hd, kv, eps)
            z = sym.RMSNorm(x, eps=eps, name=p + "mlp_norm")
            if layer["mlp"] == "dense":
                m = sym.GatedFFN(z, num_hidden=int(cfg["intermediate_size"]),
                                 name=p + "mlp")
            else:
                m = _routed(z, p, cfg)
            x = x + m
    with AttrScope(__block__="loss_head", **remat):
        x = sym.RMSNorm(x, eps=eps, name="final_norm")
        logits = _linear(x, vocab, "lm_head", **tied)
        return sym.TokenCrossEntropy(logits, sym.var("softmax_label"),
                                     name="loss")
