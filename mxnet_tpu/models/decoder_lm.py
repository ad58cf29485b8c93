"""A decoder-only language model in the Symbol language, built from a
configuration's per-layer lists.

One definition for the family of pre-norm decoders that published
``config.json`` files describe: every layer is RMSNorm -> attention ->
residual -> RMSNorm -> MLP -> residual, and what differs by layer comes from
lists in the configuration: the attention kind (``layer_types``: full or
sliding-window), the number of query heads (``num_attention_heads_per_layer``)
over ``num_key_value_heads`` key/value heads, the rotary embedding of each
kind (``rope_parameters``: partial, YaRN), a per-head output gate
(``gating``), and the MLP kind (``mlp_layer_types``: a dense SwiGLU or routed
SwiGLU experts with a shared one). A new architecture of the family is a
configuration, not another model file.

The graph is made of registered ops only (``RMSNorm``, ``FullyConnected``,
``RotaryEmbedding``, ``GroupedQueryAttention``, ``GatedFFN``, ``MoEFFN``,
``TokenCrossEntropy``), so ``SPMDTrainer`` / ``Module`` train it like any
other symbol. Each layer is made under ``mx.AttrScope(__block__="layer<k>")``:
its instructions read ``layer<k>/<Op>/<node>/...`` in a device trace, and with
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
    "full_attention" | "sliding_attention", "heads": H, "window": W or 0,
    "rope": {...}, "mlp": "dense" | "sparse"}, ...]`` for the first
    ``num_hidden_layers`` layers."""
    n = int(cfg["num_hidden_layers"])
    kinds = cfg.get("layer_types") or ["full_attention"] * n
    heads = cfg.get("num_attention_heads_per_layer") \
        or [cfg["num_attention_heads"]] * n
    mlps = cfg.get("mlp_layer_types") or ["dense"] * n
    if min(len(kinds), len(heads), len(mlps)) < n:
        raise MXNetError(
            f"decoder_lm: {n} layers asked for, but the per-layer lists "
            f"hold {len(kinds)}, {len(heads)} and {len(mlps)} entries")
    ropes = cfg.get("rope_parameters") or {}
    plan = []
    for k in range(n):
        sliding = kinds[k] == "sliding_attention"
        if not sliding and kinds[k] != "full_attention":
            raise MXNetError(f"decoder_lm: unknown layer type {kinds[k]!r}")
        if mlps[k] not in ("dense", "sparse"):
            raise MXNetError(f"decoder_lm: unknown MLP type {mlps[k]!r}")
        plan.append({
            "attention": kinds[k], "heads": int(heads[k]),
            "window": int(cfg["sliding_window"]) if sliding else 0,
            "rope": dict(ropes.get(kinds[k]) or {}), "mlp": mlps[k]})
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


def _linear(x, width, name):
    return sym.FullyConnected(x, num_hidden=width, no_bias=True,
                              flatten=False, name=name)


def get_symbol(cfg=None, **kwargs):
    """The training symbol of the configuration ``cfg`` (a dict with the
    keys of a published ``config.json``; ``kwargs`` override). Inputs:
    ``data`` (rows, seq_len) token ids and ``softmax_label`` (rows, seq_len)
    next-token ids. Output: the mean next-token cross-entropy, shape (1,)."""
    cfg = dict(cfg or {}, **kwargs)
    d, hd = int(cfg["hidden_size"]), int(cfg["head_dim"])
    kv = int(cfg["num_key_value_heads"])
    eps = float(cfg.get("rms_norm_eps", 1e-6))
    vocab = int(cfg["vocab_size"])
    gated = bool(cfg.get("gating", False))
    remat = {"__remat__": "block"} if cfg.get("recompute") == "layer" else {}

    x = sym.Embedding(sym.var("data"), input_dim=vocab, output_dim=d,
                      name="embed")
    for k, layer in enumerate(layer_plan(cfg)):
        p = f"layer{k}_"
        heads = layer["heads"]
        with AttrScope(__block__=f"layer{k}", **remat):
            u = sym.RMSNorm(x, eps=eps, name=p + "attn_norm")
            q = _rotary(_linear(u, heads * hd, p + "q"), p + "q_rope", hd,
                        layer["rope"])
            key = _rotary(_linear(u, kv * hd, p + "k"), p + "k_rope", hd,
                          layer["rope"])
            ins = [q, key, _linear(u, kv * hd, p + "v")]
            if gated:
                ins.append(_linear(u, heads, p + "gate"))
            attn = sym.GroupedQueryAttention(
                *ins, num_heads=heads, num_kv_heads=kv,
                window=layer["window"], causal=True, gated=gated,
                name=p + "attn")
            x = x + _linear(attn, d, p + "o")
            z = sym.RMSNorm(x, eps=eps, name=p + "mlp_norm")
            if layer["mlp"] == "dense":
                m = sym.GatedFFN(z, num_hidden=int(cfg["intermediate_size"]),
                                 name=p + "mlp")
            else:
                experts = int(cfg["num_experts"])
                m = sym.MoEFFN(
                    z, num_experts=experts,
                    hidden_size=int(cfg["moe_intermediate_size"]),
                    top_k=int(cfg["num_experts_per_tok"]),
                    experts_held=int(cfg.get("num_experts_held", experts)),
                    expert_offset=int(cfg.get("expert_offset", 0)),
                    routed_scale=float(
                        cfg.get("moe_routed_scaling_factor", 1.0)),
                    shared_hidden_size=int(
                        cfg.get("shared_expert_intermediate_size", 0)),
                    name=p + "moe")
            x = x + m
    with AttrScope(__block__="loss_head", **remat):
        x = sym.RMSNorm(x, eps=eps, name="final_norm")
        logits = _linear(x, vocab, "lm_head")
        return sym.TokenCrossEntropy(logits, sym.var("softmax_label"),
                                     name="loss")
