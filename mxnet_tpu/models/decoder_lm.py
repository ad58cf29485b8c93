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

The training objective is a key too. Absent, it is next-token prediction
under a causal mask. ``objective: "block_diffusion"`` (BD3-LM,
arXiv:2503.09573; with ``block_length``) trains a block-diffusion model:
every document runs twice in one sequence, a noisy copy (tokens replaced by
the mask token, the feed's work) and then the clean one, each half of the
positions ``data`` has and both at positions 0 .. L - 1, under the mask of
``GroupedQueryAttention(block_length=...)``; the head and a weighted loss
read the noisy half only (:func:`get_symbol`).

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


def _rotary(x, name, head_dim, rope, copies=1):
    rotary_dim = int(round(head_dim * rope.get("partial_rotary_factor", 1)))
    attrs = dict(head_dim=head_dim, rotary_dim=rotary_dim,
                 theta=float(rope.get("rope_theta", 10000.0)),
                 rope_type=rope.get("rope_type", "default"))
    if copies > 1:
        attrs["copies"] = copies
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


def _attention(u, layer, p, cfg, hd, kv, eps, block_length=0):
    """The attention mixer of one layer over the normed stream ``u``: q, k
    (each under ``qk_norm`` where asked, then rotated), v, the per-head gate
    where asked, grouped-query attention and the output projection. With
    ``block_length`` (the block-diffusion objective's) the sequence is two
    copies of a document, each at positions 0 .. S / 2 - 1, under the
    block-diffusion mask."""
    heads, d = layer["heads"], int(cfg["hidden_size"])
    gated = bool(cfg.get("gating", False))
    q, key = _linear(u, heads * hd, p + "q"), _linear(u, kv * hd, p + "k")
    if cfg.get("qk_norm"):
        q = _head_norm(q, p + "q_norm", hd, eps)
        key = _head_norm(key, p + "k_norm", hd, eps)
    copies, mask = (2, {"block_length": block_length}) if block_length \
        else (1, {})
    ins = [_rotary(q, p + "q_rope", hd, layer["rope"], copies),
           _rotary(key, p + "k_rope", hd, layer["rope"], copies),
           _linear(u, kv * hd, p + "v")]
    if gated:
        ins.append(_linear(u, heads, p + "gate"))
    attn = sym.GroupedQueryAttention(
        *ins, num_heads=heads, num_kv_heads=kv, window=layer["window"],
        causal=True, gated=gated, name=p + "attn", **mask)
    return _linear(attn, d, p + "o")


def _routed(z, p, cfg):
    experts = int(cfg["num_experts"])
    if cfg.get("norm_topk_prob") is False:
        raise MXNetError("decoder_lm: norm_topk_prob false (weights not "
                         "renormalised over the chosen experts) is not built")
    # absent, the attribute's default: the sigmoid router's graph as it was
    scoring = {"score_func": cfg["score_func"]} if "score_func" in cfg else {}
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
        name=p + "moe", **scoring)


def _block_length(cfg, plan):
    """The block-diffusion objective's block length, or 0 for next-token
    training. The document's length is half of what ``data`` holds: the
    ops read it from the shape."""
    objective = cfg.get("objective", "next_token")
    if objective == "next_token":
        return 0
    if objective != "block_diffusion":
        raise MXNetError(f"decoder_lm: unknown objective {objective!r}")
    if "block_length" not in cfg:
        raise MXNetError("decoder_lm: objective 'block_diffusion' needs "
                         "'block_length'")
    block = int(cfg["block_length"])
    if block < 1:
        raise MXNetError(f"decoder_lm: block_length {block} is no length of "
                         f"a block")
    other = sorted({layer["attention"] for layer in plan} - {"full_attention"})
    if other:
        raise MXNetError(f"decoder_lm: the block-diffusion mask is built "
                         f"for full attention layers, not {other}")
    return block


def get_symbol(cfg=None, **kwargs):
    """The training symbol of the configuration ``cfg`` (a dict with the
    keys of a published ``config.json``; ``kwargs`` override). Inputs:
    ``data`` (rows, seq_len) token ids and ``softmax_label`` (rows, seq_len)
    next-token ids. Output: the mean next-token cross-entropy, shape (1,).

    Under ``objective: "block_diffusion"`` (documents of L tokens in blocks
    of ``block_length``): ``data`` (rows, 2 L) holds ``[x_t ; x_0]``, the
    noisy copy and then the clean one; ``softmax_label`` (rows, 2, L)
    float32 holds the targets ``x_0`` in plane 0 and a weight a position in
    plane 1 (``1 / t`` where the feed put the mask token into ``x_t``, 0
    elsewhere). Every layer runs over the 2 L rows; the final norm, the head
    and the loss over the first L: ``sum(w * CE(logits_i, x0_i)) / (rows
    L)``, position i predicting token i."""
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
    plan = layer_plan(cfg)
    block_length = _block_length(cfg, plan)
    for k, layer in enumerate(plan):
        p = f"layer{k}_"
        with AttrScope(__block__=f"layer{k}", **remat):
            if layer["attention"] == "conv":
                u = sym.RMSNorm(x, eps=eps, name=p + "conv_norm")
                x = x + sym.ShortConv(u, kernel=int(cfg["conv_L_cache"]),
                                      name=p + "conv")
            else:
                u = sym.RMSNorm(x, eps=eps, name=p + "attn_norm")
                x = x + _attention(u, layer, p, cfg, hd, kv, eps,
                                   block_length)
            z = sym.RMSNorm(x, eps=eps, name=p + "mlp_norm")
            if layer["mlp"] == "dense":
                m = sym.GatedFFN(z, num_hidden=int(cfg["intermediate_size"]),
                                 name=p + "mlp")
            else:
                m = _routed(z, p, cfg)
            x = x + m
    with AttrScope(__block__="loss_head", **remat):
        if block_length:
            # the noisy half alone is scored; the last layer's clean half
            # feeds nothing and is computed as every other layer's is
            x = sym.split(x, num_outputs=2, axis=1, name="noisy_half")[0]
        x = sym.RMSNorm(x, eps=eps, name="final_norm")
        logits = _linear(x, vocab, "lm_head", **tied)
        label = sym.var("softmax_label")
        if not block_length:
            return sym.TokenCrossEntropy(logits, label, name="loss")
        targets, weights = sym.split(label, num_outputs=2, axis=1,
                                     squeeze_axis=True, name="label_planes")
        return sym.TokenCrossEntropy(logits, targets, weights, weighted=True,
                                     name="loss")
