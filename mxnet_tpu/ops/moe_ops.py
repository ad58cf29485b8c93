"""Mixture-of-experts operator: SwitchFFN for sym/nd/gluon.

Beyond-reference (the 2017 reference has no MoE; SURVEY.md §2.5 expert
parallelism ❌). Same productization pattern as ``MultiHeadAttention``
(attention_ops.py): a registered graph op whose ``expert_axis`` attr
names a mesh axis — under an ambient ``parallel.mesh_scope`` carrying
that axis the experts run expert-parallel with all_to_all dispatch
(parallel/moe.py); otherwise a dense single-device fallback with the
same router/capacity math, so one graph runs anywhere.

Two outputs: the mixed tokens AND the Switch load-balancing auxiliary
loss — feed the loss through ``MakeLoss`` (models/transformer_sym.py
does) or experts collapse during training.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..base import AttrSpec
from .registry import OP_TABLE, register


def _switch_param_shapes(attrs, shapes):
    d_model = shapes[0][-1]
    e = int(attrs["num_experts"])
    f = int(attrs["hidden_size"])
    return [shapes[0], (d_model, e), (e, d_model, f), (e, f),
            (e, f, d_model), (e, d_model)]


@register("SwitchFFN",
          attrs=AttrSpec(num_experts=("int",), hidden_size=("int",),
                         top_k=("int", 1), capacity_factor=("float", 2.0),
                         expert_axis=("str", "")),
          num_inputs=6,
          input_names=["data", "gate_weight", "expert_w1", "expert_b1",
                       "expert_w2", "expert_b2"],
          num_outputs=2, output_names=["output", "aux_loss"],
          param_shapes=_switch_param_shapes)
def _switch_ffn(data, gate_weight, expert_w1, expert_b1, expert_w2,
                expert_b2, num_experts, hidden_size, top_k=1,
                capacity_factor=2.0, expert_axis=""):
    """Switch/GShard FFN over (..., d_model) inputs.

    Routes each token to its top-k experts (relu FFN each), bounded by a
    static capacity. ``expert_axis`` names the mesh axis to shard
    experts (and the token stream) over; absent mesh/axis falls back to
    the dense path. Output 0: mixed tokens, same shape as ``data``;
    output 1: scalar load-balance loss (Switch aux; minimum 1.0 at
    uniform utilization).
    """
    from ..parallel.mesh import current_mesh
    from ..parallel.moe import moe_apply, moe_dense_apply

    shape = data.shape
    toks = data.reshape(-1, shape[-1])
    params = (expert_w1, expert_b1, expert_w2, expert_b2)

    def expert_fn(p, t):
        w1, b1, w2, b2 = p
        return jnp.maximum(t @ w1 + b1, 0.0) @ w2 + b2

    mesh = None
    if expert_axis:
        m = current_mesh()
        if (m is not None and expert_axis in m.axis_names
                and m.shape[expert_axis] > 1
                and toks.shape[0] % m.shape[expert_axis] == 0
                and num_experts % m.shape[expert_axis] == 0):
            mesh = m
    if mesh is not None:
        out, aux = moe_apply(toks, gate_weight, params, expert_fn, mesh,
                             axis_name=expert_axis,
                             capacity_factor=capacity_factor,
                             top_k=top_k, return_aux=True)
    else:
        out, aux = moe_dense_apply(toks, gate_weight, params, expert_fn,
                                   capacity_factor=capacity_factor,
                                   top_k=top_k)
    return out.reshape(shape).astype(data.dtype), aux


def _moe_ffn_input_names(attrs):
    """The inputs of this instantiation, in order: the bias of the
    selection and the shared expert's three weights are there where the
    attrs ask for them."""
    names = ["data", "router_weight", "expert_gate_weight",
             "expert_up_weight", "expert_down_weight", "stats"]
    if attrs.get("use_expert_bias"):
        names.append("expert_bias")
    if attrs.get("shared_hidden_size"):
        names += ["shared_gate_weight", "shared_up_weight",
                  "shared_down_weight"]
    return names


def _moe_ffn_param_shapes(attrs, shapes):
    d = shapes[0][-1]
    e, f = int(attrs["num_experts"]), int(attrs["hidden_size"])
    held = int(attrs["experts_held"]) or e
    fs = int(attrs["shared_hidden_size"])
    out = [shapes[0], (e, d), (held, d, f), (held, d, f), (held, f, d), (3,)]
    if attrs.get("use_expert_bias"):
        out.append((e,))
    return out + ([(fs, d), (fs, d), (d, fs)] if fs else [])


@register("MoEFFN",
          attrs=AttrSpec(num_experts=("int",), hidden_size=("int",),
                         top_k=("int", 1), experts_held=("int", 0),
                         expert_offset=("int", 0),
                         routed_scale=("float", 1.0),
                         shared_hidden_size=("int", 0),
                         use_expert_bias=("bool", False),
                         renorm_eps=("float", 0.0),
                         score_func=("str", "sigmoid")),
          num_inputs=None,
          input_names=_moe_ffn_input_names(
              {"use_expert_bias": True, "shared_hidden_size": 1}),
          param_shapes=_moe_ffn_param_shapes, needs_is_train=True,
          aux_inputs=lambda attrs: (5, 6) if attrs.get("use_expert_bias")
          else (5,),
          aux_update={1: 5},
          aux_counters={5: ("moe.assignments_held", "moe.load_max",
                            "moe.overflow")})
def _moe_ffn(data, router_weight, expert_gate_weight, expert_up_weight,
             expert_down_weight, stats, *rest, num_experts, hidden_size,
             top_k=1, experts_held=0, expert_offset=0, routed_scale=1.0,
             shared_hidden_size=0, use_expert_bias=False, renorm_eps=0.0,
             score_func="sigmoid", _is_train=False):
    """A routed feed-forward layer as one chip of an expert-parallel
    deployment holds it, over (..., d) inputs.

    The router scores all ``num_experts`` experts in float32 (``score_func``:
    a ``sigmoid`` an expert, or the ``softmax`` over all of them), every
    token keeps its ``top_k`` with weights ``routed_scale * s_e / (sum of
    the chosen s + renorm_eps)``, and the layer computes the part of the
    result that its own experts give: those numbered ``expert_offset ..
    expert_offset + experts_held - 1`` (all of them when ``experts_held``
    is 0), each a SwiGLU of width ``hidden_size``, by sort, grouped matmul
    and weighted return (``parallel.moe.held_experts_apply``): no token is
    dropped, and the grouped matmul runs every choice's row, held or not,
    so that a step's time does not hang on the routing.
    With ``use_expert_bias`` a second auxiliary input ``expert_bias`` (E,)
    float32 follows ``stats``: the ``top_k`` are those with the largest
    ``s + expert_bias``, their weights still come from ``s`` alone. It is a
    buffer: no gradient reaches it, no optimizer touches it, and the op
    does not update it (a rule that balances the load with it is the
    caller's). With ``shared_hidden_size`` a shared SwiGLU expert of that
    width (three more inputs, last) is added for every token. The shares of
    all chips, the shared expert counted once, add up to the whole layer.

    The auxiliary state ``stats`` (3,) accumulates per training step, on
    the device: the token-choices that fell on held experts, the fullest
    held expert's tokens, and the choices left out for want of room
    (always 0: the buffer holds the worst case): the op's ``aux_counters``
    name them. Read them at a boundary (``SPMDTrainer.aux_counters``),
    never every step."""
    from ..parallel.moe import held_experts_apply
    from .nn_ops import gated_ffn

    shape = data.shape
    toks = data.reshape(-1, shape[-1])
    bias, shared = (rest[0], rest[1:]) if use_expert_bias else (None, rest)
    y, counts = held_experts_apply(
        toks, router_weight, expert_gate_weight, expert_up_weight,
        expert_down_weight, num_experts=num_experts, top_k=top_k,
        expert_offset=expert_offset, routed_scale=routed_scale,
        select_bias=bias, renorm_eps=renorm_eps, score_func=score_func)
    if shared_hidden_size:
        with jax.named_scope("shared"):
            y = y + gated_ffn(toks, *shared)
    if _is_train:
        step = jnp.stack([jnp.sum(counts), jnp.max(counts),
                          jnp.zeros((), counts.dtype)])
        stats = stats + step.astype(stats.dtype)
    return (y.reshape(shape).astype(data.dtype),
            jax.lax.stop_gradient(stats))


OP_TABLE["MoEFFN"].dynamic_input_names = _moe_ffn_input_names
