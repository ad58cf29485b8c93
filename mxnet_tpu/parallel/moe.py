"""Expert parallelism: Switch-style top-k MoE with all_to_all dispatch.

Absent from the reference entirely (SURVEY.md §2.5: expert parallelism ❌);
built TPU-first: experts are sharded over a named ``expert`` mesh axis,
token->expert routing builds dispatch/combine one-hots, and two
``jax.lax.all_to_all`` hops move token blocks to their experts' devices and
back over ICI. Dense einsum dispatch keeps everything static-shaped for XLA
(no data-dependent gather shapes), with a capacity_factor bound exactly like
the public Switch/GShard recipe.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, PartitionSpec as P

from .. import profiler
from ..base import MXNetError
from ..ops.pallas import moe_rows

__all__ = ["moe_apply", "moe_dense_apply", "top1_router", "topk_router",
           "load_balance_loss", "scored_topk_router", "sigmoid_topk_router",
           "held_experts_apply"]


def top1_router(x, router_w):
    """Softmax router; returns (gate, expert_index) per token."""
    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    idx = jnp.argmax(probs, axis=-1)
    gate = jnp.max(probs, axis=-1)
    return gate, idx


def topk_router(x, router_w, k: int):
    """Softmax router, top-k choices per token.

    Returns (probs (T,E), gates (T,k) renormalized over the chosen k,
    indices (T,k)) — the GShard/Switch recipe (top-1 degenerates to the
    Switch router)."""
    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    if k > probs.shape[-1]:
        raise MXNetError(
            f"top_k={k} exceeds the number of experts "
            f"{probs.shape[-1]}")
    gates, idxs = jax.lax.top_k(probs, k)
    if k > 1:
        # GShard renormalizes over the chosen k; Switch top-1 keeps the
        # raw probability so the router gets its gradient signal
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return probs, gates, idxs


def load_balance_loss(probs, first_choice, n_experts: int):
    """Switch load-balancing auxiliary loss: ``E * sum_e f_e * P_e``.

    ``f_e`` = fraction of tokens whose FIRST routing choice is expert e,
    ``P_e`` = mean router probability of e. Minimized (= 1.0) at uniform
    utilization; without it real MoE training collapses experts (the
    Switch Transformer recipe this module cites)."""
    onehot = jax.nn.one_hot(first_choice, n_experts, dtype=jnp.float32)
    f = onehot.mean(axis=0)
    p = probs.mean(axis=0)
    return n_experts * jnp.sum(f * p)


def _dispatch_topk(gates, idxs, n_experts: int, capacity: int):
    """Dispatch one-hot (T,E,C) and combine weights (T,E,C) for top-k
    routing with one shared per-expert capacity budget: choice 0 slots
    fill first (a token's primary expert beats another's secondary)."""
    T, k = gates.shape
    dispatch = jnp.zeros((T, n_experts, capacity), jnp.float32)
    combine = jnp.zeros((T, n_experts, capacity), jnp.float32)
    used = jnp.zeros((n_experts,), jnp.float32)
    for j in range(k):  # k is a small static constant
        onehot = jax.nn.one_hot(idxs[:, j], n_experts, dtype=jnp.float32)
        pos = (jnp.cumsum(onehot, axis=0) + used[None, :]) * onehot
        keep = (pos > 0) & (pos <= capacity)
        slot = jax.nn.one_hot((pos - 1).astype(jnp.int32), capacity,
                              dtype=jnp.float32)
        dj = slot * keep[..., None]
        dispatch = dispatch + dj
        combine = combine + dj * gates[:, j][:, None, None]
        used = used + onehot.sum(axis=0)
    return dispatch, combine


def _moe_local(x, router_w, expert_params, expert_fn, axis_name,
               capacity_factor, top_k):
    """Per-device body: route local tokens, a2a to experts, a2a back.

    x: (T_loc, D) local tokens; expert_params: pytree with leading dim
    E_loc (this device's experts). Returns (out, aux_loss) where the aux
    loss is the GLOBAL Switch load-balance term (psum over the axis).
    """
    n = axis_size(axis_name)
    t_loc, d = x.shape
    e_loc = jax.tree.leaves(expert_params)[0].shape[0]
    n_experts = e_loc * n
    capacity = max(1, int(capacity_factor * top_k * t_loc / n_experts))

    probs, gates, idxs = topk_router(x, router_w, top_k)
    # global balance statistics: local sums psum'd over the mesh axis
    onehot1 = jax.nn.one_hot(idxs[:, 0], n_experts, dtype=jnp.float32)
    f = jax.lax.psum(onehot1.sum(0), axis_name)
    p = jax.lax.psum(probs.sum(0), axis_name)
    total = jnp.float32(t_loc * n)
    aux = n_experts * jnp.sum((f / total) * (p / total))
    dispatch, combine = _dispatch_topk(gates, idxs, n_experts, capacity)
    # (T,E,C),(T,D) -> (E,C,D): per-expert token buffers, expert index
    # e = owner_device * e_loc + local_expert
    xin = jnp.einsum("tec,td->ecd", dispatch, x.astype(jnp.float32))
    # split by owner device and trade blocks; split==concat axis keeps the
    # shape and just transposes blocks across devices: dim 0 becomes the
    # *source* device after the a2a
    xin = xin.reshape(n, e_loc, capacity, d)
    xin = jax.lax.all_to_all(xin, axis_name, split_axis=0, concat_axis=0,
                             tiled=True)
    # per local expert, one token stream holding every source's block
    xin = xin.transpose(1, 0, 2, 3).reshape(e_loc, n * capacity, d)
    yout = jax.vmap(expert_fn)(expert_params, xin)  # (e_loc, n*C, d)
    # return trip: regroup by source device and a2a home
    yout = yout.reshape(e_loc, n, capacity, d).transpose(1, 0, 2, 3)
    yout = jax.lax.all_to_all(yout, axis_name, split_axis=0, concat_axis=0,
                              tiled=True)  # dim 0: expert-owner device
    yout = yout.reshape(n_experts, capacity, d)
    out = jnp.einsum("tec,ecd->td", combine, yout)
    return out.astype(x.dtype), aux


def moe_dense_apply(x, router_w, expert_params, expert_fn: Callable,
                    capacity_factor: float = 2.0, top_k: int = 1):
    """Single-device MoE — the no-mesh fallback for SwitchFFN, like
    attention's full-softmax fallback. Same router/combine math as the
    expert-parallel path; outputs are identical whenever no expert
    overflows its capacity (the sharded path bounds capacity per source
    shard, this one globally). Returns (out, aux_loss)."""
    t, d = x.shape
    n_experts = jax.tree.leaves(expert_params)[0].shape[0]
    capacity = max(1, int(capacity_factor * top_k * t / n_experts))
    probs, gates, idxs = topk_router(x, router_w, top_k)
    aux = load_balance_loss(probs, idxs[:, 0], n_experts)
    dispatch, combine = _dispatch_topk(gates, idxs, n_experts, capacity)
    xin = jnp.einsum("tec,td->ecd", dispatch, x.astype(jnp.float32))
    yout = jax.vmap(expert_fn)(expert_params, xin)
    out = jnp.einsum("tec,ecd->td", combine, yout)
    return out.astype(x.dtype), aux


def moe_apply(x, router_w, expert_params, expert_fn: Callable, mesh: Mesh,
              axis_name: str = "expert", capacity_factor: float = 2.0,
              top_k: int = 1, return_aux: bool = False):
    """Apply an expert-parallel MoE layer to tokens ``x``.

    x: (tokens, d_model), sharded over ``axis_name`` (tokens and experts
    share the axis, EP=DP style). expert_params: pytree with leading dim
    n_experts (divisible by the axis size); ``expert_fn(params_e, (t, d))``
    -> (t, d) is vmapped over local experts. Top-k routing with a static
    per-expert ``capacity`` bound keeps shapes XLA-friendly; overflow
    tokens pass through with weight 0 (standard Switch behavior).

    With ``return_aux`` also returns the Switch load-balancing loss —
    add it (scaled) to the training objective or experts collapse.
    """
    if axis_name not in mesh.axis_names:
        raise MXNetError(f"mesh has no axis {axis_name!r}")
    n = mesh.shape[axis_name]
    n_experts = jax.tree.leaves(expert_params)[0].shape[0]
    if n_experts % n:
        raise MXNetError(f"n_experts {n_experts} not divisible by mesh axis "
                         f"{axis_name!r} size {n}")
    if x.shape[0] % n:
        raise MXNetError(f"tokens {x.shape[0]} not divisible by mesh axis "
                         f"size {n}")
    if router_w.shape[-1] != n_experts:
        raise MXNetError(
            f"router_w routes to {router_w.shape[-1]} experts but "
            f"expert_params holds {n_experts}")
    e_spec = jax.tree.map(lambda _: P(axis_name), expert_params)
    fn = shard_map(
        functools.partial(_moe_local, expert_fn=expert_fn,
                          axis_name=axis_name,
                          capacity_factor=capacity_factor, top_k=top_k),
        mesh=mesh, in_specs=(P(axis_name), P(), e_spec),
        out_specs=(P(axis_name), P()), check_vma=False)
    out, aux = fn(x, router_w, expert_params)
    return (out, aux) if return_aux else out


# -- the share of a routed layer that one chip holds, with no token dropped ---

def scored_topk_router(x, router_w, k: int, scale: float = 1.0,
                       select_bias=None, renorm_eps: float = 0.0,
                       score_func: str = "sigmoid"):
    """Scores over all experts in float32, the ``k`` largest, and their
    weights ``scale * s_e / (sum of the chosen s + renorm_eps)``. The
    scores are sigmoids of the logits, or with ``score_func="softmax"``
    their softmax over ALL the experts (the weights are then a softmax over
    the chosen logits). With ``select_bias`` (E,) the ``k`` chosen are
    those with the largest ``s + select_bias``; the weights are still made
    of the unbiased ``s``, and no gradient reaches the bias. ``router_w`` is (E, d) as
    ``FullyConnected`` keeps it. Returns (weights (T, k) float32, indices
    (T, k) int32)."""
    logits = jax.lax.dot_general(
        x, router_w.astype(x.dtype), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    if score_func not in ("sigmoid", "softmax"):
        raise MXNetError(f"unknown score_func {score_func!r}: sigmoid or "
                         f"softmax")
    scores = jax.nn.sigmoid(logits) if score_func == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    if k > scores.shape[-1]:
        raise MXNetError(f"top_k={k} exceeds the number of experts "
                         f"{scores.shape[-1]}")
    if select_bias is None:
        top, idx = jax.lax.top_k(scores, k)
    else:
        _, idx = jax.lax.top_k(scores + jax.lax.stop_gradient(
            select_bias.astype(scores.dtype)), k)
        top = jnp.take_along_axis(scores, idx, axis=-1)
    weights = scale * top
    total = jnp.sum(top, -1, keepdims=True)
    if renorm_eps:
        total = total + renorm_eps
    return weights / total, idx


# the name it had while the sigmoid was its only scoring
sigmoid_topk_router = scored_topk_router


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _rows_by_slot(x, take, put, k, plan=None):
    """``x[take % T]``: row r of the result is the token of slot ``take[r]``
    (slot ``j * T + t`` is token t's choice j, k choices a token). ``put``
    is the inverse permutation, so the cotangent is a gather too: slot by
    slot, then the sum of a token's k slots, which are k whole (T, d)
    slabs; a scatter-add over k T rows is what this spares. With a ``plan``
    (:func:`~mxnet_tpu.ops.pallas.moe_rows.row_plan`) both ways run the
    row kernels: the gather, and the k rows summed in float32 and rounded
    once."""
    return _rows_by_slot_fwd(x, take, put, k, plan)[0]


def _rows_by_slot_fwd(x, take, put, k, plan):
    t = x.shape[0]
    if plan is None:
        return x[take % t], put
    return moe_rows.rows_to_slots(x, take % t, plan), take


def _rows_by_slot_bwd(k, plan, res, g):
    if plan is None:
        return g[res].reshape(k, -1, g.shape[-1]).sum(0), None, None
    return moe_rows.slots_to_rows(g, res, k, plan), None, None


_rows_by_slot.defvjp(_rows_by_slot_fwd, _rows_by_slot_bwd)


@jax.custom_vjp
def _permute_rows(x, take, put):
    """``x[take]`` for a permutation ``take`` whose inverse is ``put``: the
    cotangent is ``g[put]``, a gather again."""
    return x[take]


_permute_rows.defvjp(lambda x, take, put: (x[take], put),
                     lambda put, g: (g[put], None, None))


@jax.custom_vjp
def _weighted_return(per_slot, weights, held):
    """The sum over a token's k slots of ``weights * per_slot`` where
    ``held``, and exactly nothing where not: ``per_slot`` (k, T, d) the rows
    back in slot order, ``weights`` (k, T) float32, ``held`` (k, T) bool.
    Widened, weighted and summed in float32, rounded once to the rows'
    dtype. One pass over the rows each way: the residuals are the three
    arguments as they came (no float32 copy of the rows), the rows'
    cotangent is written once in their dtype, selected and not multiplied,
    so a row that is not held may hold anything finite or not."""
    return _weighted_return_fwd(per_slot, weights, held)[0]


def _held_rows(per_slot, held):
    """The rows in float32, an absent slot's selected to zero."""
    return jnp.where(held[..., None], per_slot, 0).astype(jnp.float32)


def _weighted_return_fwd(per_slot, weights, held):
    y = jnp.sum(_held_rows(per_slot, held) * weights[..., None], axis=0)
    return y.astype(per_slot.dtype), (per_slot, weights, held)


def _weighted_return_bwd(res, g):
    per_slot, weights, held = res
    profiler.count("moe.fused_return_layers")
    g = g.astype(jnp.float32)
    d_rows = jnp.where(held[..., None], g[None] * weights[..., None], 0)
    # the caller flattens the slots to rows: hoisted above this product, the
    # flattening leaves g's broadcast over k a float32 (k, T, d) in memory
    d_rows = jax.lax.optimization_barrier(d_rows.astype(per_slot.dtype))
    d_weights = jnp.sum(_held_rows(per_slot, held) * g[None], axis=-1)
    return d_rows, d_weights, None


_weighted_return.defvjp(_weighted_return_fwd, _weighted_return_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _return_by_kernel(out, weights, held, take, put, plan):
    """:func:`_weighted_return` of ``out`` (k T, d), the rows in sorted order,
    put back in slot order, by the row kernels: token t's float32 sum over
    its k slots of ``select(held, row, 0) * weights``, rounded once, with no
    (k, T, d) tensor in slot order among the residuals (they are ``out`` as it
    came, ``weights`` and ``held`` (k, T), ``take`` and ``put``). The
    cotangent of ``out`` is the tokens' cotangent gathered to sorted order,
    ``select(held, g x w, 0)`` rounded once, and the weights' is the dot of
    each slot's row with its token's cotangent, both from one pass."""
    return _return_by_kernel_fwd(out, weights, held, take, put, plan)[0]


def _return_by_kernel_fwd(out, weights, held, take, put, plan):
    y = moe_rows.slots_to_rows(out, take, weights.shape[0], plan, weights,
                               held)
    return y, (out, weights, held, take, put)


def _return_by_kernel_bwd(plan, res, g):
    out, weights, held, take, put = res
    profiler.count("moe.fused_return_layers")
    k, t = weights.shape
    d_out, d_weights = moe_rows.rows_to_slots(
        g, take % t, plan, weights.reshape(-1)[take], held.reshape(-1)[take],
        out)
    return (d_out, d_weights[put].reshape(k, t), None, None, None)


_return_by_kernel.defvjp(_return_by_kernel_fwd, _return_by_kernel_bwd)


def held_experts_apply(x, router_w, w_gate, w_up, w_down, *, num_experts,
                       top_k, expert_offset=0, routed_scale=1.0,
                       select_bias=None, renorm_eps=0.0,
                       score_func="sigmoid"):
    """What the experts held here add to a routed layer's output.

    ``x`` (T, d) tokens; ``router_w`` (E, d) scores ALL ``num_experts``
    experts and every token keeps its ``top_k`` (chosen under
    ``select_bias``, weighted without it, ``renorm_eps`` in the weights'
    denominator, scored by ``score_func``: :func:`scored_topk_router`);
    the stacked weights
    ``w_gate``/``w_up`` (Eh, d, f) and ``w_down`` (Eh, f, d) are those of
    experts ``expert_offset .. expert_offset + Eh - 1``, each a SwiGLU.
    The token-choices (slots, numbered choice-major: slot ``j * T + t`` is
    token t's choice j) are sorted by expert, the absent experts' last, and
    ALL of them run through a grouped matmul (``jax.lax.ragged_dot``): the
    absent experts' choices ride at the end of the last held expert's
    group and their results are selected away on the way back, so a step
    costs the same wherever the routing goes: the grouped matmul's time
    goes with the rows in its groups, and with the held rows alone in them
    the step's time moves by 4% between a routing that passes this chip by
    and one that lands on it (it is a seed's coin which; PERF.md, PR 27).
    The price is the matmul of k T rows a layer, always. The results return
    to their tokens weighted (:func:`_weighted_return`): no (T, E, C)
    tensor, no capacity, nothing dropped, and no tensor with a token's k
    slots as a second-minor axis, which at k = 4 is half a sublane tile
    and made every meeting of the sorted rows with their tokens a copy
    (PERF.md, PR 32). What the absent experts would add is left out.
    Returns (y (T, d) in x's dtype, counts (Eh,) int32: the choices that
    fell on each held expert)."""
    t, d = x.shape
    held = w_gate.shape[0]
    with jax.named_scope("route"):
        weights, chosen = scored_topk_router(
            x, router_w, top_k, routed_scale, select_bias, renorm_eps,
            score_func)
    with jax.named_scope("dispatch"):
        # slot j * T + t is token t's choice j: a token's k slots are k
        # whole (T, d) slabs, never the second-minor axis of a tile
        local = chosen.T.reshape(-1) - expert_offset        # slot -> expert
        local = jnp.where((local >= 0) & (local < held), local, held)
        # slots in the order of their expert, the absent ones last
        take = jnp.argsort(local, stable=True).astype(jnp.int32)
        put = jnp.argsort(take).astype(jnp.int32)
        counts = jnp.sum(
            local[:, None] == jnp.arange(held, dtype=local.dtype)[None, :],
            axis=0, dtype=jnp.int32)
        # every row lies in a group (the grouped matmul writes no other)
        groups = counts.at[-1].add(
            (t * top_k - jnp.sum(counts)).astype(counts.dtype))
        plan = moe_rows.row_plan(t, d, x.dtype)
        rows = _rows_by_slot(x, take, put, top_k, plan)
    with jax.named_scope("experts"):
        dt = x.dtype
        gate = jax.lax.ragged_dot(rows, w_gate.astype(dt), groups)
        up = jax.lax.ragged_dot(rows, w_up.astype(dt), groups)
        out = jax.lax.ragged_dot(jax.nn.silu(gate) * up, w_down.astype(dt),
                                 groups)
    with jax.named_scope("combine"):
        # an absent slot's row is selected away inside the return, not
        # multiplied: its cotangent is zero too, so it reaches neither the
        # tokens nor the last expert
        held_slots = (local < held).reshape(top_k, t)
        if plan is None:
            per_slot = _permute_rows(out, put, take).reshape(top_k, t, d)
            y = _weighted_return(per_slot, weights.T, held_slots)
        else:
            y = _return_by_kernel(out, weights.T, held_slots, take, put,
                                  plan)
    return y, counts
