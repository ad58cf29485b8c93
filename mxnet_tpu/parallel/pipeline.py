"""Pipeline parallelism: GPipe-style microbatch schedule over a mesh axis.

The reference's only pipeline-ish facility is manual ctx_group layer
placement (`mx.AttrScope(ctx_group=...)` + `group2ctx`, SURVEY.md §2.5) with
whatever overlap the dependency engine finds — no microbatch schedule. This
is the TPU-native upgrade: stages are sharded over a named ``pipe`` mesh
axis, activations hop stage-to-stage with ``jax.lax.ppermute`` (ICI
neighbor traffic), and a GPipe fill/drain loop keeps all stages busy on
different microbatches.

Design (SPMD, homogeneous stages): a stack of per-stage parameter pytrees
with a leading ``n_stages`` dim is sharded over the pipe axis so each device
holds exactly its stage's weights; inside ``jax.shard_map`` a fori_loop of
``n_micro + n_stages - 1`` ticks runs stage_fn on every device each tick.
This is the standard XLA pipeline pattern — compare the scaling-book
recipe — not a port of any reference scheduler.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, PartitionSpec as P

from ..base import MXNetError

__all__ = ["pipeline_apply", "pipeline_value_and_grad",
           "stack_stage_params", "pipeline_from_symbol",
           "psum_in_backward", "psum_in_forward"]


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def psum_in_backward(x, axis_name):
    """Identity forward, all-reduce backward (Megatron's *g* operator).

    Inside a manual ``shard_map`` body, an activation that is logically
    replicated across ``axis_name`` but consumed by ``axis_name``-sharded
    weights (tensor-parallel column split) receives only the LOCAL shard's
    cotangent from ordinary AD; the true cotangent is the sum over
    shards. Wrap the activation with this before the sharded branch."""
    return x


def _psum_in_backward_fwd(x, axis_name):
    return x, None


def _psum_in_backward_bwd(axis_name, _, ct):
    return (jax.lax.psum(ct, axis_name),)


psum_in_backward.defvjp(_psum_in_backward_fwd, _psum_in_backward_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def psum_in_forward(x, axis_name):
    """All-reduce forward, identity backward (Megatron's *f* operator —
    the pair of :func:`psum_in_backward`, used after a row-sharded
    matmul). A raw ``lax.psum`` must not be used there: under
    ``check_vma=False`` its transpose is another psum, which multiplies
    the cotangent by the axis size."""
    return jax.lax.psum(x, axis_name)


def _psum_in_forward_fwd(x, axis_name):
    return jax.lax.psum(x, axis_name), None


def _psum_in_forward_bwd(axis_name, _, ct):
    return (ct,)


psum_in_forward.defvjp(_psum_in_forward_fwd, _psum_in_forward_bwd)


def stack_stage_params(param_list):
    """Stack per-stage parameter pytrees along a new leading stage dim."""
    return jax.tree.map(lambda *xs: jnp.stack(xs, 0), *param_list)


def _pipe_local(params, x, fn: Callable, axis_name: str, n_micro: int):
    """Per-device body. params: this stage's pytree (leading dim squeezed);
    x: (n_micro, mb, ...) replicated microbatch inputs."""
    n = axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    params = jax.tree.map(lambda p: jnp.squeeze(p, 0), params)
    perm = [(i, (i + 1) % n) for i in range(n)]
    mb_shape = x.shape[1:]

    def tick(t, carry):
        state, outputs = carry
        # stage 0 ingests microbatch t (clipped; stale ingests are ignored
        # because their results drain past the output window)
        inp = jax.lax.dynamic_index_in_dim(
            x, jnp.clip(t, 0, n_micro - 1), 0, keepdims=False)
        state = jnp.where(idx == 0, inp, state)
        out = fn(params, state)
        # the last stage finishes microbatch (t - n + 1) at tick t
        m = t - (n - 1)
        updated = jax.lax.dynamic_update_index_in_dim(
            outputs, out, jnp.clip(m, 0, n_micro - 1), 0)
        outputs = jnp.where((m >= 0) & (idx == n - 1), updated, outputs)
        state = jax.lax.ppermute(out, axis_name, perm)
        return state, outputs

    init = (jnp.zeros(mb_shape, x.dtype),
            jnp.zeros((n_micro,) + mb_shape, x.dtype))
    _, outputs = jax.lax.fori_loop(0, n_micro + n - 1, tick, init)
    # out_specs stacks per-device buffers along a leading pipe dim; only
    # the last stage's buffer holds the real outputs (the others stay
    # zero) — caller contracts the stage dim away
    return outputs[None]


def pipeline_apply(fn: Callable, stacked_params, x, mesh: Mesh,
                   axis_name: str = "pipe", n_microbatches: int = None):
    """Run ``x`` through ``n_stages`` copies of ``fn`` pipelined over the mesh.

    fn(stage_params, h) -> h with h.shape preserved; ``stacked_params`` has a
    leading n_stages dim (see ``stack_stage_params``) which must equal the
    pipe-axis size. ``x`` is (batch, ...); it is split into
    ``n_microbatches`` equal microbatches along axis 0.
    """
    if axis_name not in mesh.axis_names:
        raise MXNetError(f"mesh has no axis {axis_name!r}")
    n = mesh.shape[axis_name]
    leaves = jax.tree.leaves(stacked_params)
    if leaves and leaves[0].shape[0] != n:
        raise MXNetError(
            f"stacked_params leading dim {leaves[0].shape[0]} != pipe axis "
            f"size {n}")
    n_micro = n_microbatches or n
    batch = x.shape[0]
    if batch % n_micro:
        raise MXNetError(f"batch {batch} not divisible by "
                         f"n_microbatches {n_micro}")
    xm = x.reshape((n_micro, batch // n_micro) + x.shape[1:])

    p_spec = jax.tree.map(lambda _: P(axis_name), stacked_params)
    out = shard_map(
        functools.partial(_pipe_local, fn=fn, axis_name=axis_name,
                          n_micro=n_micro),
        mesh=mesh, in_specs=(p_spec, P()), out_specs=P(axis_name),
        check_vma=False)(stacked_params, xm)
    # exact out[-1], written as a one-hot contraction over the sharded
    # stage dim: slicing it would transpose to a cross-partition
    # dynamic_update_slice, which old jaxlib's SPMD partitioner
    # miscompiles (s64/s32 index compare); multiply+reduce transposes to
    # broadcast+mask, safe on every build. Non-last buffers are exactly
    # zero, so the sum is bitwise the last stage's buffer.
    mask = (jnp.arange(n) == n - 1).astype(out.dtype)
    last = jnp.tensordot(mask, out, axes=1)
    return last.reshape((batch,) + x.shape[1:])


def _1f1b_local(params, tail_params, x, y, fn: Callable, loss_fn: Callable,
                axis_name: str, n_micro: int, reduce_axes=()):
    """Per-device 1F1B body: each tick runs one backward microbatch-step
    then one forward microbatch-step, so at most ``2n`` stage inputs are
    ever live per device (a ring buffer) — versus GPipe's ``n_micro``.

    Schedule (device s, tick t): forward of microbatch ``t - s``;
    backward of microbatch ``t - 2n + 1 + s``. Activations flow s -> s+1
    by ppermute, cotangents s -> s-1 by the reverse ppermute; the loss
    (and its cotangent) is produced on the LAST stage the tick after its
    forward. Each backward step re-linearizes the stage function at the
    saved stage input (jax.vjp = per-stage rematerialization).
    """
    n = axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    params = jax.tree.map(lambda p: jnp.squeeze(p, 0), params)
    fwd_perm = [(i, (i + 1) % n) for i in range(n)]
    bwd_perm = [(i, (i - 1) % n) for i in range(n)]
    mb_shape = x.shape[1:]
    ring_sz = 2 * n
    is_first = idx == 0
    is_last = idx == n - 1

    def masked_add(acc, upd, active):
        return jax.tree.map(
            lambda a, u: a + jnp.where(active, u, jnp.zeros_like(u)),
            acc, upd)

    def tick(t, carry):
        (state_f, state_b, pending_ct, ring, grads, tail_g, loss_sum,
         xgrads) = carry

        # ---- backward half (first: it reads pending_ct from the
        # previous tick's forward on the last stage)
        m_b = t - 2 * n + 1 + idx
        active_b = (m_b >= 0) & (m_b < n_micro)
        ct_in = jnp.where(is_last, pending_ct, state_b)
        h_saved = jax.lax.dynamic_index_in_dim(
            ring, jnp.clip(m_b, 0, n_micro - 1) % ring_sz, 0,
            keepdims=False)
        _, stage_vjp = jax.vjp(fn, params, h_saved)
        dparams, dh_in = stage_vjp(ct_in)
        grads = masked_add(grads, dparams, active_b)
        xg_upd = jax.lax.dynamic_update_index_in_dim(
            xgrads, dh_in, jnp.clip(m_b, 0, n_micro - 1), 0)
        xgrads = jnp.where(active_b & is_first, xg_upd, xgrads)

        # ---- forward half
        m_f = t - idx
        active_f = (m_f >= 0) & (m_f < n_micro)
        mth = jnp.clip(m_f, 0, n_micro - 1)
        inp = jax.lax.dynamic_index_in_dim(x, mth, 0, keepdims=False)
        h_in = jnp.where(is_first, inp, state_f)
        ring_upd = jax.lax.dynamic_update_index_in_dim(
            ring, h_in, mth % ring_sz, 0)
        ring = jnp.where(active_f, ring_upd, ring)
        h_out = fn(params, h_in)
        y_mb = jax.lax.dynamic_index_in_dim(y, mth, 0, keepdims=False)
        l, (d_tail, dh) = jax.value_and_grad(loss_fn, argnums=(0, 1))(
            tail_params, h_out, y_mb)
        produce = active_f & is_last
        loss_sum = loss_sum + jnp.where(produce, l, 0.0)
        tail_g = masked_add(tail_g, d_tail, produce)
        pending_ct = jnp.where(produce, dh, pending_ct)

        # ---- neighbor exchange
        state_f = jax.lax.ppermute(h_out, axis_name, fwd_perm)
        state_b = jax.lax.ppermute(dh_in, axis_name, bwd_perm)
        return (state_f, state_b, pending_ct, ring, grads, tail_g,
                loss_sum, xgrads)

    zeros_h = jnp.zeros(mb_shape, x.dtype)
    init = (zeros_h, zeros_h, zeros_h,
            jnp.zeros((ring_sz,) + mb_shape, x.dtype),
            jax.tree.map(jnp.zeros_like, params),
            jax.tree.map(jnp.zeros_like, tail_params),
            jnp.zeros((), jnp.float32),
            jnp.zeros((n_micro,) + mb_shape, x.dtype))
    carry = jax.lax.fori_loop(0, n_micro + 2 * n - 1, tick, init)
    _, _, _, _, grads, tail_g, loss_sum, xgrads = carry
    # only one stage holds each of these; psum replicates them
    loss = jax.lax.psum(loss_sum, axis_name) / n_micro
    tail_g = jax.tree.map(lambda g: jax.lax.psum(g, axis_name) / n_micro,
                          tail_g)
    xgrads = jax.lax.psum(xgrads, axis_name) / n_micro
    grads = jax.tree.map(lambda g: g[None] / n_micro, grads)
    # composition with data/sequence sharding of the microbatches: each
    # shard computed the mean loss of ITS slice, so the global mean (and
    # its gradients) is the psum over those axes divided by their size
    for ax in reduce_axes:
        size = axis_size(ax)
        loss = jax.lax.psum(loss, ax) / size
        grads = jax.tree.map(lambda g: jax.lax.psum(g, ax) / size, grads)
        tail_g = jax.tree.map(lambda g: jax.lax.psum(g, ax) / size, tail_g)
        xgrads = xgrads / size  # stays sharded like x
    return loss, grads, tail_g, xgrads


def pipeline_value_and_grad(fn: Callable, loss_fn: Callable, stacked_params,
                            tail_params, x, y, mesh: Mesh,
                            axis_name: str = "pipe",
                            n_microbatches: int = None,
                            mb_spec: P = None, label_spec: P = None,
                            param_spec=None):
    """1F1B pipeline training step: (mean loss, stage grads, tail grads,
    input cotangent).

    ``fn(stage_params, h) -> h`` is the per-stage body (stacked_params as
    in :func:`pipeline_apply`); ``loss_fn(tail_params, h, y_mb) -> scalar``
    runs on the LAST stage per microbatch — the model's head/epilogue and
    loss live here, which is what lets backward start while later
    microbatches are still filling (the 1F1B property). Activation
    memory per device is a ring of ``2 * n_stages`` stage inputs,
    independent of the microbatch count (GPipe stores all
    ``n_micro``); each backward re-linearizes the stage at its saved
    input (remat). Returns ``x_grad`` so a prologue (embedding) outside
    the pipeline can be trained through it.
    """
    if axis_name not in mesh.axis_names:
        raise MXNetError(f"mesh has no axis {axis_name!r}")
    n = mesh.shape[axis_name]
    leaves = jax.tree.leaves(stacked_params)
    if leaves and leaves[0].shape[0] != n:
        raise MXNetError(
            f"stacked_params leading dim {leaves[0].shape[0]} != pipe axis "
            f"size {n}")
    n_micro = n_microbatches or n
    batch = x.shape[0]
    if batch % n_micro:
        raise MXNetError(f"batch {batch} not divisible by "
                         f"n_microbatches {n_micro}")
    mb = batch // n_micro
    xm = x.reshape((n_micro, mb) + x.shape[1:])
    ym = y.reshape((n_micro, mb) + y.shape[1:])

    # mb_spec/label_spec shard the per-microbatch dims (dim 0 of each
    # microbatch = batch over 'data', a sequence dim over 'seq', ...);
    # the named axes become grad-reduce axes for the (replicated) params
    mb_spec = tuple(mb_spec) if mb_spec is not None else ()
    label_spec = tuple(label_spec) if label_spec is not None else mb_spec
    reduce_axes = tuple(
        ax for spec in (mb_spec,) for ax in spec if ax is not None)
    x_spec = P(None, *mb_spec) if mb_spec else P()
    y_spec = P(None, *label_spec) if label_spec else P()

    # param_spec (optional): per-leaf PartitionSpecs for stacked_params —
    # tensor parallelism inside the stage body (e.g. Megatron FFN weights
    # over 'model'; the body then psums over that axis itself). Such
    # shard-local params get shard-local exact grads, so they are NOT in
    # reduce_axes.
    p_spec = (param_spec if param_spec is not None
              else jax.tree.map(lambda _: P(axis_name), stacked_params))
    rep = jax.tree.map(lambda _: P(), tail_params)
    loss, grads, tail_g, xgrads = shard_map(
        functools.partial(_1f1b_local, fn=fn, loss_fn=loss_fn,
                          axis_name=axis_name, n_micro=n_micro,
                          reduce_axes=reduce_axes),
        mesh=mesh, in_specs=(p_spec, rep, x_spec, y_spec),
        out_specs=(P(), p_spec, rep, x_spec),
        check_vma=False)(stacked_params, tail_params, xm, ym)
    return loss, grads, tail_g, xgrads.reshape((batch,) + x.shape[1:])



def _run_nodes(nodes_list, values, name_to_val, is_train):
    """Evaluate a node list given seeded entry values and named params.

    Thin wrapper over the shared section evaluator in
    :mod:`.pipeline_hetero` — this path never sees rng nodes (graphs
    containing them delegate before reaching it), so no key is needed."""
    from .pipeline_hetero import _run
    _run(nodes_list, values, name_to_val, is_train, None, {})
    return values


def pipeline_from_symbol(symbol, mesh: Mesh, axis_name: str = "pipe",
                         n_microbatches: int = None,
                         data_name: str = "data"):
    """Drive a microbatch pipeline from ctx_group stage annotations.

    The reference expressed layer placement with ``mx.AttrScope(
    ctx_group='stageK')`` + ``group2ctx`` and got only the dependency
    engine's implicit overlap (SURVEY.md §2.5, graph_executor.cc:386-398).
    Here the annotations drive a real SPMD pipeline over the
    ``axis_name`` mesh axis, and a real model SHAPE is supported:

    * ``ctx_group='prologue'`` (or any unlabeled nodes with no staged
      ancestor) — embedding/input stem, computed outside the pipeline
      loop and trained through the pipeline's input cotangent;
    * ``ctx_group='stage0'..'stage{n-1}'`` — the pipelined body,
      connected by exactly one activation per boundary and no
      cross-stage weight sharing. Isomorphic stages (one program on
      every pipe device — the natural shape of a repeated-block
      transformer) take the fast stacked-parameter path below; stages
      that are ragged, carry aux states (BatchNorm moving stats), or
      contain rng ops (Dropout) automatically delegate to
      :func:`.pipeline_hetero.hetero_pipeline_from_symbol`, whose
      ``train_step`` additionally returns aux updates;
    * ``ctx_group='epilogue'`` — head + output op, evaluated on the
      last stage (its loss feeds the 1F1B backward schedule).

    Returns ``apply(arg_dict, x, n_microbatches=...) -> out`` (inference,
    GPipe schedule) with two attributes:

    * ``apply.train_step(arg_dict, x, labels, n_microbatches=...) ->
      (loss, grads_dict, aux_updates)`` — the 1F1B schedule
      (:func:`pipeline_value_and_grad`): backward starts while the fill
      is still running, activation memory is a ring of ``2n`` stage
      inputs per device regardless of microbatch count. Requires the
      epilogue to end in ``SoftmaxOutput`` (cross-entropy).
    * ``apply.stage_param_names`` — per-stage parameter name lists.
    """
    from ..base import MXNetError as _Err
    from .pipeline_hetero import (hetero_pipeline_from_symbol, _partition,
                                  _softmax_ce)

    n = mesh.shape.get(axis_name)
    if not n:
        raise _Err(f"mesh has no axis {axis_name!r}")

    nodes = symbol._topo_nodes()
    if symbol._aux_node_ids() or any(
            not m.is_variable and m.op.needs_rng for m in nodes):
        # aux states (BatchNorm moving stats) and rng ops (Dropout) need
        # the aux-threading / key-replay machinery — in ANY section: the
        # strict evaluator never passes rng keys, so even an unstaged
        # random op must take the hetero path
        return hetero_pipeline_from_symbol(
            symbol, mesh, axis_name=axis_name,
            n_microbatches=n_microbatches, data_name=data_name)

    # shared partitioning — pipeline_hetero owns the role-assignment and
    # boundary rules; the aux name lists are empty here (aux delegated)
    part = _partition(symbol, n, data_name)
    prologue, epilogue = part["prologue"], part["epilogue"]
    stages, stage_ios = part["stages"], part["stage_ios"]
    pro_vars = part["pro_vars"]
    epi_vars = list(part["epi_vars"])
    data_key, pro_out = part["data_key"], part["pro_out"]
    out_entries = part["out_entries"]
    out_node = out_entries[0][0]

    # -- isomorphism check: ragged stages take the flat-buffer path ------
    def signature(sec):
        return [(m.op.name,
                 tuple(sorted((k, str(v)) for k, v in m.attrs.items())))
                for m in sec]

    sig0 = signature(stages[0])
    for si in range(1, n):
        if (signature(stages[si]) != sig0
                or len(stage_ios[si][2]) != len(stage_ios[0][2])):
            return hetero_pipeline_from_symbol(
                symbol, mesh, axis_name=axis_name,
                n_microbatches=n_microbatches, data_name=data_name,
                _part=part)

    st0_nodes = stages[0]
    act_in0, act_out0, var_order0, _ = stage_ios[0]
    per_stage_vars = [io[2] for io in stage_ios]

    # -- section functions ------------------------------------------------
    def make_stage_fn(is_train):
        def stage_fn(stage_params, h):
            values = {act_in0: h}
            name_to_val = dict(zip(var_order0, stage_params))
            _run_nodes(st0_nodes, values, name_to_val, is_train)
            return values[act_out0]
        return stage_fn

    def prologue_run(pro_params, x, is_train):
        if not prologue:
            return x
        values = {data_key: x}
        _run_nodes(prologue, values, dict(zip(pro_vars, pro_params)),
                   is_train)
        return values[pro_out]

    epi_entry = stage_ios[-1][1] if epilogue else None

    # training loss: epilogue terminating in SoftmaxOutput -> CE on its
    # logits (the op's implicit loss, like the executor path)
    softmax_node = out_node if (epilogue and not out_node.is_variable
                                and out_node.op.name == "SoftmaxOutput") \
        else None
    label_var_name = None
    if softmax_node is not None and len(softmax_node.inputs) > 1:
        lbl = softmax_node.inputs[1][0]
        if lbl.is_variable:
            label_var_name = lbl.name
    # the label is fed as y, never gathered as a parameter
    epi_vars = [v for v in epi_vars if v != label_var_name]

    def epilogue_run(epi_params, h, is_train):
        if not epilogue:
            return h
        values = {epi_entry: h}
        name_to_val = dict(zip(epi_vars, epi_params))
        if label_var_name and label_var_name not in name_to_val:
            # inference: SoftmaxOutput ignores the label in forward
            name_to_val[label_var_name] = jnp.zeros(h.shape[:-1], h.dtype)
        _run_nodes(epilogue, values, name_to_val, is_train)
        return values[(id(out_entries[0][0]), out_entries[0][1])]

    sm_attrs = (softmax_node.op.attr_spec.parse(
        softmax_node.attrs, "SoftmaxOutput")
        if softmax_node is not None else {})

    def loss_fn(epi_params, h, y_mb, is_train=True):
        if softmax_node is None:
            raise _Err("train_step requires the epilogue to end in "
                       "SoftmaxOutput (cross-entropy)")
        values = {epi_entry: h}
        name_to_val = dict(zip(epi_vars, epi_params))
        if label_var_name:
            name_to_val[label_var_name] = y_mb
        head_nodes = [m for m in epilogue if m is not softmax_node]
        _run_nodes(head_nodes, values, name_to_val, is_train)
        logits_key = (id(softmax_node.inputs[0][0]),
                      softmax_node.inputs[0][1])
        logits = values.get(logits_key)
        if logits is None:  # logits come straight from the pipeline body
            logits = h
        return _softmax_ce(logits, y_mb, sm_attrs)

    # -- public entry points ----------------------------------------------
    def _gather(arg_dict, names, what):
        try:
            return tuple(arg_dict[v] for v in names)
        except KeyError as e:
            raise _Err(f"missing {what} parameter {e}")

    def _stacked(arg_dict):
        stage_params = [_gather(arg_dict, vs, f"stage{si}")
                        for si, vs in enumerate(per_stage_vars)]
        try:
            return stack_stage_params(stage_params)
        except Exception as e:
            raise _Err(f"per-stage parameter shapes differ — stages must "
                       f"be isomorphic: {e}")

    def apply(arg_dict, x, n_microbatches=n_microbatches, is_train=False):
        pro = _gather(arg_dict, pro_vars, "prologue")
        epi = _gather(arg_dict, epi_vars, "epilogue")
        h = prologue_run(pro, x, bool(is_train))
        h = pipeline_apply(make_stage_fn(bool(is_train)), _stacked(arg_dict),
                           h, mesh, axis_name=axis_name,
                           n_microbatches=n_microbatches)
        return epilogue_run(epi, h, bool(is_train))

    def train_step(arg_dict, x, labels, n_microbatches=n_microbatches,
                   mb_spec=None, label_spec=None):
        """1F1B step -> (loss, grads keyed by variable name, aux_updates).

        ``aux_updates`` is always empty on this path (graphs with aux
        states delegate to the heterogeneous pipeline, whose train_step
        returns the same 3-tuple with the written-back values).
        ``mb_spec``/``label_spec``: optional PartitionSpec entries for
        the per-microbatch dims, composing pp with dp/sp sharding
        (see :func:`pipeline_value_and_grad`)."""
        pro = _gather(arg_dict, pro_vars, "prologue")
        epi = _gather(arg_dict, epi_vars, "epilogue")
        stacked = _stacked(arg_dict)
        h0, pro_vjp = jax.vjp(
            lambda pv: prologue_run(pv, x, True), pro)
        loss, g_stacked, g_epi, dh0 = pipeline_value_and_grad(
            make_stage_fn(True), loss_fn, stacked, epi, h0, labels, mesh,
            axis_name=axis_name, n_microbatches=n_microbatches,
            mb_spec=mb_spec, label_spec=label_spec)
        (g_pro,) = pro_vjp(dh0)
        grads = {}
        for si, vs in enumerate(per_stage_vars):
            for j, name in enumerate(vs):
                grads[name] = jax.tree.leaves(g_stacked)[j][si]
        grads.update(zip(epi_vars, g_epi))
        grads.update(zip(pro_vars, g_pro))
        return loss, grads, {}

    apply.train_step = train_step
    apply.stage_param_names = per_stage_vars
    apply.prologue_param_names = list(pro_vars)
    apply.epilogue_param_names = list(epi_vars)
    apply.stage_fn = make_stage_fn(True)
    return apply
