"""Heterogeneous 1F1B pipeline: ragged stages, BatchNorm aux states, rng ops.

The companion to :mod:`.pipeline`'s isomorphic SPMD pipeline. The strict
path runs ONE stage program on every pipe device (stacked parameters
sharded over the axis) — the natural shape of a repeated-block
transformer, but it cannot stage a ResNet: the four macro-stages have
different channel counts, strides, *and* boundary activation shapes, the
blocks carry BatchNorm moving statistics (auxiliary state), and models
with Dropout need per-stage randomness. The reference's ctx_group
placement had none of these restrictions (graph_executor.cc:386-398
splits any graph between devices); this module removes them the
TPU-native way:

* **Ragged stages** — every stage's parameters / auxiliary states /
  boundary activation are flattened into fixed-size padded float32
  buffers (``(n_stages, L)`` sharded over the pipe axis). Inside
  ``shard_map`` a ``lax.switch`` over ``axis_index`` selects the stage's
  body, which statically unflattens its own slice. One SPMD program,
  static shapes everywhere, XLA-compilable — the standard trick for
  heterogeneous pipeline stages on TPU.
* **Aux states** — each device carries its stage's flat aux buffer in
  the loop carry; BatchNorm updates it on every *forward* microbatch
  (in microbatch order, matching a sequential-microbatch reference),
  and the final values are returned for writeback. Train-mode BN reads
  batch statistics, not the aux, so 1F1B's interleaving cannot skew the
  math; only ``use_global_stats=True`` would read moving stats mid-step
  (documented approximation: the backward re-linearization then sees
  the latest aux rather than the forward-time snapshot).
* **rng ops** — every random node draws from a key folded as
  ``fold_in(fold_in(fold_in(base, 1 + stage), microbatch), node)``, so
  the backward half's re-linearization (1F1B remat) replays *exactly*
  the forward's randomness, and the schedule is bit-deterministic.

``reference_step`` implements the sequential-microbatch semantics the
pipeline must reproduce (same key folding, same aux chaining) — the
test oracle and the specification in executable form.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, PartitionSpec as P

from ..base import MXNetError
from .. import random as _random

__all__ = ["hetero_pipeline_from_symbol"]

_PRO, _EPI = "prologue", "epilogue"


# ---------------------------------------------------------------------------
# graph partitioning (relaxed: aux + rng + ragged allowed)
# ---------------------------------------------------------------------------

def _assign_roles(nodes, n):
    """ctx_group -> prologue / stage<k> / epilogue roles (inherited for
    unlabeled nodes, same rules as the strict path)."""
    role_of = {}
    for node in nodes:
        if node.is_variable:
            continue
        grp = node.scope_attrs.get("ctx_group")
        role = None
        if grp in (_PRO, _EPI):
            role = grp
        elif grp is not None:
            if not grp.startswith("stage"):
                raise MXNetError(
                    f"ctx_group {grp!r} is not a pipeline label "
                    "(want 'prologue', 'epilogue' or 'stage<k>')")
            try:
                role = int(grp[len("stage"):])
            except ValueError:
                raise MXNetError(f"ctx_group {grp!r} is not a pipeline "
                                 "stage label (want 'stage<k>')")
        else:
            parent_roles = [role_of[id(p)] for p, _ in node.inputs
                            if id(p) in role_of]
            if any(r == _EPI for r in parent_roles):
                role = _EPI
            else:
                staged = [r for r in parent_roles if isinstance(r, int)]
                role = max(staged) if staged else _PRO
        role_of[id(node)] = _PRO if role is None else role
    return role_of


def _partition(symbol, n, data_name):
    """Split the graph into prologue / n stages / epilogue sections with
    per-section parameter and aux-state variable lists."""
    nodes = symbol._topo_nodes()
    aux_ids = symbol._aux_node_ids()
    out_entries = list(symbol._outputs)
    if len(out_entries) != 1:
        raise MXNetError("pipeline symbol must have exactly one output")
    role_of = _assign_roles(nodes, n)

    prologue = [m for m in nodes
                if not m.is_variable and role_of[id(m)] == _PRO]
    epilogue = [m for m in nodes
                if not m.is_variable and role_of[id(m)] == _EPI]
    stages = [[] for _ in range(n)]
    seen_max = -1
    for node in nodes:
        if node.is_variable or not isinstance(role_of[id(node)], int):
            continue
        st = role_of[id(node)]
        if not 0 <= st < n:
            raise MXNetError(f"stage{st} out of range for pipe axis "
                             f"size {n}")
        if st < seen_max:
            raise MXNetError(
                "stage labels must be topologically non-decreasing")
        seen_max = max(seen_max, st)
        stages[st].append(node)
    if any(not s for s in stages):
        raise MXNetError(f"need exactly {n} populated stages (pipe axis "
                         f"size), got {sum(1 for s in stages if s)}")
    out_node = out_entries[0][0]
    if epilogue and role_of.get(id(out_node)) != _EPI:
        raise MXNetError("the symbol output must come from the epilogue")

    var_role = {}

    def section_io(sec_nodes, role):
        produced = {(id(m), i) for m in sec_nodes
                    for i in range(m.num_outputs())}
        entries, var_names, aux_names = [], [], []
        for m in sec_nodes:
            for parent, i in m.inputs:
                key = (id(parent), i)
                if key in produced:
                    continue
                if parent.is_variable and parent.name != data_name:
                    prev = var_role.setdefault(id(parent), role)
                    if prev != role:
                        raise MXNetError(
                            f"variable {parent.name} is shared between "
                            f"{prev} and {role} — unsupported in the SPMD "
                            "pipeline (make per-section copies)")
                    bucket = (aux_names if id(parent) in aux_ids
                              else var_names)
                    if parent.name not in bucket:
                        bucket.append(parent.name)
                else:
                    if key not in entries:
                        entries.append(key)
        return entries, var_names, aux_names

    pro_entries, pro_vars, pro_aux = section_io(prologue, _PRO)
    if prologue:
        if len(pro_entries) != 1:
            raise MXNetError("prologue must consume exactly the data input")
        data_key = pro_entries[0]
        cands = {(id(p), i) for m in stages[0] for p, i in m.inputs
                 if role_of.get(id(p)) == _PRO}
        if len(cands) != 1:
            raise MXNetError("prologue -> stage0 boundary must be exactly "
                             f"one tensor, got {len(cands)}")
        pro_out = cands.pop()
    else:
        data_key = None
        pro_out = None

    stage_ios = []
    for si, sec in enumerate(stages):
        entries, var_names, aux_names = section_io(sec, si)
        if len(entries) != 1:
            raise MXNetError(f"stage{si} must consume exactly one "
                             f"cross-stage tensor, got {len(entries)}")
        act_in = entries[0]
        if si == 0 and prologue and act_in != pro_out:
            raise MXNetError("stage0 must consume the prologue output")
        downstream = stages[si + 1] if si < n - 1 else epilogue
        produced = {(id(m), i) for m in sec for i in range(m.num_outputs())}
        if downstream:
            down_prod = {(id(m), i) for m in downstream
                         for i in range(m.num_outputs())}
            outs = {(id(p), i) for m in downstream for p, i in m.inputs
                    if (id(p), i) in produced and (id(p), i) not in down_prod}
            if len(outs) != 1:
                raise MXNetError(f"stage{si} boundary must be exactly one "
                                 f"tensor, got {len(outs)}")
            act_out = outs.pop()
        else:
            act_out = (id(out_entries[0][0]), out_entries[0][1])
        stage_ios.append((act_in, act_out, var_names, aux_names))

    if epilogue:
        epi_entries, epi_vars, epi_aux = section_io(epilogue, _EPI)
        if epi_aux:
            raise MXNetError(
                "auxiliary states in the epilogue are not supported — "
                "keep BatchNorm out of the head (it runs replicated on "
                f"the last stage): {epi_aux}")
        if epi_entries != [stage_ios[-1][1]]:
            raise MXNetError(
                "epilogue must consume exactly the last stage's output; "
                f"it consumes {len(epi_entries)} cross-section tensors")
    else:
        epi_vars = []

    rng_nodes = [m for m in nodes
                 if not m.is_variable and m.op.needs_rng]
    rng_index = {id(m): i for i, m in enumerate(rng_nodes)}
    return dict(nodes=nodes, prologue=prologue, stages=stages,
                epilogue=epilogue, stage_ios=stage_ios, pro_vars=pro_vars,
                pro_aux=pro_aux, epi_vars=epi_vars, data_key=data_key,
                pro_out=pro_out, out_entries=out_entries,
                rng_index=rng_index)


# ---------------------------------------------------------------------------
# section evaluation (executor-compatible: rng folding + aux collection)
# ---------------------------------------------------------------------------

def _run(nodes, values, name_to_val, is_train, key, rng_index):
    """Evaluate a node list; returns {aux_name: new_value} updates."""
    aux_updates = {}
    for node in nodes:
        ins = []
        for parent, i in node.inputs:
            k = (id(parent), i)
            ins.append(values[k] if k in values
                       else name_to_val[parent.name])
        call_attrs = dict(node.attrs)
        if node.op.needs_is_train:
            call_attrs["_is_train"] = is_train
        if node.op.key_var_num_args and not call_attrs.get(
                node.op.key_var_num_args):
            call_attrs[node.op.key_var_num_args] = len(ins)
        if node.op.needs_rng:
            out = node.op.fn(jax.random.fold_in(key, rng_index[id(node)]),
                             *ins, **call_attrs)
        else:
            out = node.op.fn(*ins, **call_attrs)
        if not isinstance(out, tuple):
            out = (out,)
        for i, o in enumerate(out):
            values[(id(node), i)] = o
        if is_train and node.op.aux_update:
            for out_idx, in_idx in node.op.aux_update.items():
                if in_idx < len(node.inputs):
                    p, _ = node.inputs[in_idx]
                    if p.is_variable and p.name in name_to_val:
                        aux_updates[p.name] = out[out_idx]
    return aux_updates


def _tracing_active():
    """True when called under a jax trace (jit/grad) rather than eagerly."""
    try:
        from jax.core import trace_ctx
        return type(trace_ctx.trace).__name__ != "EvalTrace"
    except Exception:
        return False


def _softmax_ce(logits, y_mb, sm_attrs):
    """SoftmaxOutput's implicit cross-entropy, honoring the op's declared
    semantics (use_ignore/ignore_label, smooth_alpha, grad_scale) the way
    the executor path does (ops/nn_ops.py SoftmaxOutput). Shared by both
    pipeline loss heads."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    ids = y_mb.astype(jnp.int32)
    smooth = float(sm_attrs.get("smooth_alpha", 0.0) or 0.0)
    picked = jnp.take_along_axis(
        logp, jnp.maximum(ids, 0)[..., None], axis=-1)[..., 0]
    if smooth:
        picked = (1.0 - smooth) * picked + smooth * logp.mean(axis=-1)
    if sm_attrs.get("use_ignore"):
        keep = (ids != int(sm_attrs.get("ignore_label", -1))) \
            .astype(picked.dtype)
        loss = -(picked * keep).sum() / jnp.maximum(keep.sum(), 1.0)
    else:
        loss = -jnp.mean(picked)
    return loss * float(sm_attrs.get("grad_scale", 1.0) or 1.0)


# ---------------------------------------------------------------------------
# flat-buffer packing
# ---------------------------------------------------------------------------

def _meta_of(arrs):
    """[(offset, size, shape, dtype)] + total for a value list."""
    metas, off = [], 0
    for a in arrs:
        sz = int(np.prod(a.shape)) if a.shape else 1
        metas.append((off, sz, tuple(a.shape), a.dtype))
        off += sz
    return metas, off


def _pack(vals, L):
    parts = [jnp.ravel(v).astype(jnp.float32) for v in vals]
    total = sum(p.shape[0] for p in parts)
    if total < L:
        parts.append(jnp.zeros((L - total,), jnp.float32))
    return (jnp.concatenate(parts) if parts
            else jnp.zeros((L,), jnp.float32))


def _unpack(flat, metas):
    return tuple(
        jax.lax.dynamic_slice_in_dim(flat, off, sz).reshape(shape)
        .astype(dt)
        for off, sz, shape, dt in metas)


def _pad_flat(h, L):
    f = jnp.ravel(h).astype(jnp.float32)
    return jnp.concatenate([f, jnp.zeros((L - f.shape[0],), jnp.float32)]) \
        if f.shape[0] < L else f


# ---------------------------------------------------------------------------
# the builder
# ---------------------------------------------------------------------------

def hetero_pipeline_from_symbol(symbol, mesh: Mesh, axis_name: str = "pipe",
                                n_microbatches: int = None,
                                data_name: str = "data", _part=None):
    """ctx_group-staged pipeline for heterogeneous graphs.

    Same surface as :func:`.pipeline.pipeline_from_symbol` (which
    delegates here when stages are ragged or carry aux/rng), plus aux
    state threading:

    * ``apply(arg_dict, x, aux_dict=None, n_microbatches=...,
      is_train=False) -> out`` — GPipe-scheduled inference.
    * ``apply.train_step(arg_dict, x, labels, aux_dict=None,
      n_microbatches=..., rng=None) -> (loss, grads, aux_updates)`` —
      the 1F1B schedule; ``aux_updates`` holds every section's final
      auxiliary values for writeback.
    * ``apply.reference_step(...)`` — identical signature/returns,
      sequential-microbatch semantics (the exactness oracle).
    """
    if axis_name not in mesh.axis_names:
        raise MXNetError(f"mesh has no axis {axis_name!r}")
    n = mesh.shape[axis_name]
    # _part: precomputed partition handed over by pipeline_from_symbol's
    # ragged-stage delegation, so the graph is only partitioned once
    part = _part if _part is not None else _partition(symbol, n, data_name)
    stages, stage_ios = part["stages"], part["stage_ios"]
    prologue, epilogue = part["prologue"], part["epilogue"]
    pro_vars, pro_aux = part["pro_vars"], part["pro_aux"]
    epi_vars = part["epi_vars"]
    rng_index = part["rng_index"]
    out_entries = part["out_entries"]
    out_node = out_entries[0][0]
    per_stage_vars = [io[2] for io in stage_ios]
    per_stage_aux = [io[3] for io in stage_ios]

    # loss head: epilogue terminating in SoftmaxOutput -> its implicit CE
    softmax_node = out_node if (epilogue and not out_node.is_variable
                                and out_node.op.name == "SoftmaxOutput") \
        else None
    label_var_name = None
    if softmax_node is not None and len(softmax_node.inputs) > 1:
        lbl = softmax_node.inputs[1][0]
        if lbl.is_variable:
            label_var_name = lbl.name
    epi_vars = [v for v in epi_vars if v != label_var_name]
    sm_attrs = (softmax_node.op.attr_spec.parse(
        softmax_node.attrs, "SoftmaxOutput")
        if softmax_node is not None else {})
    epi_entry = stage_ios[-1][1] if epilogue else None

    def stage_compute(si, params, auxs, h, key, is_train):
        """One stage body -> (act_out, new aux tuple)."""
        nodes = stages[si]
        act_in, act_out, vnames, anames = stage_ios[si]
        values = {act_in: h}
        ntv = dict(zip(vnames, params))
        ntv.update(zip(anames, auxs))
        upd = _run(nodes, values, ntv, is_train, key, rng_index)
        return values[act_out], tuple(upd.get(a, ntv[a]) for a in anames)

    def prologue_compute(params, auxs, x, key, is_train):
        if not prologue:
            return x, {}
        values = {part["data_key"]: x}
        ntv = dict(zip(pro_vars, params))
        ntv.update(zip(pro_aux, auxs))
        upd = _run(prologue, values, ntv, is_train, key, rng_index)
        return values[part["pro_out"]], upd

    def epilogue_compute(params, h, key, is_train, y=None):
        if not epilogue:
            return h
        values = {epi_entry: h}
        ntv = dict(zip(epi_vars, params))
        if label_var_name and label_var_name not in ntv:
            ntv[label_var_name] = (y if y is not None
                                   else jnp.zeros(h.shape[:-1], h.dtype))
        _run(epilogue, values, ntv, is_train, key, rng_index)
        return values[(id(out_entries[0][0]), out_entries[0][1])]

    def loss_from_h(epi_params, h, y_mb, key):
        if softmax_node is None:
            raise MXNetError("train_step requires the epilogue to end in "
                             "SoftmaxOutput (cross-entropy)")
        values = {epi_entry: h}
        ntv = dict(zip(epi_vars, epi_params))
        if label_var_name:
            ntv[label_var_name] = y_mb
        head = [m for m in epilogue if m is not softmax_node]
        _run(head, values, ntv, True, key, rng_index)
        logits_key = (id(softmax_node.inputs[0][0]),
                      softmax_node.inputs[0][1])
        logits = values.get(logits_key, h)
        return _softmax_ce(logits, y_mb, sm_attrs)

    # rng stream layout: fold(base, 0)=prologue, 1+s=stage s, 1+n=epilogue
    def _skey(base, section, m=None):
        k = jax.random.fold_in(base, section)
        return k if m is None else jax.random.fold_in(k, m)

    def _gather(arg_dict, names, what):
        try:
            return tuple(arg_dict[v] for v in names)
        except KeyError as e:
            raise MXNetError(f"missing {what} parameter {e}")

    def _base_key(rng):
        """Per-step base key. Under a jax trace with random nodes in the
        graph, a default next_key() would be captured ONCE at trace time
        and every later step would replay the same dropout masks — make
        that a loud error instead."""
        if rng is not None:
            return rng
        if rng_index and _tracing_active():
            raise MXNetError(
                "this pipeline contains rng ops and is being traced "
                "(jax.jit) with rng=None — pass an explicit per-step rng "
                "key or the random stream would be frozen at trace time")
        return _random.next_key()

    def _resolve(arg_dict, aux_dict, mb_shape, x_dtype):
        """Static per-call metadata: param/aux metas, boundary act shapes
        and the padded buffer widths."""
        p_metas, p_tot, a_metas, a_tot = [], [], [], []
        for si in range(n):
            pm, pt = _meta_of(_gather(arg_dict, per_stage_vars[si],
                                      f"stage{si}"))
            am, at = _meta_of(_gather(aux_dict, per_stage_aux[si],
                                      f"stage{si} aux"))
            p_metas.append(pm)
            p_tot.append(pt)
            a_metas.append(am)
            a_tot.append(at)
        key0 = jax.random.PRNGKey(0)
        pro_p = _gather(arg_dict, pro_vars, "prologue")
        pro_a = _gather(aux_dict, pro_aux, "prologue aux")
        h = jax.eval_shape(
            lambda xx: prologue_compute(pro_p, pro_a, xx, key0, True)[0],
            jax.ShapeDtypeStruct(mb_shape, x_dtype))
        act_shapes = [h]
        for si in range(n):
            sp = _gather(arg_dict, per_stage_vars[si], f"stage{si}")
            sa = _gather(aux_dict, per_stage_aux[si], f"stage{si} aux")
            h = jax.eval_shape(
                functools.partial(
                    lambda hh, si, sp, sa: stage_compute(
                        si, sp, sa, hh, key0, True)[0],
                    si=si, sp=sp, sa=sa), h)
            act_shapes.append(h)
        L_act = max(int(np.prod(s.shape)) for s in act_shapes)
        L_p = max(p_tot) if p_tot else 1
        L_aux = max(max(a_tot), 1) if a_tot else 1
        return p_metas, a_metas, act_shapes, L_act, max(L_p, 1), L_aux

    def _branches(p_metas, a_metas, act_shapes, L_act, L_aux, is_train):
        """Per-stage switch branches over the flat buffers."""
        fwd, diff = [], []
        for k in range(n):
            a_in, a_out = act_shapes[k], act_shapes[k + 1]
            s_in = int(np.prod(a_in.shape))

            def mk(k=k, a_in=a_in, s_in=s_in):
                def run(flat_p, flat_aux, flat_h, mkey):
                    params = _unpack(flat_p, p_metas[k])
                    auxs = _unpack(flat_aux, a_metas[k])
                    h = (jax.lax.dynamic_slice_in_dim(flat_h, 0, s_in)
                         .reshape(a_in.shape).astype(a_in.dtype))
                    h_out, aux_new = stage_compute(k, params, auxs, h,
                                                   mkey, is_train)
                    return _pad_flat(h_out, L_act), _pack(aux_new, L_aux)

                def run_diff(flat_p, flat_aux, flat_h, mkey):
                    return run(flat_p, flat_aux, flat_h, mkey)[0]
                return run, run_diff

            f, d = mk()
            fwd.append(f)
            diff.append(d)
        return fwd, diff

    # -- 1F1B training ----------------------------------------------------
    def _local_train(stacked_p, stacked_aux, epi_params, xflat, ym,
                     base_key, *, n_micro, fwd_br, diff_br, act_n_shape,
                     L_act):
        nn = axis_size(axis_name)
        idx = jax.lax.axis_index(axis_name)
        p_loc = jnp.squeeze(stacked_p, 0)
        aux0 = jnp.squeeze(stacked_aux, 0)
        fwd_perm = [(i, (i + 1) % nn) for i in range(nn)]
        bwd_perm = [(i, (i - 1) % nn) for i in range(nn)]
        ring_sz = 2 * nn
        is_first = idx == 0
        is_last = idx == nn - 1
        s_n = int(np.prod(act_n_shape.shape))

        def mkey(m):
            return _skey(base_key, 1 + idx, m)

        def loss_local(epi, flat_h, y_mb, m):
            h = (jax.lax.dynamic_slice_in_dim(flat_h, 0, s_n)
                 .reshape(act_n_shape.shape).astype(act_n_shape.dtype))
            return loss_from_h(epi, h, y_mb, _skey(base_key, 1 + nn, m))

        def masked_add(acc, upd, active):
            return jax.tree.map(
                lambda a, u: a + jnp.where(active, u, jnp.zeros_like(u)),
                acc, upd)

        def tick(t, carry):
            (state_f, state_b, pending_ct, ring, grads, aux, tail_g,
             loss_sum, xgrads) = carry

            # backward half (reads pending_ct from the previous tick's
            # forward on the last stage)
            m_b = t - 2 * nn + 1 + idx
            active_b = (m_b >= 0) & (m_b < n_micro)
            mbc = jnp.clip(m_b, 0, n_micro - 1)
            ct_in = jnp.where(is_last, pending_ct, state_b)
            h_saved = jax.lax.dynamic_index_in_dim(
                ring, mbc % ring_sz, 0, keepdims=False)
            _, svjp = jax.vjp(
                lambda p, h: jax.lax.switch(idx, diff_br, p, aux, h,
                                            mkey(mbc)),
                p_loc, h_saved)
            dparams, dh_in = svjp(ct_in)
            grads = grads + jnp.where(active_b, dparams,
                                      jnp.zeros_like(dparams))
            xg_upd = jax.lax.dynamic_update_index_in_dim(
                xgrads, dh_in, mbc, 0)
            xgrads = jnp.where(active_b & is_first, xg_upd, xgrads)

            # forward half
            m_f = t - idx
            active_f = (m_f >= 0) & (m_f < n_micro)
            mth = jnp.clip(m_f, 0, n_micro - 1)
            inp = jax.lax.dynamic_index_in_dim(xflat, mth, 0,
                                               keepdims=False)
            h_in = jnp.where(is_first, inp, state_f)
            ring_upd = jax.lax.dynamic_update_index_in_dim(
                ring, h_in, mth % ring_sz, 0)
            ring = jnp.where(active_f, ring_upd, ring)
            h_out, aux_new = jax.lax.switch(idx, fwd_br, p_loc, aux, h_in,
                                            mkey(mth))
            aux = jnp.where(active_f, aux_new, aux)
            y_mb = jax.lax.dynamic_index_in_dim(ym, mth, 0, keepdims=False)
            l, (d_epi, dh) = jax.value_and_grad(loss_local, argnums=(0, 1))(
                epi_params, h_out, y_mb, mth)
            produce = active_f & is_last
            loss_sum = loss_sum + jnp.where(produce, l, 0.0)
            tail_g = masked_add(tail_g, d_epi, produce)
            pending_ct = jnp.where(produce, dh, pending_ct)

            state_f = jax.lax.ppermute(h_out, axis_name, fwd_perm)
            state_b = jax.lax.ppermute(dh_in, axis_name, bwd_perm)
            return (state_f, state_b, pending_ct, ring, grads, aux,
                    tail_g, loss_sum, xgrads)

        zeros_h = jnp.zeros((L_act,), jnp.float32)
        init = (zeros_h, zeros_h, zeros_h,
                jnp.zeros((ring_sz, L_act), jnp.float32),
                jnp.zeros_like(p_loc), aux0,
                jax.tree.map(jnp.zeros_like, epi_params),
                jnp.zeros((), jnp.float32),
                jnp.zeros((n_micro, L_act), jnp.float32))
        carry = jax.lax.fori_loop(0, n_micro + 2 * nn - 1, tick, init)
        _, _, _, _, grads, aux, tail_g, loss_sum, xgrads = carry
        loss = jax.lax.psum(loss_sum, axis_name) / n_micro
        tail_g = jax.tree.map(
            lambda g: jax.lax.psum(g, axis_name) / n_micro, tail_g)
        xgrads = jax.lax.psum(xgrads, axis_name) / n_micro
        return loss, grads[None] / n_micro, aux[None], tail_g, xgrads

    # -- GPipe inference ---------------------------------------------------
    def _local_fwd(stacked_p, stacked_aux, xflat, base_key, *, n_micro,
                   fwd_br, L_act):
        nn = axis_size(axis_name)
        idx = jax.lax.axis_index(axis_name)
        p_loc = jnp.squeeze(stacked_p, 0)
        aux_loc = jnp.squeeze(stacked_aux, 0)
        perm = [(i, (i + 1) % nn) for i in range(nn)]

        def tick(t, carry):
            state, outputs = carry
            inp = jax.lax.dynamic_index_in_dim(
                xflat, jnp.clip(t, 0, n_micro - 1), 0, keepdims=False)
            h_in = jnp.where(idx == 0, inp, state)
            mth = jnp.clip(t - idx, 0, n_micro - 1)
            out, _ = jax.lax.switch(idx, fwd_br, p_loc, aux_loc, h_in,
                                    _skey(base_key, 1 + idx, mth))
            m = t - (nn - 1)
            upd = jax.lax.dynamic_update_index_in_dim(
                outputs, out, jnp.clip(m, 0, n_micro - 1), 0)
            outputs = jnp.where((m >= 0) & (idx == nn - 1), upd, outputs)
            state = jax.lax.ppermute(out, axis_name, perm)
            return state, outputs

        init = (jnp.zeros((L_act,), jnp.float32),
                jnp.zeros((n_micro, L_act), jnp.float32))
        _, outputs = jax.lax.fori_loop(0, n_micro + nn - 1, tick, init)
        return outputs[None]

    def _micro(x, n_microbatches):
        n_micro = n_microbatches or n
        if x.shape[0] % n_micro:
            raise MXNetError(f"batch {x.shape[0]} not divisible by "
                             f"n_microbatches {n_micro}")
        return n_micro, x.shape[0] // n_micro

    # -- public entry points ----------------------------------------------
    def apply(arg_dict, x, aux_dict=None, n_microbatches=n_microbatches,
              is_train=False, rng=None):
        aux_dict = aux_dict or {}
        base_key = _base_key(rng)
        n_micro, mb = _micro(x, n_microbatches)
        p_metas, a_metas, act_shapes, L_act, L_p, L_aux = _resolve(
            arg_dict, aux_dict, (mb,) + tuple(x.shape[1:]), x.dtype)
        fwd_br, _ = _branches(p_metas, a_metas, act_shapes, L_act, L_aux,
                              bool(is_train))
        pro_p = _gather(arg_dict, pro_vars, "prologue")
        pro_a = _gather(aux_dict, pro_aux, "prologue aux")
        h0, _ = prologue_compute(pro_p, pro_a, x, _skey(base_key, 0),
                                 bool(is_train))
        h0m = h0.reshape((n_micro, mb) + h0.shape[1:])
        xflat = jax.vmap(lambda h: _pad_flat(h, L_act))(h0m)

        stacked_p = jnp.stack([
            _pack(_gather(arg_dict, per_stage_vars[k], f"stage{k}"), L_p)
            for k in range(n)])
        stacked_aux = jnp.stack([
            _pack(_gather(aux_dict, per_stage_aux[k], f"stage{k} aux"),
                  L_aux) for k in range(n)])
        out = shard_map(
            functools.partial(_local_fwd, n_micro=n_micro, fwd_br=fwd_br,
                              L_act=L_act),
            mesh=mesh, in_specs=(P(axis_name), P(axis_name), P(), P()),
            out_specs=P(axis_name), check_vma=False)(
            stacked_p, stacked_aux, xflat, base_key)
        a_n = act_shapes[n]
        s_n = int(np.prod(a_n.shape))
        h = (out[-1][:, :s_n].reshape((n_micro,) + a_n.shape)
             .astype(a_n.dtype))
        h = h.reshape((x.shape[0],) + a_n.shape[1:])
        epi_p = _gather(arg_dict, epi_vars, "epilogue")
        return epilogue_compute(epi_p, h, _skey(base_key, 1 + n),
                                bool(is_train))

    def train_step(arg_dict, x, labels, aux_dict=None,
                   n_microbatches=n_microbatches, rng=None,
                   mb_spec=None, label_spec=None):
        """1F1B step -> (loss, grads by name, aux_updates by name)."""
        if mb_spec is not None or label_spec is not None:
            raise MXNetError(
                "mb_spec/label_spec (dp/sp sharding of microbatches) is "
                "not supported on the heterogeneous pipeline path — the "
                "flat activation buffers carry no named sub-axes; shard "
                "the batch outside the pipeline or use isomorphic stages")
        aux_dict = aux_dict or {}
        base_key = _base_key(rng)
        n_micro, mb = _micro(x, n_microbatches)
        p_metas, a_metas, act_shapes, L_act, L_p, L_aux = _resolve(
            arg_dict, aux_dict, (mb,) + tuple(x.shape[1:]), x.dtype)
        fwd_br, diff_br = _branches(p_metas, a_metas, act_shapes, L_act,
                                    L_aux, True)
        pro_p = _gather(arg_dict, pro_vars, "prologue")
        pro_a = _gather(aux_dict, pro_aux, "prologue aux")

        def _pro(pv):
            return prologue_compute(pv, pro_a, x, _skey(base_key, 0), True)
        (h0, pro_vjp, pro_upd) = jax.vjp(_pro, pro_p, has_aux=True)
        h0m = h0.reshape((n_micro, mb) + h0.shape[1:])
        xflat = jax.vmap(lambda h: _pad_flat(h, L_act))(h0m)
        ym = labels.reshape((n_micro, mb) + labels.shape[1:])

        stacked_p = jnp.stack([
            _pack(_gather(arg_dict, per_stage_vars[k], f"stage{k}"), L_p)
            for k in range(n)])
        stacked_aux = jnp.stack([
            _pack(_gather(aux_dict, per_stage_aux[k], f"stage{k} aux"),
                  L_aux) for k in range(n)])
        epi_p = _gather(arg_dict, epi_vars, "epilogue")

        loss, g_stacked, aux_out, g_epi, xgrads = shard_map(
            functools.partial(_local_train, n_micro=n_micro,
                              fwd_br=fwd_br, diff_br=diff_br,
                              act_n_shape=act_shapes[n], L_act=L_act),
            mesh=mesh,
            in_specs=(P(axis_name), P(axis_name), P(), P(), P(), P()),
            out_specs=(P(), P(axis_name), P(axis_name), P(), P()),
            check_vma=False)(
            stacked_p, stacked_aux, epi_p, xflat, ym, base_key)

        s0 = int(np.prod(act_shapes[0].shape))
        dh0 = (xgrads[:, :s0].reshape((n_micro,) + act_shapes[0].shape)
               .astype(act_shapes[0].dtype)
               .reshape((x.shape[0],) + act_shapes[0].shape[1:]))
        (g_pro,) = pro_vjp(dh0)

        grads = {}
        for k in range(n):
            for name, g in zip(per_stage_vars[k],
                               _unpack(g_stacked[k], p_metas[k])):
                grads[name] = g
        grads.update(zip(epi_vars, g_epi))
        grads.update(zip(pro_vars, g_pro))
        aux_updates = dict(pro_upd)
        for k in range(n):
            for name, v in zip(per_stage_aux[k],
                               _unpack(aux_out[k], a_metas[k])):
                aux_updates[name] = v
        return loss, grads, aux_updates

    def reference_step(arg_dict, x, labels, aux_dict=None,
                       n_microbatches=n_microbatches, rng=None):
        """Sequential-microbatch oracle: identical semantics (key folding,
        aux chaining, loss normalization) without the pipeline."""
        aux_dict = dict(aux_dict or {})
        base_key = _base_key(rng)
        n_micro, mb = _micro(x, n_microbatches)
        pro_p = _gather(arg_dict, pro_vars, "prologue")
        pro_a = _gather(aux_dict, pro_aux, "prologue aux")

        def _pro(pv):
            return prologue_compute(pv, pro_a, x, _skey(base_key, 0), True)
        (h0, pro_vjp, pro_upd) = jax.vjp(_pro, pro_p, has_aux=True)
        h0m = h0.reshape((n_micro, mb) + h0.shape[1:])
        ym = labels.reshape((n_micro, mb) + labels.shape[1:])
        epi_p = _gather(arg_dict, epi_vars, "epilogue")
        stage_p = [_gather(arg_dict, per_stage_vars[k], f"stage{k}")
                   for k in range(n)]
        aux_cur = [list(_gather(aux_dict, per_stage_aux[k],
                                f"stage{k} aux")) for k in range(n)]

        g_stages = [jax.tree.map(jnp.zeros_like, sp) for sp in stage_p]
        g_epi = jax.tree.map(jnp.zeros_like, epi_p)
        dh0m = []
        loss_sum = 0.0
        for m in range(n_micro):
            def f(sps, ep, h):
                auxs_new = []
                for k in range(n):
                    h, a_new = stage_compute(
                        k, sps[k], tuple(aux_cur[k]), h,
                        _skey(base_key, 1 + k, m), True)
                    auxs_new.append(a_new)
                return (loss_from_h(ep, h, ym[m],
                                    _skey(base_key, 1 + n, m)), auxs_new)
            l, auxs_new = f(stage_p, epi_p, h0m[m])
            (gl_st, gl_epi, gl_h) = jax.grad(
                lambda sps, ep, h: f(sps, ep, h)[0],
                argnums=(0, 1, 2))(stage_p, epi_p, h0m[m])
            for k in range(n):
                aux_cur[k] = list(auxs_new[k])
                g_stages[k] = jax.tree.map(lambda a, b: a + b,
                                           g_stages[k], gl_st[k])
            g_epi = jax.tree.map(lambda a, b: a + b, g_epi, gl_epi)
            dh0m.append(gl_h)
            loss_sum = loss_sum + l
        loss = loss_sum / n_micro
        dh0 = (jnp.stack(dh0m) / n_micro).reshape(h0.shape)
        (g_pro,) = pro_vjp(dh0)
        grads = {}
        for k in range(n):
            grads.update(zip(per_stage_vars[k],
                             jax.tree.map(lambda g: g / n_micro,
                                          g_stages[k])))
        grads.update(zip(epi_vars,
                         jax.tree.map(lambda g: g / n_micro, g_epi)))
        grads.update(zip(pro_vars, g_pro))
        aux_updates = dict(pro_upd)
        for k in range(n):
            aux_updates.update(zip(per_stage_aux[k], aux_cur[k]))
        return loss, grads, aux_updates

    apply.train_step = train_step
    apply.reference_step = reference_step
    apply.stage_param_names = per_stage_vars
    apply.stage_aux_names = per_stage_aux
    apply.prologue_param_names = list(pro_vars)
    apply.epilogue_param_names = list(epi_vars)
    return apply
