"""Executor: a bound, XLA-compiled symbol graph.

Reference analogue: include/mxnet/executor.h + src/executor/graph_executor.cc
(Bind/SimpleBind/Forward/Backward). The reference compiles a Symbol into a
memory-planned, device-placed sequence of engine ops (SURVEY.md §3.2); here
the whole graph is traced once into a jax computation and jit-compiled —
XLA does gradient construction (vjp), buffer assignment (PlanMemory), fusion
(bulk exec) and scheduling. Forward and fused forward+backward are separate
compiled programs; the fused path is what Module uses per training step.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from . import autograd, profiler, random as _random
from .base import MXNetError, getenv
from .ndarray import NDArray
from .ndarray.ndarray import _as_jax
from .ops.registry import KEPT_RESIDUAL

__all__ = ["Executor", "build_graph_eval", "build_placed_graph_eval"]


def _ambient_mesh_key():
    """Hashable identity of the ambient mesh_scope mesh (or None).

    Mesh-aware ops resolve the mesh at trace time, so compiled executor
    programs are keyed on it — entering/leaving mesh_scope between calls
    forces a retrace instead of silently reusing a program traced under
    the other sharding regime."""
    from .parallel.mesh import current_mesh
    return current_mesh()


def _resolve_group_devs(group2ctx):
    """group2ctx {name: Context|Device} -> {name: jax Device}."""
    devs = {}
    for grp, c in (group2ctx or {}).items():
        dev = getattr(c, "jax_device", c)  # Context property or raw Device
        if callable(dev):
            dev = dev()
        if dev is not None:
            devs[grp] = dev
    return devs


def _is_placed(group2ctx):
    """True when the bind takes the multi-device placed path (>=2 distinct
    group devices) — the one predicate shared by Symbol.simple_bind/bind
    grad allocation and Executor.__init__'s branch."""
    return len(set(_resolve_group_devs(group2ctx).values())) >= 2


def _call_node(node, ins, rng, rng_index, is_train):
    """The one place a graph op is called while a program traces: under
    ``jax.named_scope("<op>/<node>")`` (``Convolution/stage1_unit1_conv1``),
    so every HLO instruction it emits, forward and transposed, carries the
    graph's own name in its ``op_name`` (read back through
    ``profiler.op_scopes``). A scope exists at trace time only. Returns the
    node's outputs as a tuple."""
    call_attrs = dict(node.attrs)
    op = node.op
    if op.needs_is_train:
        call_attrs["_is_train"] = is_train
    if op.key_var_num_args and not call_attrs.get(op.key_var_num_args):
        call_attrs[op.key_var_num_args] = len(ins)
    with jax.named_scope(f"{op.name}/{node.name}"):
        if id(node) in rng_index:
            key = jax.random.fold_in(rng, rng_index[id(node)])
            out = op.fn(key, *ins, **call_attrs)
        elif op.needs_rng:
            out = op.fn(rng, *ins, **call_attrs)
        else:
            out = op.fn(*ins, **call_attrs)
    return out if isinstance(out, tuple) else (out,)


def _block_segments(nodes):
    """The op nodes in topological order, cut into runs that share a
    ``__block__`` attribute (``mx.AttrScope(__block__="layer3")``): a list
    of ``(block name or None, [nodes])``."""
    runs = []
    for node in nodes:
        if node.is_variable:
            continue
        tag = node.scope_attrs.get("__block__")
        if runs and runs[-1][0] == tag:
            runs[-1][1].append(node)
        else:
            runs.append((tag, [node]))
    return runs


_named_residuals = jax.checkpoint_policies.save_only_these_names(KEPT_RESIDUAL)


def _keep_named_residuals(prim, *avals, **params):
    """The block checkpoint's policy: recompute everything but the values an
    op named with ``ops.registry.keep_residual``. JAX asks it once for each
    equation of a block while the training step is traced, so the counters
    ``remat.kept_values`` / ``remat.kept_bytes`` say what the step's
    checkpoints were told to keep; they never grow once the step is
    compiled."""
    keep = _named_residuals(prim, *avals, **params)
    if keep:
        profiler.count("remat.kept_values")
        profiler.count("remat.kept_bytes",
                       sum(a.size * a.dtype.itemsize for a in avals))
    return keep


def build_graph_eval(symbol, collect_all=False, proxies=None,
                     remat_blocks=False):
    """Build eval_fn(arg_vals: dict, aux_vals: dict, rng, is_train: bool)
    -> (outputs: list, aux_updates: dict). Pure and jax-traceable.

    With ``collect_all`` the outputs list holds every op output in
    topological order instead of just the symbol's outputs (Monitor).

    ``proxies`` maps node id -> extra input name: that node's first
    output gets the named arg added to it when present in ``arg_vals``.
    Fed zeros it changes nothing, but its vjp cotangent is exactly the
    gradient at that op's output — the hook the sparse-grad Embedding
    path uses to obtain d(out) without differentiating through the
    (vocab, dim) gather (see Executor).

    Nodes made under ``mx.AttrScope(__block__=<name>)`` run inside
    ``jax.named_scope(<name>)``, so a model's blocks read ``layer3/...`` in
    every instruction's ``op_name``. With ``remat_blocks`` (the remat-policy
    pass sets it where the model asks, ``__remat__="block"``) each such run
    of nodes is also a ``jax.checkpoint``: a training step keeps what
    crosses a block's boundary and recomputes its inside in the backward,
    but for the values an op named with ``ops.registry.keep_residual``
    (the band attention's output and row logsumexp: the layer's dearest
    operation, one activation in size), which it keeps too. A block that
    names nothing is a plain checkpoint."""
    nodes = symbol._topo_nodes()
    aux_ids = symbol._aux_node_ids()
    # deterministic per-random-node key folding. Only nodes that ACTUALLY
    # sample (op.uses_rng — e.g. RNN with inter-layer dropout p=0 does
    # not) get a folded key; ops whose signature takes a key they will
    # not use receive the step key unfolded. A graph with no sampling
    # node at all sets ``eval_fn.needs_rng = False`` so the caller can
    # skip the per-step key split entirely.
    random_nodes = [n for n in nodes
                    if n.op is not None and n.op.uses_rng(n.attrs)]
    rng_index = {id(n): i for i, n in enumerate(random_nodes)}
    out_entries = list(symbol._outputs)
    proxies = proxies or {}
    segments = _block_segments(nodes)
    # what each block hands on: the entries a later node or the symbol's
    # outputs read (every entry under ``collect_all``)
    produced_in = {id(n): k for k, (_, seg) in enumerate(segments)
                   for n in seg}
    handed_on = [set() for _ in segments]
    for k, (_, seg) in enumerate(segments):
        for node in seg:
            for p, i in node.inputs:
                if produced_in.get(id(p), k) != k:
                    handed_on[produced_in[id(p)]].add((id(p), i))
    for n, i in out_entries:
        if id(n) in produced_in:
            handed_on[produced_in[id(n)]].add((id(n), i))

    def run_nodes(seg, values, arg_vals, rng, is_train, aux_updates):
        for node in seg:
            ins = [values[(id(p), i)] for p, i in node.inputs]
            out = _call_node(node, ins, rng, rng_index, is_train)
            pname = proxies.get(id(node))
            if pname is not None and pname in arg_vals:
                out = (out[0] + arg_vals[pname],) + out[1:]
            for i, o in enumerate(out):
                values[(id(node), i)] = o
            if is_train and node.op.aux_update:
                for out_idx, in_idx in node.op.aux_update.items():
                    if in_idx < len(node.inputs):
                        p, _ = node.inputs[in_idx]
                        if p.is_variable and id(p) in aux_ids:
                            aux_updates[p.name] = out[out_idx]

    # what each block reads from outside itself, in a fixed order
    reads_of = []
    for _, seg in segments:
        inside = {id(n) for n in seg}
        reads_of.append(sorted({(id(p), i) for n in seg for p, i in n.inputs
                                if id(p) not in inside}))
    keeps_of = [sorted(entries) for entries in handed_on]

    def run_block(k, values, arg_vals, rng, is_train, aux_updates):
        """One named block: a function of the entries it reads from outside
        to the entries it hands on, so that ``jax.checkpoint`` has a
        boundary to keep."""
        tag, seg = segments[k]
        reads, keeps = reads_of[k], keeps_of[k]
        used = [n for n in (proxies.get(id(node)) for node in seg)
                if n is not None and n in arg_vals]

        def block(read_vals, proxy_vals, key):
            local = dict(zip(reads, read_vals))
            ups = {}
            with jax.named_scope(tag):
                run_nodes(seg, local, proxy_vals, key, is_train, ups)
            return [local[e] for e in keeps], ups

        if remat_blocks and is_train:
            block = jax.checkpoint(block, policy=_keep_named_residuals)
        kept, ups = block([values[e] for e in reads],
                          {n: arg_vals[n] for n in used}, rng)
        values.update(zip(keeps, kept))
        aux_updates.update(ups)

    def eval_fn(arg_vals: Dict, aux_vals: Dict, rng, is_train: bool):
        values = {}
        aux_updates = {}
        for node in nodes:
            if node.is_variable:
                values[(id(node), 0)] = (aux_vals if id(node) in aux_ids
                                         else arg_vals)[node.name]
        for k, (tag, seg) in enumerate(segments):
            if tag is None or collect_all:
                run_nodes(seg, values, arg_vals, rng, is_train, aux_updates)
            else:
                run_block(k, values, arg_vals, rng, is_train, aux_updates)
        if collect_all:
            outputs = [values[(id(n), i)] for n in nodes
                       if not n.is_variable for i in range(n.num_outputs())]
        else:
            outputs = [values[(id(n), i)] for n, i in out_entries]
        return outputs, aux_updates

    eval_fn.needs_rng = bool(random_nodes)
    return eval_fn


def build_placed_graph_eval(symbol, group2dev):
    """Device-placed eval for ctx_group model parallelism.

    Reference analogue: nnvm::pass::PlaceDevice + ``_CrossDeviceCopy``
    insertion (graph_executor.cc:386-398) driven by ``__ctx_group__``
    attrs, with the engine overlapping stages. Here: nodes are assigned
    devices (explicit ``ctx_group`` wins, otherwise inherited from the
    first placed input), contiguous same-device runs are jit-compiled
    onto their device, boundary values are ``jax.device_put`` transfers,
    and jax's async dispatch provides the cross-stage overlap.

    Returns eval_fn with the same signature/contract as
    :func:`build_graph_eval`; outputs stay on their producing devices.
    """
    nodes = symbol._topo_nodes()
    aux_ids = symbol._aux_node_ids()
    random_nodes = [n for n in nodes
                    if n.op is not None and n.op.uses_rng(n.attrs)]
    rng_index = {id(n): i for i, n in enumerate(random_nodes)}
    out_entries = list(symbol._outputs)
    default_dev = next(iter(group2dev.values()))

    # -- PlaceDevice: explicit group attr, else inherit from first input --
    dev_of = {}
    for node in nodes:
        if node.is_variable:
            continue
        grp = node.scope_attrs.get("ctx_group")
        dev = group2dev.get(grp) if grp is not None else None
        if dev is None:
            for parent, _ in node.inputs:
                if id(parent) in dev_of:
                    dev = dev_of[id(parent)]
                    break
        dev_of[id(node)] = dev or default_dev
    var_dev = {}
    for node in nodes:
        if node.is_variable:
            grp = node.scope_attrs.get("ctx_group")
            if grp is not None and grp in group2dev:
                var_dev[id(node)] = group2dev[grp]
    for node in nodes:
        if node.is_variable:
            continue
        for parent, _ in node.inputs:
            if parent.is_variable and id(parent) not in var_dev:
                var_dev[id(parent)] = dev_of[id(node)]

    # -- segment contiguous same-device op runs (bulk-exec analog) --------
    segments = []  # (device, [nodes])
    for node in nodes:
        if node.is_variable:
            continue
        dev = dev_of[id(node)]
        if segments and segments[-1][0] is dev:
            segments[-1][1].append(node)
        else:
            segments.append((dev, [node]))

    def _seg_io(seg_nodes):
        produced = {(id(n), i) for n in seg_nodes
                    for i in range(n.num_outputs())}
        needed = []
        for n in seg_nodes:
            for parent, i in n.inputs:
                key = (id(parent), i)
                if key not in produced and key not in needed:
                    needed.append(key)
        return produced, needed

    seg_meta = []
    all_later_needs = [set() for _ in segments]
    # keys each segment must export: used by later segments or final outputs
    for si, (dev, seg_nodes) in enumerate(segments):
        produced, needed = _seg_io(seg_nodes)
        for key in needed:
            for sj in range(si):
                if key in seg_meta[sj][0]:
                    all_later_needs[sj].add(key)
        seg_meta.append((produced, needed))
    final_keys = {(id(n), i) for n, i in out_entries}
    for si, (produced, _) in enumerate(seg_meta):
        all_later_needs[si] |= (produced & final_keys)

    compiled = []
    for si, (dev, seg_nodes) in enumerate(segments):
        produced, needed = seg_meta[si]
        exports = sorted(all_later_needs[si])

        def seg_fn(is_train, rng, in_vals, _seg_nodes=seg_nodes,
                   _needed=tuple(needed), _exports=tuple(exports)):
            values = dict(zip(_needed, in_vals))
            aux_updates = {}
            for node in _seg_nodes:
                ins = [values[(id(p), i)] for p, i in node.inputs]
                out = _call_node(node, ins, rng, rng_index, is_train)
                for i, o in enumerate(out):
                    values[(id(node), i)] = o
                if is_train and node.op.aux_update:
                    for out_idx, in_idx in node.op.aux_update.items():
                        if in_idx < len(node.inputs):
                            p, _ = node.inputs[in_idx]
                            if p.is_variable and id(p) in aux_ids:
                                aux_updates[p.name] = out[out_idx]
            return [values[k] for k in _exports], aux_updates

        # one wrapper per device segment, built once per bind and cached
        # in `compiled` for the executor's lifetime — not a per-step loop
        compiled.append((dev, jax.jit(seg_fn, static_argnums=(0,)),  # tpu-lint: disable=retrace-amplification
                         tuple(needed), tuple(exports)))

    def eval_fn(arg_vals: Dict, aux_vals: Dict, rng, is_train: bool):
        values = {}
        for node in nodes:
            if not node.is_variable:
                continue
            src = (aux_vals if id(node) in aux_ids else arg_vals)[node.name]
            dev = var_dev.get(id(node), default_dev)
            values[(id(node), 0)] = jax.device_put(src, dev)
        aux_updates = {}
        for dev, seg_jit, needed, exports in compiled:
            # _CrossDeviceCopy: move boundary values onto this segment's
            # device (no-op when already there)
            in_vals = [jax.device_put(values[k], dev) for k in needed]
            seg_rng = jax.device_put(rng, dev)
            outs, aux_up = seg_jit(bool(is_train), seg_rng, in_vals)
            values.update(zip(exports, outs))
            aux_updates.update(aux_up)
        outputs = [values[(id(n), i)] for n, i in out_entries]
        return outputs, aux_updates

    eval_fn.needs_rng = bool(random_nodes)
    return eval_fn


_NULL_KEY = None

_PROGRAMS = None


def _program_registry():
    """Process-wide fingerprint-keyed registry of executor programs
    (compiler.aot.ProgramRegistry): two executors over structurally
    identical graphs share ONE pair of traced fwd/fwd_bwd callables —
    the replacement for the old ``shared_exec._symbol is symbol``
    staleness rule, which only ever shared through an explicitly
    threaded executor and silently retraced for equal graphs built
    twice."""
    global _PROGRAMS
    if _PROGRAMS is None:
        from .compiler.aot import ProgramRegistry
        _PROGRAMS = ProgramRegistry()
    return _PROGRAMS


def _null_key():
    """Cached PRNG key fed to executors whose graph samples nothing: the
    per-bind/per-step key-split subgraph (a device dispatch + a host
    round-trip through the key chain) is skipped for pure-deterministic
    graphs — it showed up as copy/layout ms in the r5 profile."""
    global _NULL_KEY
    if _NULL_KEY is None:
        with jax.ensure_compile_time_eval():
            _NULL_KEY = jax.random.PRNGKey(0)
    return _NULL_KEY


def _sparse_grad_specs(symbol, grad_req):
    """Embedding nodes whose weight gradient stays row_sparse.

    Conditions (reference: the sparse-embedding FComputeEx path): the op
    carries ``sparse_grad=True``, its weight is a trainable variable and
    its indices input is a graph input variable. grad_req='add' is
    rejected like the reference rejects kAddTo for sparse outputs.
    """
    nodes = symbol._topo_nodes()
    consumers = {}  # variable id -> number of consuming input slots
    for n in nodes:
        if n.is_variable:
            continue
        for p, _ in n.inputs:
            if p.is_variable:
                consumers[id(p)] = consumers.get(id(p), 0) + 1
    specs = []
    for node in nodes:
        if node.is_variable or node.op.name != "Embedding":
            continue
        if not node.attrs.get("sparse_grad"):
            continue
        data_p, w_p = node.inputs[0][0], node.inputs[1][0]
        if not (w_p.is_variable and data_p.is_variable):
            continue
        if consumers.get(id(w_p), 0) != 1:
            # tied weights (lm head, second embedding, ...): the proxy
            # would capture only this node's contribution — fall back to
            # the ordinary dense gradient, which is always correct
            continue
        req = grad_req.get(w_p.name, "null")
        if req == "null":
            continue
        if req == "add":
            raise MXNetError(
                "grad_req='add' is not supported for sparse_grad "
                "Embedding weights (reference: kAddTo unsupported for "
                "sparse outputs)")
        specs.append({"nid": id(node), "w": w_p.name, "d": data_p.name,
                      "dim": int(node.attrs["output_dim"]),
                      "proxy": f"_sgproxy{len(specs)}"})
    return specs


class Executor:
    """A bound executor over one symbol (reference: graph_executor.h:57-66)."""

    def __init__(self, symbol, ctx, args: Dict[str, NDArray],
                 grads: Dict[str, NDArray], grad_req: Dict[str, str],
                 aux: Dict[str, NDArray], shared_exec: Optional["Executor"] = None,
                 group2ctx=None, sparse_specs=None):
        self._symbol = symbol
        self._ctx = ctx
        self.arg_dict = args
        self.grad_dict = grads
        self.aux_dict = aux
        self._grad_req = grad_req
        self._arg_names = symbol.list_arguments()
        self._aux_names = symbol.list_auxiliary_states()
        self._output_names = symbol.list_outputs()
        self.outputs: List[NDArray] = []
        self._diff_args = [n for n in self._arg_names
                          if grad_req.get(n, "null") != "null"]
        # compiled-program sharing across executors happens through the
        # fingerprint-keyed registry below (reference: shared_exec
        # memory-pool reuse for bucketing, graph_executor.cc:879-881 —
        # ``shared_exec`` still shares BUFFERS in simple_bind; programs
        # are shared whenever the graph fingerprints match, no explicit
        # threading required)
        self._needs_rng = any(
            n.op is not None and not n.is_variable
            and n.op.uses_rng(n.attrs) for n in symbol._topo_nodes())
        if shared_exec is not None and shared_exec._symbol is symbol \
                and getattr(shared_exec, "_placed", False):
            # placed executors keep the identity-based share (the
            # fingerprint registry below covers only the jitted
            # single-device path): reshape()/bucketing over a ctx_group
            # graph must reuse the per-group segment jits. Checked
            # before _is_placed because reshape() does not re-thread
            # group2ctx — the shared executor's placement carries over.
            self._placed = True
            self._fwd = shared_exec._fwd
            self._fwd_bwd = shared_exec._fwd_bwd
            self._sparse_specs = shared_exec._sparse_specs
            self._last = None
            return
        if _is_placed(group2ctx):
            # ctx_group model parallelism: per-group device placement with
            # internally jitted segments; no outer jit (it would collapse
            # everything back onto one device). The segment jits are built
            # per ambient mesh: mesh-aware ops resolve the mesh at trace
            # time, so a mesh change must produce fresh segment programs
            # (same staleness rule as the single-device jit cache).
            placed_devs = _resolve_group_devs(group2ctx)
            placed_evals = {}

            def _placed_eval(mesh_key):
                fn = placed_evals.get(mesh_key)
                if fn is None:
                    fn = build_placed_graph_eval(symbol, placed_devs)
                    placed_evals[mesh_key] = fn
                return fn

            def fwd_placed(arg_vals, aux_vals, rng, is_train, mesh_key=None):
                return _placed_eval(mesh_key)(arg_vals, aux_vals, rng,
                                              is_train)

            def fwd_bwd_placed(arg_vals, aux_vals, rng, head_grads,
                               diff_names, mesh_key=None):
                eval_fn = _placed_eval(mesh_key)
                diff = {n: arg_vals[n] for n in diff_names}

                def f(diff_args):
                    merged = dict(arg_vals)
                    merged.update(diff_args)
                    return eval_fn(merged, aux_vals, rng, True)

                if getenv("MXTPU_BACKWARD_DO_MIRROR", 0, int):
                    # same remat knob as the single-device path — most
                    # relevant here, where the model already didn't fit
                    f = jax.checkpoint(f)
                (outs, aux_up), vjp_fn = jax.vjp(f, diff)
                cts = [hg if hg is not None else jnp.ones_like(o)
                       for o, hg in zip(outs, head_grads)]
                zero_aux = jax.tree_util.tree_map(jnp.zeros_like, aux_up)
                (grads,) = vjp_fn((cts, zero_aux))
                return outs, aux_up, grads, {}

            self._sparse_specs = []  # placed path: dense gradients only
            self._placed = True
            self._fwd = fwd_placed
            self._fwd_bwd = fwd_bwd_placed
            self._last = None
            return
        else:
            if shared_exec is not None and shared_exec._symbol is symbol \
                    and getattr(shared_exec, "_psig", None) is not None:
                # identity memoization over the fingerprint route: the
                # SAME symbol object (reshape(), bucketing partial
                # batches) has by definition the same fingerprint, so
                # re-running the pass pipeline and re-serializing the
                # canonical graph would only rediscover it. Programs are
                # shared directly when the grad-req-derived sparse-proxy
                # signature also matches; any mismatch falls through to
                # the full (registry) path.
                specs = (sparse_specs if sparse_specs is not None
                         else _sparse_grad_specs(symbol, grad_req))
                psig = tuple((s["w"], s["d"], s["dim"]) for s in specs)
                if psig == shared_exec._psig:
                    self._sparse_specs = shared_exec._sparse_specs
                    self._psig = psig
                    self.graph_fingerprint = shared_exec.graph_fingerprint
                    self._fwd = shared_exec._fwd
                    self._fwd_bwd = shared_exec._fwd_bwd
                    self._last = None
                    return
            # the compiler layer runs here: graph passes at bind time,
            # then fingerprint-keyed program sharing + the persistent
            # executable cache (mxnet_tpu/compiler, docs/how_to/compiler.md)
            from . import compiler as _compiler
            all_arrs = list(args.items()) + list(aux.items())
            opt_res = _compiler.optimize(
                symbol,
                input_shapes={n: tuple(v.shape) for n, v in all_arrs},
                input_dtypes={n: str(v.dtype) for n, v in all_arrs},
                for_training=any(r != "null" for r in grad_req.values()),
                mesh_key=_ambient_mesh_key())
            opt_sym = opt_res.symbol
            if opt_res.changed or sparse_specs is None:
                # a rewriting pass invalidates precomputed node ids (and
                # can change variable consumer counts): recompute on the
                # graph that is actually traced
                sparse_specs = _sparse_grad_specs(opt_sym, grad_req)
            self._sparse_specs = specs = sparse_specs
            remat = bool(opt_res.remat
                         or getenv("MXTPU_BACKWARD_DO_MIRROR", 0, int))
            fp = _compiler.graph_fingerprint(opt_sym)
            self.graph_fingerprint = fp
            psig = tuple((s["w"], s["d"], s["dim"]) for s in specs)
            self._psig = psig
            eager = bool(getenv("MXTPU_EXEC_EAGER", 0, int))

            def _build_programs():
                eval_fn = build_graph_eval(
                    opt_sym, proxies={s["nid"]: s["proxy"] for s in specs})

                # mesh_key is a pure cache key: mesh-aware ops (attention
                # seq_axis) consult the ambient mesh at TRACE time, so the
                # compiled program must be keyed on it — otherwise a program
                # first traced outside mesh_scope would silently keep running
                # unsharded under a later mesh (and vice versa)
                def fwd(arg_vals, aux_vals, rng, is_train, mesh_key=None):
                    outs, aux_up = eval_fn(arg_vals, aux_vals, rng, is_train)
                    return outs, aux_up

                def fwd_bwd(arg_vals, aux_vals, rng, head_grads, diff_names,
                            mesh_key=None):
                    # diff_names is static: each executor passes its own
                    # grad_req selection even when the program is shared
                    diff = {n: arg_vals[n] for n in diff_names}
                    # zero proxies on each sparse-grad Embedding output: the
                    # vjp cotangent w.r.t. a proxy is d(emb_out), from which
                    # the row_sparse weight grad is assembled host-side
                    # without ever materializing the dense (vocab, dim) grad
                    proxy_vals = {
                        s["proxy"]: jnp.zeros(
                            tuple(arg_vals[s["d"]].shape) + (s["dim"],),
                            arg_vals[s["w"]].dtype)
                        for s in specs}

                    def f(diff_args, proxy_args):
                        merged = dict(arg_vals)
                        merged.update(diff_args)
                        merged.update(proxy_args)
                        outs, aux_up = eval_fn(merged, aux_vals, rng, True)
                        return outs, aux_up

                    if remat:
                        # trade FLOPs for memory: recompute activations in
                        # the backward pass (the remat-policy pass decision,
                        # or the explicit MXNET_BACKWARD_DO_MIRROR knob —
                        # reference memonger; here XLA rematerialization)
                        f = jax.checkpoint(f)
                    (outs, aux_up), vjp_fn = jax.vjp(f, diff, proxy_vals)
                    cts = [hg if hg is not None else jnp.ones_like(o)
                           for o, hg in zip(outs, head_grads)]
                    zero_aux = jax.tree_util.tree_map(jnp.zeros_like, aux_up)
                    grads, proxy_grads = vjp_fn((cts, zero_aux))
                    return outs, aux_up, grads, proxy_grads

                if eager:
                    # debugging mode: run un-jitted, op by op (reference
                    # MXNET_ENGINE_TYPE=NaiveEngine — engine.cc:31-41)
                    return fwd, fwd_bwd
                # the EFFECTIVE remat flag, not transform_sig's: with
                # MXTPU_GRAPH_PASSES=0 the sig is frozen at remat=0
                # while MXNET_BACKWARD_DO_MIRROR can still flip the
                # traced program — the persisted key must split on it
                key_parts = (fp, opt_res.transform_sig,
                             f"effremat={int(remat)}", f"sparse={psig}")
                return (_compiler.PersistentJit(
                            fwd, kind="executor-fwd", key_parts=key_parts,
                            static_argnums=(3, 4)),
                        _compiler.PersistentJit(
                            fwd_bwd, kind="executor-fwd-bwd",
                            key_parts=key_parts, static_argnums=(4, 5)))

            if eager:
                self._fwd, self._fwd_bwd = _build_programs()
            else:
                self._fwd, self._fwd_bwd = _program_registry().get_or_build(
                    (fp, psig, remat), _build_programs)
        self._last = None  # (arg_vals, aux_vals, rng) of the last forward

    # -- API ----------------------------------------------------------------
    @property
    def arg_arrays(self):
        return [self.arg_dict[n] for n in self._arg_names]

    @property
    def grad_arrays(self):
        return [self.grad_dict.get(n) for n in self._arg_names]

    @property
    def aux_arrays(self):
        return [self.aux_dict[n] for n in self._aux_names]

    @property
    def output_dict(self):
        return dict(zip(self._output_names, self.outputs))

    def _arg_val(self, name):
        """Value handed to the traced graph: dense jax array, or a BCOO
        pytree for CSR arguments (symbolic sparse execution — the csr
        never densifies; ops like ``dot`` dispatch on BCOO)."""
        v = self.arg_dict[name]
        from .ndarray.sparse import CSRNDArray
        if isinstance(v, CSRNDArray):
            return v._to_bcoo()
        return v._data

    def forward(self, is_train=False, **kwargs):
        for name, val in kwargs.items():
            if name not in self.arg_dict:
                raise MXNetError(f"unknown argument {name}")
            self.arg_dict[name]._set_data(
                _as_jax(val, dtype=self.arg_dict[name].dtype))
        arg_vals = {n: self._arg_val(n) for n in self._arg_names}
        aux_vals = {n: self.aux_dict[n]._data for n in self._aux_names}
        # deterministic graphs skip the per-step key split (and leave the
        # global key chain untouched — they draw nothing from it)
        rng = _random.next_key() if self._needs_rng else _null_key()
        from . import profiler as _profiler
        with _profiler.span("Forward", cat="executor", kind="symbolic"):
            outs, aux_up = self._fwd(arg_vals, aux_vals, rng, bool(is_train),
                                     _ambient_mesh_key())
        if is_train:
            for name, val in aux_up.items():
                self.aux_dict[name]._set_data(val)
        self.outputs = [NDArray(o) for o in outs]
        self._last = (arg_vals, aux_vals, rng, bool(is_train))
        return self.outputs

    def backward(self, out_grads=None):
        """Gradient pass. Recomputes forward inside the compiled vjp program
        (XLA CSEs shared subexpressions); Module's fused step avoids the
        double work by calling forward_backward."""
        if self._last is None:
            raise MXNetError("backward called before forward")
        self._run_fwd_bwd(*self._last[:3], out_grads)

    def forward_backward(self, out_grads=None, **kwargs):
        for name, val in kwargs.items():
            self.arg_dict[name]._set_data(
                _as_jax(val, dtype=self.arg_dict[name].dtype))
        arg_vals = {n: self._arg_val(n) for n in self._arg_names}
        aux_vals = {n: self.aux_dict[n]._data for n in self._aux_names}
        rng = _random.next_key() if self._needs_rng else _null_key()
        self._run_fwd_bwd(arg_vals, aux_vals, rng, out_grads)
        return self.outputs

    def _run_fwd_bwd(self, arg_vals, aux_vals, rng, out_grads):
        if out_grads is None:
            head_grads = [None] * len(self._output_names)
        else:
            if isinstance(out_grads, NDArray):
                out_grads = [out_grads]
            head_grads = [g._data if g is not None else None for g in out_grads]
        sparse_w = {s["w"] for s in self._sparse_specs}
        dense_diff = tuple(n for n in self._diff_args if n not in sparse_w)
        from . import profiler as _profiler
        with _profiler.span("ForwardBackward", cat="executor",
                            kind="symbolic"):
            outs, aux_up, grads, proxy_grads = self._fwd_bwd(
                arg_vals, aux_vals, rng, head_grads, dense_diff,
                _ambient_mesh_key())
        self._last = (arg_vals, aux_vals, rng, True)
        self.outputs = [NDArray(o) for o in outs]
        for name, val in aux_up.items():
            self.aux_dict[name]._set_data(val)
        for name in dense_diff:
            g = grads[name]
            buf = self.grad_dict.get(name)
            if buf is None:
                continue
            if self._grad_req.get(name) == "add":
                buf._set_data(buf._data + g)
            else:
                buf._set_data(g)
        if self._sparse_specs:
            self._store_sparse_grads(arg_vals, proxy_grads)

    def _store_sparse_grads(self, arg_vals, proxy_grads):
        """Assemble row_sparse weight grads from the proxy cotangents.

        d(emb_out) is (batch..., dim); the rsp grad holds one row per
        *unique* index with duplicate contributions summed (reference:
        the sparse embedding backward's unique+sum kernel). The dense
        (vocab, dim) gradient is never allocated.

        The result is written THROUGH the array the caller bound via
        ``args_grad`` (reference bind contract: gradients land in the
        caller's NDArrays, c_api callers read them via their own handle):
        a bound RowSparseNDArray has its components swapped in place, a
        bound dense array gets the scattered rows. Only when no grad
        array was bound do we publish a fresh rsp array under the name.
        """
        import numpy as np

        from .ndarray.sparse import RowSparseNDArray

        for s in self._sparse_specs:
            idx = np.asarray(
                jax.device_get(arg_vals[s["d"]])).astype(np.int64).ravel()
            g = np.asarray(jax.device_get(proxy_grads[s["proxy"]]))
            g = g.reshape(idx.size, -1)
            rows, inv = np.unique(idx, return_inverse=True)
            vals = np.zeros((rows.size, g.shape[1]), g.dtype)
            np.add.at(vals, inv, g)
            w_shape = tuple(self.arg_dict[s["w"]].shape)
            bound = self.grad_dict.get(s["w"])
            if isinstance(bound, RowSparseNDArray):
                bound._replace_components(vals, rows)
            elif bound is not None:
                bound._set_data(
                    jnp.zeros(w_shape, bound.dtype).at[rows].add(vals))
            else:
                self.grad_dict[s["w"]] = RowSparseNDArray(
                    vals, rows, w_shape)

    def internal_outputs(self):
        """Evaluate and return {entry_name: NDArray} for EVERY op output in
        the graph, using the last forward's inputs.

        Reference analogue: MXExecutorSetMonitorCallback firing the monitor
        per op output (src/c_api/c_api_executor.cc); here the internals are
        produced by one extra jitted evaluation (XLA shares subexpressions
        with nothing — it is a debugging path, run on demand by Monitor)."""
        if self._last is None:
            raise MXNetError("internal_outputs called before forward")
        if not hasattr(self, "_internals_fn"):
            nodes = self._symbol._topo_nodes()
            names = []
            for node in nodes:
                if node.is_variable:
                    continue
                for i in range(node.num_outputs()):
                    if node.num_outputs() == 1:
                        names.append(f"{node.name}_output")
                    else:
                        out_name = (node.op.output_names[i]
                                    if i < len(node.op.output_names)
                                    else str(i))
                        names.append(f"{node.name}_{out_name}")
            raw_eval = build_graph_eval(self._symbol, collect_all=True)

            def internals_eval(arg_vals, aux_vals, rng, is_train,
                               mesh_key=None):
                return raw_eval(arg_vals, aux_vals, rng, is_train)

            self._internals_fn = jax.jit(internals_eval,
                                         static_argnums=(3, 4))
            self._internals_names = names
        arg_vals, aux_vals, rng, is_train = self._last
        # same rng + same is_train as the real pass: dropout masks and BN
        # mode match what actually executed
        vals, _ = self._internals_fn(arg_vals, aux_vals, rng, is_train,
                                     _ambient_mesh_key())
        return {n: NDArray(v) for n, v in zip(self._internals_names, vals)}

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Return an executor for new input shapes. Compilation is cached by
        XLA per shape signature (reference: GraphExecutor::Reshape)."""
        from .ndarray import zeros as nd_zeros

        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**kwargs)
        new_args = {}
        for name, shape in zip(self._arg_names, arg_shapes):
            old = self.arg_dict[name]
            new_args[name] = (old if tuple(old.shape) == tuple(shape)
                              else nd_zeros(shape, dtype=str(old.dtype)))
        new_aux = {}
        for name, shape in zip(self._aux_names, aux_shapes):
            old = self.aux_dict[name]
            new_aux[name] = (old if tuple(old.shape) == tuple(shape)
                             else nd_zeros(shape, dtype=str(old.dtype)))
        from .ndarray import sparse as _sparse
        from .ndarray.sparse import RowSparseNDArray as _Rsp
        grads = {}
        for n, old_g in self.grad_dict.items():
            if isinstance(old_g, _Rsp):
                grads[n] = _sparse.zeros("row_sparse",
                                         tuple(new_args[n].shape),
                                         dtype=str(old_g.dtype))
            else:
                grads[n] = nd_zeros(new_args[n].shape,
                                    dtype=str(new_args[n].dtype))
        return Executor(self._symbol, self._ctx, new_args, grads,
                        self._grad_req, new_aux, shared_exec=self)

    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        for name, val in (arg_params or {}).items():
            if name in self.arg_dict:
                self.arg_dict[name]._set_data(
                    _as_jax(val, dtype=self.arg_dict[name].dtype))
            elif not allow_extra_params:
                raise MXNetError(f"unknown argument {name}")
        for name, val in (aux_params or {}).items():
            if name in self.aux_dict:
                self.aux_dict[name]._set_data(
                    _as_jax(val, dtype=self.aux_dict[name].dtype))
            elif not allow_extra_params:
                raise MXNetError(f"unknown aux state {name}")

    def debug_str(self):
        return self._symbol.debug_str()
