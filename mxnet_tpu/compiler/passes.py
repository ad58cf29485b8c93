"""The graph-pass framework: small, ordered rewrites over :class:`GraphIR`.

Reference analogue: the NNVM pass pipeline the original stack ran
between symbol composition and execution (``nnvm::ApplyPass`` —
Gradient/PlaceDevice/PlanMemory), rebuilt in the shape TVM (arxiv
1802.04799) and Relay (arxiv 1810.00952) standardized: a ``Pass`` maps
an IR to an IR, a ``PassManager`` schedules passes by declared
dependencies, and every pass records what it changed. Passes run at
bind time — ``Executor``/``FusedStep``/``SPMDTrainer`` construction —
so Module, Gluon, SPMD and the serving backends inherit them through
the seams they already use.

Shipped passes:

* **dead-op-elimination** — prune nodes unreachable from the requested
  outputs. A well-formed Symbol is reachability-defined so this finds
  nothing on its own; it is the cleanup guarantee for rewriting passes
  (CSE below, the quantization/sharding rewrites that will live in the
  ``annotate`` slot) whose rewires orphan nodes.
* **cse** — common-subexpression elimination: two ops with the same
  registered op, canonicalized attrs, scope attrs and input entries
  compute the same value; the later one is replaced by the first.
  Sampling ops (``uses_rng``/``needs_rng`` — two Dropouts draw
  *different* masks by design) and aux-updating ops (BatchNorm running
  stats) never merge.
* **remat-policy** — the memory-vs-recompute decision
  (``jax.checkpoint`` over the backward), fed by the per-op costs the
  profiling harness collects (``MXTPU_OP_COSTS`` json, the
  ``benchmarks/profile_lstm.py``/``profile_resnet.py`` output) and an
  activation-memory budget (``MXTPU_REMAT_MB``). Decision only — the
  executor/fused-step honor ``annotations['remat']`` when tracing.
* **annotate** — the no-op-safe extension slot: external providers
  (sharding specs for the pod-scale work, quantization rewrites)
  register annotator callbacks; with none registered the pass is a
  no-op. See docs/how_to/compiler.md.

Pass transforms are value-preserving by contract: the pass-correctness
suite (tests/test_compiler.py) asserts bitwise-identical step outputs
vs. the un-passed graph for Module, Gluon and SPMD programs.
``MXTPU_GRAPH_PASSES=0`` disables the whole pipeline.
"""
from __future__ import annotations

import json
import logging
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from ..base import MXNetError, getenv
from .ir import GraphIR, clone_node

__all__ = ["Pass", "PassContext", "PassManager", "OptimizeResult",
           "DeadOpElimination", "CommonSubexpressionElimination",
           "RematPolicy", "Annotate", "register_annotator",
           "default_pass_manager", "optimize", "pass_stats",
           "reset_pass_stats"]


class PassContext:
    """Bind-time facts a pass may consult.

    ``input_shapes``/``input_dtypes`` map every graph input (args + aux)
    known at bind; ``mesh_key`` is the ambient mesh identity (or None);
    ``op_costs`` maps op name -> measured ms per dispatch (the
    profile-harness feed); ``for_training`` distinguishes a training
    bind (remat relevant) from inference.
    """

    def __init__(self, input_shapes: Optional[Dict[str, tuple]] = None,
                 input_dtypes: Optional[Dict[str, str]] = None,
                 mesh_key=None, for_training: bool = True,
                 op_costs: Optional[Dict[str, float]] = None,
                 remat_budget_mb: Optional[float] = None):
        self.input_shapes = dict(input_shapes or {})
        self.input_dtypes = dict(input_dtypes or {})
        self.mesh_key = mesh_key
        self.for_training = bool(for_training)
        self.op_costs = op_costs if op_costs is not None else _env_op_costs()
        if remat_budget_mb is None:
            remat_budget_mb = getenv("MXTPU_REMAT_MB", None, float)
        self.remat_budget_mb = remat_budget_mb


_OP_COSTS_CACHE: Optional[Dict[str, float]] = None


def _env_op_costs() -> Dict[str, float]:
    """Per-op cost table from ``MXTPU_OP_COSTS`` (a json file mapping op
    name -> ms per dispatch, as the profile harness measures). Read once
    per process; unreadable/absent -> empty table."""
    global _OP_COSTS_CACHE
    if _OP_COSTS_CACHE is None:
        table: Dict[str, float] = {}
        path = getenv("MXTPU_OP_COSTS", None)
        if path:
            try:
                with open(path, "r", encoding="utf-8") as f:
                    raw = json.load(f)
                table = {str(k): float(v) for k, v in raw.items()}
            except (OSError, ValueError, TypeError) as err:
                logging.warning("MXTPU_OP_COSTS %r unreadable (%s); "
                                "remat policy falls back to byte "
                                "estimates only", path, err)
        _OP_COSTS_CACHE = table
    return _OP_COSTS_CACHE


class Pass:
    """One IR -> IR rewrite. Subclasses set ``name`` and ``requires``
    (names of passes that must run earlier) and implement :meth:`run`
    returning ``(ir, info)`` where ``info`` holds integer change
    counters (summed into :func:`pass_stats`)."""

    name: str = "pass"
    requires: Tuple[str, ...] = ()

    def run(self, ir: GraphIR, ctx: PassContext):
        raise NotImplementedError


class DeadOpElimination(Pass):
    name = "dead-op-elimination"

    def run(self, ir: GraphIR, ctx: PassContext):
        keep = ir.reachable_ids()
        removed = [n for n in ir.nodes if id(n) not in keep]
        if not removed:
            return ir, {"removed": 0}
        out = GraphIR([n for n in ir.nodes if id(n) in keep], ir.outputs)
        out.annotations = ir.annotations
        return out, {"removed": len(removed)}


def _canon_attrs(node) -> tuple:
    """Canonical, hashable attr form: the op's own serialization (stable
    strings) sorted by key, plus the scope attrs that change semantics
    (ctx_group placement, user annotations)."""
    if node.op is not None:
        ser = node.op.attr_spec.serialize(node.attrs)
    else:
        ser = {k: str(v) for k, v in node.attrs.items()}
    return (tuple(sorted(ser.items())),
            tuple(sorted(node.scope_attrs.items())))


class CommonSubexpressionElimination(Pass):
    name = "cse"
    requires = ("dead-op-elimination",)

    @staticmethod
    def _mergeable(node) -> bool:
        op = node.op
        if op is None:
            return False            # variables are identity by name
        if op.needs_rng or op.uses_rng(node.attrs):
            return False            # distinct nodes draw distinct keys
        if op.aux_update:
            return False            # running-stat writers stay distinct
        if op.stateful:
            # Custom-op invocations own per-invocation _op_state and may
            # run side-effecting user callbacks — merging halves their
            # firing count and breaks forward/backward state pairing
            return False
        if node.attrs.get("sparse_grad"):
            # merging identical sparse_grad Embeddings changes the
            # weight's consumer count, flipping _sparse_grad_specs'
            # tied-weight classification — under grad_req='add' that
            # turns a valid bind into the kAddTo rejection. The pass
            # pipeline must never make a bind fail; skip these nodes.
            return False
        return True

    def run(self, ir: GraphIR, ctx: PassContext):
        rep: Dict[int, object] = {}         # original node id -> representative
        seen: Dict[tuple, object] = {}      # structural key -> representative
        merged = 0
        for node in ir.nodes:
            if node.is_variable:
                rep[id(node)] = node
                continue
            new_inputs = [(rep[id(p)], i) for p, i in node.inputs]
            rewired = any(a is not b for (a, _), (b, _)
                          in zip(new_inputs, node.inputs))
            cand = clone_node(node, new_inputs) if rewired else node
            if self._mergeable(node):
                key = (node.op.name, _canon_attrs(node),
                       tuple((id(p), i) for p, i in new_inputs))
                hit = seen.get(key)
                if hit is not None:
                    rep[id(node)] = hit
                    merged += 1
                    continue
                seen[key] = cand
            rep[id(node)] = cand
        if not merged:
            return ir, {"merged": 0}
        from ..symbol.symbol import Symbol
        outputs = [(rep[id(n)], i) for n, i in ir.outputs]
        # rebuild the explicit node list from the rewired outputs
        # (topological, reachable-only); orphaned duplicates drop here
        out = GraphIR.from_symbol(Symbol(outputs))
        out.annotations = ir.annotations
        return out, {"merged": merged}


class RematPolicy(Pass):
    """Memory-vs-recompute decision for the backward pass.

    The decision (``annotations['remat']``) is taken when a training
    bind's estimated forward-activation footprint exceeds
    ``MXTPU_REMAT_MB`` — or unconditionally when the explicit
    ``MXTPU_BACKWARD_DO_MIRROR`` knob is set, preserving the pre-pass
    behavior. The per-op cost table (``ctx.op_costs``, measured by the
    profile harness) prices the recompute so the decision's estimated
    overhead is visible in the annotations instead of being a blind
    trade (the value-function angle of arxiv 2011.14486).
    """

    name = "remat-policy"
    requires = ("cse",)

    def run(self, ir: GraphIR, ctx: PassContext):
        ann = ir.annotations
        mirror = bool(getenv("MXTPU_BACKWARD_DO_MIRROR", 0, int))
        decision = mirror
        act_bytes = None
        if (not decision and ctx.for_training
                and ctx.remat_budget_mb is not None and ctx.input_shapes):
            act_bytes = self._activation_bytes(ir, ctx)
            if act_bytes is not None:
                decision = act_bytes > ctx.remat_budget_mb * (1 << 20)
        if decision and ctx.op_costs:
            recompute_ms = sum(ctx.op_costs.get(n.op.name, 0.0)
                               for n in ir.nodes if not n.is_variable)
            ann["remat_recompute_ms_est"] = round(recompute_ms, 3)
        if act_bytes is not None:
            ann["remat_activation_bytes_est"] = int(act_bytes)
        ann["remat"] = bool(decision)
        # recomputation by block: where the model asks for it
        # (``mx.AttrScope(__block__="layer3", __remat__="block")``) every
        # named block of a training bind becomes a jax.checkpoint boundary
        # (executor.build_graph_eval); the whole-graph decision above
        # keeps nothing a block's boundary would
        blocks = ctx.for_training and any(
            n.scope_attrs.get("__remat__") == "block"
            and n.scope_attrs.get("__block__")
            for n in ir.nodes if not n.is_variable)
        if blocks:
            ann["remat_blocks"] = True
        return ir, {"remat_on": int(bool(decision))}

    @staticmethod
    def _activation_bytes(ir: GraphIR, ctx: PassContext):
        # the memory model owns byte accounting now (compiler/memory.py);
        # this term — every non-variable output, all live at once — is
        # unchanged, so remat decisions are stable across the refactor
        from .memory import activation_bytes
        return activation_bytes(ir, ctx.input_shapes, ctx.input_dtypes)


_ANNOTATORS: List[Callable] = []


def register_annotator(fn: Callable) -> Callable:
    """Register ``fn(ir, ctx) -> dict | None`` to run in the ``annotate``
    slot. This is where the sharding-spec and quantization-rewrite
    layers plug in; with no annotators the slot is a no-op. Returns
    ``fn`` so it can be used as a decorator."""
    _ANNOTATORS.append(fn)
    return fn


class Annotate(Pass):
    """The extension slot: runs every registered annotator, merging the
    returned dicts into ``ir.annotations``. Safe no-op when nothing is
    registered."""

    name = "annotate"
    requires = ("remat-policy",)

    def run(self, ir: GraphIR, ctx: PassContext):
        applied = 0
        for fn in list(_ANNOTATORS):
            extra = fn(ir, ctx)
            if extra:
                ir.annotations.update(extra)
                applied += 1
        return ir, {"annotators": applied}


# ---------------------------------------------------------------------------
# scheduling + stats
# ---------------------------------------------------------------------------

_stats_lock = threading.Lock()
_pass_stats: Dict[str, Dict[str, float]] = {}


def pass_stats() -> Dict[str, Dict[str, float]]:
    """Per-pass counters (runs, summed change counts, total ms)."""
    with _stats_lock:
        return {k: dict(v) for k, v in _pass_stats.items()}


def reset_pass_stats():
    with _stats_lock:
        _pass_stats.clear()


def _record(name: str, info: Dict[str, int], ms: float):
    with _stats_lock:
        rec = _pass_stats.setdefault(name, {"runs": 0, "ms": 0.0})
        rec["runs"] += 1
        rec["ms"] = round(rec["ms"] + ms, 3)
        for k, v in info.items():
            rec[k] = rec.get(k, 0) + v


class PassManager:
    """Orders passes by declared ``requires`` and runs them in sequence.

    Registration order is preserved among independent passes; a
    ``requires`` edge always wins. An unknown requirement or a cycle is
    a configuration error raised at schedule time, not silently
    reordered."""

    def __init__(self, passes: Optional[Sequence[Pass]] = None):
        self._passes: List[Pass] = list(passes or [])

    def register(self, p: Pass) -> "PassManager":
        self._passes.append(p)
        return self

    def schedule(self) -> List[Pass]:
        by_name = {p.name: p for p in self._passes}
        order: List[Pass] = []
        state: Dict[str, int] = {}      # 0 visiting, 1 done

        def visit(p: Pass, chain):
            st = state.get(p.name)
            if st == 1:
                return
            if st == 0:
                raise MXNetError(
                    f"pass dependency cycle: {' -> '.join(chain + [p.name])}")
            state[p.name] = 0
            for req in p.requires:
                dep = by_name.get(req)
                if dep is None:
                    raise MXNetError(
                        f"pass {p.name!r} requires unknown pass {req!r}")
                visit(dep, chain + [p.name])
            state[p.name] = 1
            order.append(p)

        for p in self._passes:
            visit(p, [])
        return order

    def run(self, ir: GraphIR, ctx: PassContext) -> GraphIR:
        for p in self.schedule():
            t0 = time.perf_counter()
            ir, info = p.run(ir, ctx)
            _record(p.name, info, (time.perf_counter() - t0) * 1e3)
        return ir


_DEFAULT_MANAGER: Optional[PassManager] = None


def default_pass_manager() -> PassManager:
    """The process-wide default pipeline: DCE -> CSE -> remat-policy ->
    annotate."""
    global _DEFAULT_MANAGER
    if _DEFAULT_MANAGER is None:
        _DEFAULT_MANAGER = PassManager([
            DeadOpElimination(), CommonSubexpressionElimination(),
            RematPolicy(), Annotate()])
    return _DEFAULT_MANAGER


class OptimizeResult:
    """What :func:`optimize` hands back to a bind site.

    ``symbol`` is the (possibly rewritten) graph to trace — the ORIGINAL
    object when no pass changed anything, so identity-based caches stay
    valid. ``annotations`` carries pass decisions; ``transform_sig`` is
    the stable string of trace-affecting decisions that joins the
    persistent program fingerprint (a remat flip is a different
    executable)."""

    def __init__(self, symbol, annotations: Dict[str, object],
                 changed: bool):
        self.symbol = symbol
        self.annotations = dict(annotations)
        self.changed = bool(changed)

    @property
    def remat(self) -> bool:
        return bool(self.annotations.get("remat"))

    @property
    def remat_blocks(self) -> bool:
        return bool(self.annotations.get("remat_blocks"))

    @property
    def transform_sig(self) -> str:
        sig = f"passes={int(self.changed)};remat={int(self.remat)}"
        if self.remat_blocks:
            sig += ";rematblocks=1"
        # the sharding annotator (parallel/sharding.py) stamps the plan
        # signature so program keys built from this sig can never serve
        # an executable compiled for a different layout/ZeRO mode
        shard = self.annotations.get("sharding_sig")
        if shard:
            sig += f";shard={shard}"
        # the quant annotator (quant/core.py) stamps the quantization
        # decision the same way: a precision change (int8 <-> fp32,
        # format, gated parameter set) is a different executable
        quant = self.annotations.get("quant_sig")
        if quant:
            sig += f";quant={quant}"
        return sig


def optimize(symbol, input_shapes=None, input_dtypes=None,
             for_training: bool = True, mesh_key=None,
             manager: Optional[PassManager] = None) -> OptimizeResult:
    """Run the pass pipeline over ``symbol`` at bind time.

    Returns an :class:`OptimizeResult`; with ``MXTPU_GRAPH_PASSES=0``
    (the kill switch) the original symbol comes back untouched with
    empty annotations. A pass failure is never fatal to a bind: the
    error is logged and the un-passed graph is used — the compiler
    layer must only ever make programs better, not make binds fail.
    """
    if not getenv("MXTPU_GRAPH_PASSES", 1, int):
        return OptimizeResult(symbol, {}, False)
    mgr = manager or default_pass_manager()
    ctx = PassContext(input_shapes=input_shapes, input_dtypes=input_dtypes,
                      mesh_key=mesh_key, for_training=for_training)
    ir = GraphIR.from_symbol(symbol)
    try:
        out = mgr.run(ir, ctx)
    except MXNetError:
        raise                       # scheduling errors are configuration bugs
    except Exception as err:        # noqa: BLE001 — bind must survive
        logging.warning("graph-pass pipeline failed (%s: %s); binding the "
                        "un-passed graph", type(err).__name__, err)
        return OptimizeResult(symbol, {}, False)
    # a structural pass hands back a NEW GraphIR only when it changed
    # something; annotation-only passes return the ir they were given
    changed = out is not ir
    opt_symbol = out.to_symbol() if changed else symbol
    return OptimizeResult(opt_symbol, out.annotations, changed)
