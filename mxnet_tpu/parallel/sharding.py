"""The partition-rule engine: placement as data, for every trainer.

Reference analogue: the *implicit* placement rules of the reference —
parameters replicated per device (executor_group.py), batch split along
axis 0 (``_split_input_slice``), the dist server's key-sharded optimizer
update (kvstore_dist_server.h:175-186). Here placement is an explicit,
inspectable artifact: an ordered list of ``(regex, PartitionSpec)``
rules (the GSPMD/pjit ``match_partition_rules`` idiom) is resolved
against parameter names into ``PartitionSpec`` pytrees covering params,
grads, and per-slot optimizer state, and the XLA SPMD partitioner
inserts the collectives the reference's Comm/ps-lite layers performed by
hand.

Three layers:

* rule primitives — :func:`param_pspec` (the default Megatron-style
  tensor-parallel rule), :func:`batch_pspec`, :func:`match_partition_rules`
  over ordered regex rules (first match wins, scalars stay replicated,
  non-divisible dims fall back to replicated per-dim via
  :func:`fit_spec_to_shape`), with ``MXTPU_PARTITION_RULES`` supplying
  rule lists from the environment (:func:`rules_from_env`).
* :class:`ShardingPlan` — the resolved engine for one (mesh, rules,
  ZeRO-mode) triple: param/grad/state/batch specs, the stable
  :meth:`~ShardingPlan.signature` that joins program-cache keys, and the
  ZeRO-1 mode of arxiv 2004.13336 ("Automatic Cross-Replica Sharding of
  Weight Update in Data-Parallel Training"): optimizer state and the
  update computation sharded over the ``data`` axis
  (:func:`zero_shard_spec`), updated params re-gathered via the ICI
  *inside* the donated step — per-device optimizer memory drops ~Nx and
  the gradient all-reduce lowers to reduce-scatter + all-gather.
* the compiler hook — :func:`plan_scope` makes a plan ambient for the
  bind-time graph passes; the registered annotator
  (``compiler.register_annotator``) writes the per-param specs and the
  plan signature into ``GraphIR.annotations``, so graph fingerprints /
  persistent-program keys include the sharding layout (a ZeRO flip or a
  rule edit is a different executable, never a stale cache hit).

Measurement helpers (:func:`state_bytes_per_device`,
:func:`nearest_divisible_batch`, :func:`divisibility_error`) serve the
multichip bench and the bind-time diagnostics.
"""
from __future__ import annotations

import contextlib
import hashlib
import json
import re
import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..base import MXNetError, getenv

__all__ = ["param_pspec", "batch_pspec", "shard_params",
           "parse_rules", "rules_from_env", "match_partition_rules",
           "fit_spec_to_shape", "zero_shard_spec", "zero_sharded_update",
           "ShardingPlan",
           "plan_scope", "current_plan", "nearest_divisible_batch",
           "divisibility_error", "state_bytes_per_device"]


def param_pspec(name: str, shape, mesh: Mesh, model_axis: str = "model") -> P:
    """Default tensor-parallel rule for one parameter.

    2-D+ weights get their largest mesh-divisible dim sharded over the
    ``model`` axis (Megatron-style column/row split — the MXU keeps each
    shard's matmul dense); everything else (biases, BN stats, embeddings
    smaller than the axis) is replicated. With no ``model`` axis this
    degenerates to fully-replicated data parallelism, matching the
    reference's per-device parameter copies.
    """
    if model_axis not in mesh.axis_names:
        return P()
    m = mesh.shape[model_axis]
    if m == 1 or len(shape) < 2:
        return P()
    # prefer the output-channel dim: FC weight is (out, in); conv weight is
    # (O, *spatial, I) in NHWC or (O, I, *spatial) in NCHW — axis 0 either way
    order = [0, len(shape) - 1] + list(range(1, len(shape) - 1))
    for ax in order:
        if shape[ax] % m == 0 and shape[ax] // m >= 8:
            spec = [None] * len(shape)
            spec[ax] = model_axis
            return P(*spec)
    return P()


def batch_pspec(mesh: Mesh, ndim: int = 1, data_axis: str = "data") -> P:
    """Batch rule: axis 0 sharded over ``data`` (+ nothing else)."""
    if data_axis not in mesh.axis_names:
        return P()
    return P(data_axis, *([None] * (ndim - 1)))


def shard_params(params: Dict[str, jax.Array], mesh: Mesh,
                 rules=None, model_axis: str = "model"):
    """device_put every param with its rule's NamedSharding."""
    rules = rules or param_pspec
    out = {}
    for name, v in params.items():
        if isinstance(rules, (list, tuple)):
            spec = match_partition_rules(rules, {name: v}, mesh=mesh)[name]
        else:
            spec = rules(name, v.shape, mesh, model_axis)
        out[name] = jax.device_put(v, NamedSharding(mesh, spec))
    return out


# ---------------------------------------------------------------------------
# rule lists: ordered (regex, PartitionSpec) pairs
# ---------------------------------------------------------------------------

#: one partition rule: a regex matched against the parameter name
#: (``re.search``) and the PartitionSpec applied on a hit
PartitionRule = Tuple[str, P]


def parse_rules(text: str) -> List[PartitionRule]:
    """Parse an ``MXTPU_PARTITION_RULES`` value into an ordered rule list.

    The syntax is a JSON array of ``[regex, spec]`` pairs, where ``spec``
    is a list of axis entries — an axis name, ``null`` (dim replicated),
    or a list of axis names (a dim sharded over several axes)::

        [["embed_weight$", [null, "model"]],
         ["_weight$",      ["model"]],
         [".*",            []]]

    A leading ``@`` reads the JSON from a file path instead, so long
    rule sets live next to the model code. Order is precedence: the
    FIRST matching regex wins (``match_partition_rules``); an
    unmatched name is replicated. Malformed input raises
    :class:`~mxnet_tpu.base.MXNetError` naming the defect — a silent
    fallback would train with the wrong layout.
    """
    src = text.strip()
    if src.startswith("@"):
        try:
            with open(src[1:], "r", encoding="utf-8") as f:
                src = f.read()
        except OSError as err:
            raise MXNetError(
                f"partition-rule file {src[1:]!r} unreadable: {err}") from err
    try:
        raw = json.loads(src)
    except ValueError as err:
        raise MXNetError(
            f"partition rules are not valid JSON ({err}); expected "
            '[["regex", ["axis", null, ...]], ...]') from err
    if not isinstance(raw, list):
        raise MXNetError("partition rules must be a JSON array of "
                         "[regex, spec] pairs")
    rules: List[PartitionRule] = []
    for i, item in enumerate(raw):
        if (not isinstance(item, (list, tuple)) or len(item) != 2
                or not isinstance(item[0], str)
                or not isinstance(item[1], list)):
            raise MXNetError(
                f"partition rule #{i} is not a [regex, spec] pair: {item!r}")
        pat, spec = item
        try:
            re.compile(pat)
        except re.error as err:
            raise MXNetError(
                f"partition rule #{i} regex {pat!r} invalid: {err}") from err
        entries = []
        for e in spec:
            if e is None or isinstance(e, str):
                entries.append(e)
            elif isinstance(e, list) and all(isinstance(a, str) for a in e):
                entries.append(tuple(e))
            else:
                raise MXNetError(
                    f"partition rule #{i} spec entry {e!r} must be an "
                    "axis name, null, or a list of axis names")
        rules.append((pat, P(*entries)))
    return rules


def rules_from_env() -> Optional[List[PartitionRule]]:
    """Rule list from ``MXTPU_PARTITION_RULES`` (None when unset)."""
    text = getenv("MXTPU_PARTITION_RULES", None)
    return parse_rules(text) if text else None


def _spec_axes(entry) -> tuple:
    """Mesh axes one PartitionSpec entry names (an entry is an axis
    name, a tuple of names, or None)."""
    if entry is None:
        return ()
    return tuple(entry) if isinstance(entry, tuple) else (entry,)


def fit_spec_to_shape(spec: P, shape, mesh: Optional[Mesh]) -> P:
    """Make ``spec`` legal for ``shape`` on ``mesh``.

    The per-dim fallback contract of the rule engine: an entry naming
    an axis the mesh lacks, or whose axis-size product does not divide
    the dim, drops to ``None`` (that dim replicated) instead of failing
    the bind — a rule file written for the pod keeps working on the
    2-device CI mesh. Extra entries beyond ``len(shape)`` are dropped;
    scalars are always fully replicated."""
    shape = tuple(shape)
    if not shape or int(np.prod(shape)) <= 1:
        return P()
    entries = list(spec) + [None] * (len(shape) - len(spec))
    entries = entries[:len(shape)]
    out = []
    for dim, entry in zip(shape, entries):
        axes = _spec_axes(entry)
        if not axes:
            out.append(None)
            continue
        if mesh is not None:
            if any(a not in mesh.axis_names for a in axes):
                out.append(None)
                continue
            size = int(np.prod([mesh.shape[a] for a in axes]))
            if size <= 0 or dim % size:
                out.append(None)
                continue
        out.append(entry)
    while out and out[-1] is None:      # canonical: no trailing Nones
        out.pop()
    return P(*out)


def match_partition_rules(rules: Sequence[PartitionRule], params,
                          mesh: Optional[Mesh] = None) -> Dict[str, P]:
    """Resolve ordered regex rules against named parameters.

    ``params`` maps name -> array (or shape tuple). Returns name ->
    ``PartitionSpec``: the FIRST rule whose regex ``re.search``-matches
    the name wins; scalars and unmatched names are replicated. With
    ``mesh``, every winning spec is passed through
    :func:`fit_spec_to_shape` so non-divisible dims fall back to
    replicated instead of failing downstream.
    """
    out: Dict[str, P] = {}
    for name, v in params.items():
        shape = tuple(v) if isinstance(v, (tuple, list)) \
            else tuple(getattr(v, "shape", ()))
        spec = P()
        for pat, ps in rules:
            if re.search(pat, name):
                spec = ps
                break
        out[name] = fit_spec_to_shape(spec, shape, mesh) \
            if mesh is not None else spec
    return out


# ---------------------------------------------------------------------------
# ZeRO-1: cross-replica sharding of the weight update (arxiv 2004.13336)
# ---------------------------------------------------------------------------

def zero_shard_spec(base: P, shape, mesh: Mesh,
                    data_axis: str = "data") -> P:
    """ZeRO-1 spec for one optimizer-state slot: ``base`` (the param's
    own spec) plus the first mesh-divisible unsharded dim split over the
    ``data`` axis, so each data-parallel replica owns and updates a 1/N
    slice. Falls back to ``base`` (replicated state) when no dim can
    take the split or a custom rule already spent the data axis."""
    shape = tuple(shape)
    dsize = mesh.shape.get(data_axis, 1)
    if dsize <= 1 or not shape:
        return base
    entries = list(base) + [None] * (len(shape) - len(base))
    used = {a for e in entries for a in _spec_axes(e)}
    if data_axis in used:
        return base
    for i, dim in enumerate(shape):
        if entries[i] is None and dim % dsize == 0 and dim >= dsize:
            entries[i] = data_axis
            return P(*entries)
    return base


def zero_sharded_update(mesh: Mesh, data_axis: str, update, w, g, s,
                        lr, wd, t, param_spec: P, state_spec: P):
    """Run one parameter's optimizer update sharded over ``data_axis``
    inside a :func:`jax.shard_map`.

    The shard_map is the bitwise contract's load-bearing wall: its
    boundary specs are pinned, so the sliced update's layout demands
    cannot propagate into the surrounding forward/backward and re-lay
    it out (observed without it: GSPMD turned the batch-sharded fc1
    matmul into batch-all-gather x weight-slice and replaced the
    gradient's partial-dot + all-reduce with operand-gather + full
    local dot — same values at a different summation order, last-ulp
    drift vs the replicated program). Inside, each device slices the
    (replicated, fully-reduced) grad and weight at its own data-axis
    index, updates its 1/N shard against its local optimizer-state
    slice, and re-gathers the updated weight over the ICI
    (``jax.lax.all_gather`` — inside the donated step, not a separate
    dispatch). Elementwise update math on a slice is bitwise the same
    elements the replicated program computes, so ZeRO == replicated
    exactly.

    Falls back to a plain (replicated) update when ``state_spec``
    never took the data split — the per-dim fallback for shapes with
    no divisible dim."""
    # the dim where zero_shard_spec ADDED the data split (present in
    # the state spec, absent from the param spec); a custom rule that
    # already spent the data axis on the param itself has nothing to
    # slice — the state simply inherits the param layout
    pentries = list(param_spec) + [None] * (len(state_spec)
                                            - len(param_spec))
    dim = next((i for i, e in enumerate(state_spec)
                if data_axis in _spec_axes(e)
                and data_axis not in _spec_axes(pentries[i])), None)
    if dim is None:
        return update(w, g, s, lr, wd, t)
    from jax import shard_map
    nshard = mesh.shape[data_axis]

    def body(w, g, s, lr, t):
        idx = jax.lax.axis_index(data_axis)
        width = w.shape[dim] // nshard

        def sl(x):
            return jax.lax.dynamic_slice_in_dim(
                x, idx * width, width, axis=dim)

        w2, s2 = update(sl(w), sl(g), s, lr, wd, t)
        w2 = jax.lax.all_gather(w2, data_axis, axis=dim, tiled=True)
        return w2, s2

    # the weight/grad arrive replicated over the data axis (the grad's
    # cross-replica all-reduce already ran, in the same order the
    # replicated program runs it); only the state is block-local
    other = [a for a in mesh.axis_names if a != data_axis]
    repl_over_data = P(*[tuple(a for a in _spec_axes(e) if a in other)
                         or None for e in param_spec])
    state_structs = jax.tree_util.tree_map(lambda x: state_spec, s)
    return shard_map(
        body, mesh=mesh,
        in_specs=(repl_over_data, repl_over_data, state_structs, P(), P()),
        out_specs=(repl_over_data, state_structs),
        check_vma=False)(w, g, s, lr, t)


# ---------------------------------------------------------------------------
# the resolved plan
# ---------------------------------------------------------------------------

class ShardingPlan:
    """Partition rules resolved for one mesh: the placement oracle every
    step builder consults.

    ``rules`` is an ordered ``(regex, PartitionSpec)`` list, a legacy
    callable ``(name, shape, mesh) -> PartitionSpec``, or None — None
    reads ``MXTPU_PARTITION_RULES`` and falls back to the default
    :func:`param_pspec` tensor-parallel rule. ``zero`` (default: the
    ``MXTPU_ZERO`` knob) arms ZeRO-1 cross-replica update sharding: the
    per-slot optimizer state AND the gradient feeding the update are
    pinned to :meth:`state_spec` (the reduce-scatter layout), and the
    updated parameter is constrained back to :meth:`param_spec` — the
    all-gather the ICI performs inside the donated step.

    The plan is a pure function of ``(mesh, rules, zero)``: an elastic
    re-mesh rebuilds it for the surviving topology
    (``SPMDTrainer.bind``), which is what keeps ZeRO layouts bitwise
    across 8→4 recoveries instead of migrating device-local slices.
    """

    def __init__(self, mesh: Mesh, rules=None, zero: Optional[bool] = None,
                 data_axis: str = "data", model_axis: str = "model"):
        self.mesh = mesh
        self.data_axis = data_axis
        self.model_axis = model_axis
        if rules is None:
            rules = rules_from_env()
        self.rules = rules
        if zero is None:
            zero = getenv("MXTPU_ZERO", 0, int)
        zval = (1 if zero else 0) if isinstance(zero, bool) else int(zero)
        #: ZeRO as requested; `zero` below is the EFFECTIVE mode (a
        #: 1-wide data axis has nothing to shard over)
        self.zero_requested = zval > 0
        self.zero = zval > 0 and mesh.shape.get(data_axis, 1) > 1
        #: MXTPU_ZERO=2: comm-optimal mode — the grad is pinned
        #: straight to the state layout so GSPMD lowers the
        #: cross-replica reduction to a reduce-scatter (half the
        #: gradient traffic of all-reduce + slice), at the cost of the
        #: bitwise ZeRO==replicated contract (a different summation
        #: order; expect last-ulp drift). Default (1) keeps bitwise:
        #: full all-reduce, then the shard_map-sliced update.
        self.zero_rs = self.zero and zval >= 2

    # -- specs ---------------------------------------------------------------

    def param_spec(self, name: str, shape) -> P:
        shape = tuple(shape)
        if not shape:
            return P()
        if isinstance(self.rules, (list, tuple)):
            return match_partition_rules(self.rules, {name: shape},
                                         mesh=self.mesh)[name]
        fn = self.rules or param_pspec
        return fit_spec_to_shape(fn(name, shape, self.mesh), shape,
                                 self.mesh)

    def state_spec(self, name: str, shape) -> P:
        """Per-slot optimizer-state spec (momentum/variance): the param
        spec, plus — in ZeRO mode — the data-axis split."""
        base = self.param_spec(name, shape)
        if not self.zero:
            return base
        return zero_shard_spec(base, shape, self.mesh, self.data_axis)

    def grad_spec(self, name: str, shape) -> P:
        """Gradient layout feeding the optimizer update. In the
        comm-optimal ZeRO mode (``MXTPU_ZERO=2``) this is the state
        spec — pinning the grad there is what turns the batch-axis
        all-reduce into a reduce-scatter. In the default (bitwise)
        ZeRO mode the grad stays on the param layout: the full
        all-reduce runs in the replicated program's order and the
        shard_map update slices it locally."""
        return self.state_spec(name, shape) if self.zero_rs \
            else self.param_spec(name, shape)

    def batch_spec(self, ndim: int = 1) -> P:
        return batch_pspec(self.mesh, ndim, self.data_axis)

    def param_sharding(self, name: str, shape) -> NamedSharding:
        return NamedSharding(self.mesh, self.param_spec(name, shape))

    def state_sharding(self, name: str, shape) -> NamedSharding:
        return NamedSharding(self.mesh, self.state_spec(name, shape))

    # -- introspection -------------------------------------------------------

    @property
    def zero_degree(self) -> int:
        """Replica count the update is sharded over (1 = ZeRO off)."""
        return self.mesh.shape.get(self.data_axis, 1) if self.zero else 1

    def zero_unsharded(self, shapes: Dict[str, tuple]) -> List[str]:
        """Params that stay on replicated optimizer state under ZeRO —
        no dim divisible by the data axis (and big enough to matter).
        Reported at bind so degraded sharding is visible, not silent."""
        if not self.zero:
            return []
        dsize = self.mesh.shape[self.data_axis]
        out = []
        for name, shape in shapes.items():
            if int(np.prod(shape)) < dsize:
                continue        # tiny params are noise, not a degradation
            spec = self.state_spec(name, shape)
            used = {a for e in spec for a in _spec_axes(e)}
            if self.data_axis not in used:
                out.append(name)
        return out

    def _rules_sig(self) -> str:
        if isinstance(self.rules, (list, tuple)):
            return json.dumps([[pat, str(spec)] for pat, spec in self.rules])
        if self.rules is None:
            return "default"
        return getattr(self.rules, "__qualname__", repr(self.rules))

    def signature(self) -> str:
        """Stable identity of everything placement-affecting: mesh axes,
        rules, ZeRO mode. Joins program-cache keys (via the annotator
        below and the step builders' key parts)."""
        shape = dict(getattr(self.mesh, "shape", {}))
        zmode = (2 if self.zero_rs else 1) if self.zero else 0
        return (f"axes={sorted(shape.items())};zero={zmode};"
                f"zaxis={self.data_axis};rules={self._rules_sig()}")

    def signature_hash(self) -> str:
        return hashlib.sha256(
            self.signature().encode("utf-8")).hexdigest()[:16]


# ---------------------------------------------------------------------------
# bind-time diagnostics
# ---------------------------------------------------------------------------

def nearest_divisible_batch(batch: int, degree: int) -> Tuple[int, int]:
    """(down, up): the nearest global batch sizes divisible by
    ``degree`` on either side of ``batch`` (down may equal 0)."""
    degree = max(1, int(degree))
    down = (int(batch) // degree) * degree
    return down, down + degree


def divisibility_error(value: int, input_name: str, axis: str,
                       degree: int, what: str = "mesh") -> MXNetError:
    """The bind-time error for a batch/axis mismatch: names the axis
    and its size, and suggests the nearest divisible batches — the
    message the user acts on instead of a jax shape blowup at step one."""
    down, up = nearest_divisible_batch(value, degree)
    suggest = f"{up}" if down <= 0 else f"{down} or {up}"
    return MXNetError(
        f"global batch size {value} for input '{input_name}' is not "
        f"divisible by the {what} '{axis}' axis ({degree} devices); use "
        f"a global batch divisible by {degree} — nearest: {suggest} — "
        "or re-mesh to a compatible device count (elastic re-meshing "
        "selects one automatically)")


def state_bytes_per_device(tree) -> int:
    """MEASURED per-device bytes of a live (sharded) pytree: each leaf
    contributes its own shard's footprint — ``sharding.shard_shape``
    for named shardings, the full buffer otherwise. This is the number
    the multichip bench reports for optimizer state under ZeRO vs
    replicated (measured from the arrays, not estimated from specs)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        shape = tuple(getattr(leaf, "shape", ()))
        itemsize = getattr(getattr(leaf, "dtype", None), "itemsize", None)
        if itemsize is None:
            continue
        sh = getattr(leaf, "sharding", None)
        if sh is not None and hasattr(sh, "shard_shape"):
            shape = sh.shard_shape(shape)
        total += int(np.prod(shape)) * int(itemsize)
    return total


# ---------------------------------------------------------------------------
# compiler hook: the annotate-slot provider
# ---------------------------------------------------------------------------

class _PlanTLS(threading.local):
    def __init__(self):
        self.stack: List[ShardingPlan] = []


_PLAN_TLS = _PlanTLS()
_ANNOTATOR_REGISTERED = False


def current_plan() -> Optional[ShardingPlan]:
    """The innermost active :func:`plan_scope` plan on this thread."""
    stack = _PLAN_TLS.stack
    return stack[-1] if stack else None


def _sharding_annotator(ir, ctx):
    """The ``annotate``-slot provider (compiler.register_annotator):
    with a plan ambient, record each parameter's (param, state) spec
    pair and the plan signature into the IR annotations. The signature
    joins ``OptimizeResult.transform_sig`` and therefore every
    persistent program key built from it — a sharding change can never
    serve a stale executable. No plan ambient -> None (no-op slot)."""
    plan = current_plan()
    if plan is None:
        return None
    specs = {}
    for node in ir.nodes:
        if not node.is_variable:
            continue
        shape = ctx.input_shapes.get(node.name)
        if shape is None:
            continue
        specs[node.name] = (str(plan.param_spec(node.name, shape)),
                            str(plan.state_spec(node.name, shape)))
    return {"sharding": specs, "sharding_sig": plan.signature_hash()}


def _ensure_annotator():
    # lazy registration keeps import order acyclic (compiler never
    # imports parallel); idempotent per process
    global _ANNOTATOR_REGISTERED
    if not _ANNOTATOR_REGISTERED:
        from .. import compiler as _compiler
        _compiler.register_annotator(_sharding_annotator)
        _ANNOTATOR_REGISTERED = True


class plan_scope:
    """Make ``plan`` ambient for the bind-time graph passes, so the
    sharding annotator stamps its specs into the IR the step builder is
    about to trace. Step builders wrap their ``compiler.optimize`` call::

        with plan_scope(self._plan):
            opt_res = compiler.optimize(symbol, ...)
    """

    def __init__(self, plan: Optional[ShardingPlan]):
        self.plan = plan

    def __enter__(self):
        _ensure_annotator()
        _PLAN_TLS.stack.append(self.plan)
        return self.plan

    def __exit__(self, *exc):
        _PLAN_TLS.stack.pop()
        return False
