"""Declarative operator registry — the single op table for the framework.

Reference analogue: NNVM op registration (``NNVM_REGISTER_OP`` + attribute
functors FCompute/FInferShape/FInferType, include/mxnet/op_attr_types.h:109-240)
and the 339 ``*REGISTER*`` sites under src/operator/. In the rebuild each op is
one Python record whose ``fn`` is a jax-traceable computation:

* shape/type inference  -> ``jax.eval_shape`` over ``fn`` (replaces
  FInferShape/FInferType passes, src/executor/infer_graph_attr_pass.cc)
* gradient              -> ``jax.vjp`` over ``fn`` (replaces FGradient graphs)
* kernels               -> jnp/lax compositions, Pallas where fusion loses
* the same table generates both the imperative ``nd.*`` namespace and the
  symbolic ``sym.*`` namespace, mirroring the reference's import-time codegen
  (python/mxnet/ndarray/op.py:51 ``_make_ndarray_function``).
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Sequence, Tuple, Union

from jax.ad_checkpoint import checkpoint_name

from ..base import AttrSpec, MXNetError

__all__ = ["OpDef", "register", "get_op", "list_ops", "OP_TABLE", "alias",
           "KEPT_RESIDUAL", "keep_residual"]

OP_TABLE: Dict[str, "OpDef"] = {}

# The one name an op gives a value that a block's checkpoint
# (``executor.build_graph_eval(remat_blocks=True)``) keeps for the backward
# where it recomputes the rest of the block.
KEPT_RESIDUAL = "kept_residual"


def keep_residual(x):
    """``x`` under the name the block checkpoint keeps. For a value that is
    dear to compute again and small beside what the block holds anyway; it
    is kept only if the named value itself is what the backward reads."""
    return checkpoint_name(x, KEPT_RESIDUAL)


class OpDef:
    """One operator.

    fn(*inputs, **attrs) -> array or tuple of arrays. Must be jax-traceable in
    the inputs (pure; no data-dependent python control flow). Ops that sample
    randomness take a leading ``rng`` key argument and set ``needs_rng``; ops
    whose semantics differ between train/eval read the ``_is_train`` attr
    injected by the caller and set ``needs_is_train``.
    """

    def __init__(
        self,
        name: str,
        fn: Callable,
        attrs: Optional[AttrSpec] = None,
        num_inputs: Optional[int] = None,
        num_outputs: Union[int, Callable] = 1,
        input_names: Optional[Sequence[str]] = None,
        output_names: Optional[Sequence[str]] = None,
        needs_rng: bool = False,
        needs_is_train: bool = False,
        differentiable: bool = True,
        key_var_num_args: Optional[str] = None,
        aux_update: Optional[Dict[int, int]] = None,
        grad_fn: Optional[Callable] = None,
        aux_inputs: Sequence[int] = (),
        param_shapes: Optional[Callable] = None,
        stateful: bool = False,
        aux_counters: Optional[Dict[int, Sequence[str]]] = None,
    ):
        self.name = name
        self.fn = fn
        self.attr_spec = attrs or AttrSpec()
        self.num_inputs = num_inputs
        self._num_outputs = num_outputs
        self.input_names = list(input_names) if input_names else None
        self.output_names = list(output_names) if output_names else ["output"]
        self.needs_rng = needs_rng
        self.needs_is_train = needs_is_train
        self.differentiable = differentiable
        # name of the attr holding the variadic input count (reference:
        # key_var_num_args on ops like Concat/add_n — nnvm op registration)
        self.key_var_num_args = key_var_num_args
        # output idx -> input idx written back in imperative train mode
        # (reference: auxiliary states, e.g. BatchNorm moving_mean/var)
        self.aux_update = aux_update or {}
        self.grad_fn = grad_fn
        # input indices that are auxiliary states, not gradient-bearing args
        # (reference: OperatorProperty::ListAuxiliaryStates); a callable
        # ``attrs -> indices`` where an attr adds one (MoEFFN's expert_bias)
        self.aux_inputs = aux_inputs if callable(aux_inputs) \
            else tuple(aux_inputs)
        # param_shapes(attrs, input_shapes) -> full input-shape list with
        # unknown parameter shapes filled in from the data shape + attrs;
        # the simple_bind-side half of the reference's two-way InferShape
        # (src/executor/infer_graph_attr_pass.cc)
        self.param_shapes = param_shapes
        # stateful ops get a per-invocation ``_op_state`` holder dict injected
        # into their attrs on the imperative path; the autograd tape keeps it
        # so forward-created state reaches backward (reference: stateful ops
        # save an OpStatePtr on the tape — SURVEY.md §3.3)
        self.stateful = stateful
        # auxiliary input idx -> the names of the counters its vector holds,
        # one an element, which the op adds to on the device every training
        # step; a trainer reads them at a boundary (SPMDTrainer.aux_counters)
        self.aux_counters = {k: tuple(v)
                             for k, v in (aux_counters or {}).items()}

    def num_outputs(self, attrs) -> int:
        if callable(self._num_outputs):
            return self._num_outputs(attrs)
        return self._num_outputs

    def aux_input_indices(self, attrs) -> Tuple[int, ...]:
        """The auxiliary inputs of THIS instantiation."""
        if callable(self.aux_inputs):
            return tuple(self.aux_inputs(attrs))
        return self.aux_inputs

    def uses_rng(self, attrs) -> bool:
        """Does THIS instantiation actually draw randomness?

        ``needs_rng`` stays truthy whenever the fn signature takes a key
        (every call site threads one); a *callable* ``needs_rng`` is an
        attrs predicate refining that — e.g. the fused RNN op only
        samples when its inter-layer dropout ``p`` is nonzero. Executors
        use this to skip the per-step key split/fold for graphs that are
        deterministic in practice.
        """
        if callable(self.needs_rng):
            return bool(self.needs_rng(attrs))
        return bool(self.needs_rng)

    def parse_attrs(self, raw_attrs: Dict) -> Dict:
        return self.attr_spec.parse(raw_attrs, self.name)

    def arg_names(self, n_inputs: int):
        if self.input_names and len(self.input_names) == n_inputs:
            return list(self.input_names)
        if n_inputs == 1:
            return ["data"]
        return [f"arg{i}" for i in range(n_inputs)]

    def __repr__(self):
        return f"<OpDef {self.name}>"


def register(name: str, aliases: Sequence[str] = (), **kwargs):
    """Register an operator. Usable as a decorator over its fn."""

    def deco(fn):
        op = OpDef(name, fn, **kwargs)
        if name in OP_TABLE:
            raise MXNetError(f"operator {name} registered twice")
        OP_TABLE[name] = op
        for a in aliases:
            OP_TABLE[a] = op
        return fn

    return deco


def alias(new_name: str, existing: str):
    OP_TABLE[new_name] = OP_TABLE[existing]


def resolve_inputs(opdef: "OpDef", args, kwargs, name: str,
                   is_input=None):
    """Merge positional and keyword-passed op inputs into one ordered list.

    Shared by the generated nd.* and sym.* wrappers (both accept inputs
    positionally or by their declared names, reference ndarray/op.py
    codegen). Mutates ``kwargs`` (consumed input names are popped).
    NB: generated namespaces contain ops named 'max'/'min'/'sum' that shadow
    builtins at module scope — use builtins explicitly here.
    """
    import builtins

    inputs = list(args)
    # positional parameters after the tensor inputs (reference codegen
    # signatures: ``clip(data, a_min, a_max)`` — params fill in declared
    # order). Peel non-tensor trailing args onto unconsumed attr fields.
    if opdef.attr_spec.fields:
        def _tensorish(v):
            if is_input is not None:
                return is_input(v)
            return (hasattr(v, "shape") and hasattr(v, "dtype")
                    and not isinstance(v, (tuple, list)))

        n_peel = 0
        while (n_peel < builtins.len(inputs)
               and not _tensorish(inputs[-1 - n_peel])):
            n_peel += 1
        if n_peel:
            # the variadic-count field is auto-filled, never positional
            fields = [k for k in opdef.attr_spec.fields
                      if k not in kwargs and k != opdef.key_var_num_args]
            if n_peel > builtins.len(fields):
                raise MXNetError(
                    f"{name}: {n_peel} positional parameters given but "
                    f"only {builtins.len(fields)} declared parameters "
                    f"remain ({fields}); valid: "
                    f"{builtins.sorted(opdef.attr_spec.fields)}")
            extra = inputs[builtins.len(inputs) - n_peel:]
            inputs = inputs[:builtins.len(inputs) - n_peel]
            kwargs.update(builtins.zip(fields, extra))
    # ops registered without explicit input_names still accept the
    # conventional ``data=`` keyword (the reference's generated wrappers
    # name the first input 'data' for every single-input op)
    input_names = opdef.input_names or ["data"]
    kw_inputs = {}
    for i, n in enumerate(input_names):
        if n in kwargs and (is_input is None or is_input(kwargs[n])):
            kw_inputs[i] = kwargs.pop(n)
    if kw_inputs:
        hi = builtins.max(kw_inputs)
        slots = inputs + [None] * builtins.max(0, hi + 1 - len(inputs))
        for i, v in kw_inputs.items():
            if slots[i] is not None:
                raise MXNetError(
                    f"input {input_names[i]} of {name} given "
                    "both positionally and by keyword")
            slots[i] = v
        inputs = [x for x in slots if x is not None]
    return inputs


def populate_contrib(parent_module, target_module):
    """Fill a ``contrib`` namespace module: every ``_contrib_*`` table op
    already generated on ``parent_module`` is re-exported on
    ``target_module`` with the prefix stripped (reference:
    python/mxnet/ndarray/op.py contrib-module routing)."""
    for name in list(OP_TABLE):
        if name.startswith("_contrib_"):
            setattr(target_module, name[len("_contrib_"):],
                    getattr(parent_module, name))


def get_op(name: str) -> OpDef:
    if name not in OP_TABLE:
        raise MXNetError(f"Unknown operator {name}")
    return OP_TABLE[name]


def list_ops():
    return sorted(OP_TABLE)
