"""Symbol: the declarative graph API.

Reference analogue: nnvm::Symbol + python/mxnet/symbol/symbol.py (compose,
infer_shape, simple_bind/bind, JSON save/load). In the rebuild a Symbol is a
lightweight DAG of op applications over the same OP_TABLE as nd.*; binding
compiles the whole graph with jax.jit — the NNVM pass pipeline
(Gradient/PlaceDevice/PlanMemory/bulk-exec, SURVEY.md §3.2) collapses into
jax.grad + XLA buffer assignment & fusion.
"""
from __future__ import annotations

import json
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as _np

from ..base import MXNetError, _parse_tuple
from ..ops.registry import OP_TABLE, OpDef, get_op

__all__ = ["Symbol", "SymbolNode", "Variable", "var", "Group", "load",
           "load_json", "symbol_invoke", "NameManager", "Prefix", "AttrScope"]


class _NameManagerMeta(type):
    """Makes ``NameManager.current`` thread-local while keeping the
    reference's class-attribute spelling (each thread gets its own default
    manager; scoped installs don't leak across threads)."""

    _tls = threading.local()

    @property
    def current(cls):
        cur = getattr(cls._tls, "current", None)
        if cur is None:
            cur = cls._tls.current = NameManager()
        return cur

    @current.setter
    def current(cls, value):
        cls._tls.current = value


class NameManager(metaclass=_NameManagerMeta):
    """Auto-naming for anonymous symbols (reference: python/mxnet/name.py).

    Scoped like the reference: ``NameManager.current`` is the active
    manager; ``with NameManager():`` / ``with Prefix('net_'):`` installs a
    new one for the block. Subclasses override the instance ``get``.
    """

    def __init__(self):
        self._counter = {}
        self._old_manager = None

    def get(self, name: Optional[str], hint: str) -> str:
        if name:
            return name
        hint = hint.lower().lstrip("_")
        idx = self._counter.get(hint, 0)
        self._counter[hint] = idx + 1
        return f"{hint}{idx}"

    def __enter__(self):
        self._old_manager = NameManager.current
        NameManager.current = self
        return self

    def __exit__(self, ptype, value, trace):
        assert self._old_manager is not None
        NameManager.current = self._old_manager
        return False

    @classmethod
    def reset(cls):
        cls.current._counter = {}


class Prefix(NameManager):
    """Name manager that prepends a prefix to every auto/explicit name
    (reference name.py:74)."""

    def __init__(self, prefix: str):
        super().__init__()
        self._prefix = prefix

    def get(self, name: Optional[str], hint: str) -> str:
        return self._prefix + super().get(name, hint)


class AttrScope:
    """``with mx.AttrScope(ctx_group='dev1'):`` — attach attrs to symbols
    created in scope (reference: python/mxnet/attribute.py; used for
    ctx_group model parallelism)."""

    _local = threading.local()

    def __init__(self, **attrs):
        self._attrs = {k: str(v) for k, v in attrs.items()}

    @classmethod
    def current_attrs(cls) -> Dict[str, str]:
        return dict(getattr(cls._local, "attrs", {}) or {})

    def __enter__(self):
        self._old = getattr(AttrScope._local, "attrs", {})
        merged = dict(self._old)
        merged.update(self._attrs)
        AttrScope._local.attrs = merged
        return self

    def __exit__(self, *args):
        AttrScope._local.attrs = self._old
        return False


class SymbolNode:
    """One graph node: a variable (op=None) or an op application."""

    __slots__ = ("op", "name", "attrs", "inputs", "scope_attrs")

    def __init__(self, op: Optional[OpDef], name: str, attrs: Dict,
                 inputs: List[Tuple["SymbolNode", int]]):
        self.op = op
        self.name = name
        self.attrs = attrs          # parsed python values
        self.inputs = inputs
        self.scope_attrs = AttrScope.current_attrs()

    @property
    def is_variable(self):
        return self.op is None

    def num_outputs(self):
        return 1 if self.op is None else self.op.num_outputs(self.attrs)


class Symbol:
    """A list of output entries over the node DAG."""

    def __init__(self, outputs: List[Tuple[SymbolNode, int]]):
        self._outputs = outputs

    # -- graph traversal ----------------------------------------------------
    def _topo_nodes(self) -> List[SymbolNode]:
        order, seen = [], set()
        stack = [(n, False) for n, _ in reversed(self._outputs)]
        while stack:
            node, done = stack.pop()
            if done:
                order.append(node)
                continue
            if id(node) in seen:
                continue
            seen.add(id(node))
            stack.append((node, True))
            for parent, _ in reversed(node.inputs):
                if id(parent) not in seen:
                    stack.append((parent, False))
        return order

    def _aux_node_ids(self) -> set:
        aux = set()
        for node in self._topo_nodes():
            if node.op is not None and node.op.aux_inputs:
                for i in node.op.aux_input_indices(node.attrs):
                    if i < len(node.inputs):
                        parent, _ = node.inputs[i]
                        if parent.is_variable:
                            aux.add(id(parent))
        return aux

    def list_arguments(self) -> List[str]:
        aux = self._aux_node_ids()
        return [n.name for n in self._topo_nodes()
                if n.is_variable and id(n) not in aux]

    def list_auxiliary_states(self) -> List[str]:
        aux = self._aux_node_ids()
        return [n.name for n in self._topo_nodes()
                if n.is_variable and id(n) in aux]

    def list_outputs(self) -> List[str]:
        names = []
        for node, idx in self._outputs:
            if node.num_outputs() == 1:
                names.append(f"{node.name}_output" if node.op else node.name)
            else:
                out_name = (node.op.output_names[idx]
                            if node.op and idx < len(node.op.output_names)
                            else str(idx))
                names.append(f"{node.name}_{out_name}")
        return names

    def list_inputs(self) -> List[str]:
        return [n.name for n in self._topo_nodes() if n.is_variable]

    @property
    def name(self):
        if len(self._outputs) == 1:
            return self._outputs[0][0].name
        return None

    # -- composition --------------------------------------------------------
    def __getitem__(self, index):
        if isinstance(index, str):
            names = self.list_outputs()
            if index not in names:
                raise MXNetError(f"no output named {index}; have {names}")
            index = names.index(index)
        if isinstance(index, slice):
            return Symbol(self._outputs[index])
        return Symbol([self._outputs[index]])

    def __len__(self):
        return len(self._outputs)

    def __iter__(self):
        for i in range(len(self._outputs)):
            yield self[i]

    def get_internals(self) -> "Symbol":
        outs = []
        for node in self._topo_nodes():
            for i in range(node.num_outputs()):
                outs.append((node, i))
        return Symbol(outs)

    def get_children(self) -> Optional["Symbol"]:
        node = self._outputs[0][0]
        if not node.inputs:
            return None
        return Symbol(list(node.inputs))

    def attr(self, key):
        node = self._outputs[0][0]
        v = node.scope_attrs.get(key)
        if v is None and key in node.attrs:
            v = str(node.attrs[key])
        return v

    def attr_dict(self):
        out = {}
        for node in self._topo_nodes():
            d = dict(node.scope_attrs)
            if node.op is not None:
                d.update(node.op.attr_spec.serialize(node.attrs))
            else:
                # variables keep __shape__/__lr_mult__/__wd_mult__/__init__
                # directly in node.attrs (Variable() stores them there)
                d.update({k: str(v) for k, v in node.attrs.items()})
            if d:
                out[node.name] = d
        return out

    def _arg_layouts(self):
        """Map weight-variable name -> consumer op's ``layout`` attr.

        Lets initializers compute correct fan-in/fan-out for channel-last
        (NHWC -> OHWI) conv weights; the reference never needed this because
        it is NCHW-only (initializer.py Xavier assumes OIHW).
        """
        out = {}
        for node in self._topo_nodes():
            if node.op is None:
                continue
            layout = node.attrs.get("layout")
            if not layout or str(layout) in ("None",):
                continue
            for p, _ in node.inputs:
                if p.is_variable and p.name.endswith("weight"):
                    out[p.name] = str(layout)
        return out

    def _set_attr(self, **kwargs):
        for node, _ in self._outputs:
            node.scope_attrs.update({k: str(v) for k, v in kwargs.items()})

    # -- arithmetic (same table-driven dispatch as NDArray) ------------------
    def _binop(self, other, op, scalar_op, reverse=False):
        if isinstance(other, Symbol):
            a, b = (other, self) if reverse else (self, other)
            return symbol_invoke(get_op(op), [a, b], {}, None)
        if isinstance(other, (int, float)):
            return symbol_invoke(get_op(scalar_op), [self], {"scalar": other}, None)
        return NotImplemented

    def __add__(self, other):
        return self._binop(other, "elemwise_add", "_plus_scalar")

    __radd__ = __add__

    def __sub__(self, other):
        return self._binop(other, "elemwise_sub", "_minus_scalar")

    def __rsub__(self, other):
        if isinstance(other, (int, float)):
            return symbol_invoke(get_op("_rminus_scalar"), [self],
                                 {"scalar": other}, None)
        return NotImplemented

    def __mul__(self, other):
        return self._binop(other, "elemwise_mul", "_mul_scalar")

    __rmul__ = __mul__

    def __truediv__(self, other):
        return self._binop(other, "elemwise_div", "_div_scalar")

    __div__ = __truediv__

    def __rtruediv__(self, other):
        if isinstance(other, (int, float)):
            return symbol_invoke(get_op("_rdiv_scalar"), [self],
                                 {"scalar": other}, None)
        return NotImplemented

    __rdiv__ = __rtruediv__

    def __pow__(self, other):
        return self._binop(other, "_power", "_power_scalar")

    def __neg__(self):
        return symbol_invoke(get_op("negative"), [self], {}, None)

    def __copy__(self):
        return Symbol(list(self._outputs))

    def __repr__(self):
        name = self.name
        return f"<Symbol {name if name else 'group [' + ', '.join(self.list_outputs()) + ']'}>"

    # convenience mirrors of common ops
    def reshape(self, shape):
        return symbol_invoke(get_op("Reshape"), [self], {"shape": shape}, None)

    def astype(self, dtype):
        return symbol_invoke(get_op("Cast"), [self], {"dtype": str(dtype)}, None)

    # -- shape/type inference ------------------------------------------------
    def infer_shape(self, *args, **kwargs):
        try:
            return self._infer_shape_impl(False, *args, **kwargs)
        except MXNetError:
            raise

    def infer_shape_partial(self, *args, **kwargs):
        return self._infer_shape_impl(True, *args, **kwargs)

    def _infer_shape_impl(self, partial, *args, **kwargs):
        arg_names = self.list_arguments()
        known: Dict[str, tuple] = {}
        if args:
            for name, shape in zip(arg_names, args):
                if shape is not None:
                    known[name] = tuple(shape)
        known.update({k: tuple(v) for k, v in kwargs.items() if v is not None})
        structs = self._infer_structs(known, partial=partial)
        if structs is None:
            return None, None, None
        arg_shapes = [structs["vars"].get(n, (None,)) for n in arg_names]
        aux_shapes = [structs["vars"].get(n, (None,))
                      for n in self.list_auxiliary_states()]
        out_shapes = [structs["outs"][i] for i in range(len(self._outputs))]
        return arg_shapes, out_shapes, aux_shapes

    def infer_type(self, *args, **kwargs):
        """Forward dtype inference (reference: InferType pass,
        infer_graph_attr_pass.cc). Variables take their declared dtype
        (positional in list_arguments order, or by keyword), defaulting
        to float32; op outputs carry the numpy-promoted dtype of their
        inputs, with ``Cast``'s declared dtype overriding."""
        arg_names = self.list_arguments()
        known = {}
        for i, a in enumerate(args):
            if a is not None:
                known[arg_names[i]] = _np.dtype(a)
        for k, v in kwargs.items():
            if v is not None:
                known[k] = _np.dtype(v)
        aux_ids = self._aux_node_ids()
        dtypes: Dict[Tuple[int, int], _np.dtype] = {}
        f32 = _np.dtype("float32")
        for node in self._topo_nodes():
            if node.is_variable:
                dtypes[(id(node), 0)] = known.get(node.name, f32)
                continue
            ins = [dtypes[(id(p), i)] for p, i in node.inputs]
            if node.op.name in ("Cast", "cast") and "dtype" in node.attrs:
                out = _np.dtype(node.attrs["dtype"])
            elif ins:
                out = ins[0]
                for d in ins[1:]:
                    out = _np.promote_types(out, d)
            else:
                out = f32
            for i in range(node.num_outputs()):
                dtypes[(id(node), i)] = out
        name_dt = {n.name: dtypes[(id(n), 0)]
                   for n in self._topo_nodes() if n.is_variable}
        arg_types = [name_dt[n] for n in arg_names]
        aux_types = [name_dt[n] for n in self.list_auxiliary_states()]
        out_types = [dtypes[(id(n), i)] for n, i in self._outputs]
        return arg_types, out_types, aux_types

    def infer_storage_type(self, **kwargs):
        """Forward storage-type inference over the graph.

        Rebuild of the InferStorageType pass
        (src/executor/infer_graph_attr_pass.cc:356 + per-op
        FInferStorageType): variables get stypes from ``kwargs``
        (``name='csr'``), their ``stype=`` declaration, or 'default';
        op outputs follow the rule table below, with the reference's
        dense-fallback semantics (any un-ruled op treats sparse inputs
        as densified and produces dense outputs). Returns
        (arg_stypes, out_stypes, aux_stypes).
        """
        def out_rule(node, ins):
            op = node.op.name
            if op == "cast_storage":
                return [node.attrs.get("stype", "default")]
            if op == "sparse_retain":
                return ["row_sparse"]
            if op in ("elemwise_add", "ElementWiseSum", "add_n"):
                if ins and all(s == "row_sparse" for s in ins):
                    return ["row_sparse"] * node.num_outputs()
            # dot(csr, dense) and every other op: dense out (fallback)
            return ["default"] * node.num_outputs()

        stypes: Dict[Tuple[int, int], str] = {}
        arg_stypes, aux_stypes = [], []
        aux_ids = self._aux_node_ids()
        for node in self._topo_nodes():
            if node.is_variable:
                st = kwargs.get(node.name,
                                node.attrs.get("__storage_type__", "default"))
                stypes[(id(node), 0)] = st
                (aux_stypes if id(node) in aux_ids
                 else arg_stypes).append((node.name, st))
                continue
            ins = [stypes[(id(p), i)] for p, i in node.inputs]
            for i, st in enumerate(out_rule(node, ins)):
                stypes[(id(node), i)] = st
        arg_order = self.list_arguments()
        arg_map = dict(arg_stypes)
        out_stypes = [stypes[(id(n), i)] for n, i in self._outputs]
        return ([arg_map.get(n, "default") for n in arg_order], out_stypes,
                [st for _, st in aux_stypes])

    def _infer_structs(self, known_shapes: Dict[str, tuple], partial=False,
                       dtypes: Optional[Dict[str, str]] = None):
        """Forward shape propagation with param-shape completion.

        Rebuild of the InferShape pass (src/executor/infer_graph_attr_pass.cc):
        variables get shapes from ``known_shapes`` or from the consuming op's
        ``param_shapes`` hook; op output shapes come from jax.eval_shape.
        """
        dtypes = dtypes or {}
        vals: Dict[Tuple[int, int], jax.ShapeDtypeStruct] = {}
        var_structs: Dict[str, tuple] = {}
        rng = jax.random.PRNGKey(0)

        def var_struct(node):
            shape = known_shapes.get(node.name)
            if shape is None and node.name in var_structs:
                shape = var_structs[node.name]
            if shape is None and "__shape__" in node.attrs:
                # declared shape on the Variable itself participates in
                # inference (reference: mx.sym.var(shape=...) feeds the
                # InferShape pass) — but only when complete: dim 0 means
                # "unknown, infer me" (gluon deferred init passes these)
                declared = tuple(int(x)
                                 for x in _parse_tuple(node.attrs["__shape__"]))
                if declared and all(d > 0 for d in declared):
                    shape = declared
            if shape is None:
                return None
            dt = dtypes.get(node.name, node.attrs.get("__dtype__", "float32"))
            return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dt))

        for node in self._topo_nodes():
            if node.is_variable:
                s = var_struct(node)
                if s is not None:
                    vals[(id(node), 0)] = s
                    var_structs[node.name] = tuple(s.shape)
                continue
            in_structs = [vals.get((id(p), i)) for p, i in node.inputs]
            if node.op.param_shapes and any(s is None for s in in_structs):
                shapes = [tuple(s.shape) if s is not None else None
                          for s in in_structs]
                try:
                    filled = node.op.param_shapes(node.attrs, shapes)
                except (TypeError, KeyError, IndexError):
                    filled = shapes
                for i, ((p, pidx), s) in enumerate(zip(node.inputs, filled)):
                    if in_structs[i] is None and s is not None and p.is_variable:
                        dt = dtypes.get(p.name, "float32")
                        st = jax.ShapeDtypeStruct(tuple(s), jnp.dtype(dt))
                        vals[(id(p), pidx)] = st
                        var_structs[p.name] = tuple(s)
                        in_structs[i] = st
            if any(s is None for s in in_structs):
                if partial:
                    continue
                missing = [p.name for (p, _), s in zip(node.inputs, in_structs)
                           if s is None]
                raise MXNetError(
                    f"cannot infer shape: inputs {missing} of node "
                    f"{node.name} ({node.op.name}) unknown")
            call_attrs = dict(node.attrs)
            if node.op.needs_is_train:
                call_attrs["_is_train"] = False

            def f(*xs, _node=node, _attrs=call_attrs):
                args = (rng,) + xs if _node.op.needs_rng else xs
                out = _node.op.fn(*args, **_attrs)
                return out if isinstance(out, tuple) else (out,)

            try:
                outs = jax.eval_shape(f, *in_structs)
            except Exception as e:
                raise MXNetError(
                    f"shape inference failed at node {node.name} "
                    f"({node.op.name}): {e}") from e
            for i, o in enumerate(outs):
                vals[(id(node), i)] = o

        out_structs = {}
        for i, (node, idx) in enumerate(self._outputs):
            s = vals.get((id(node), idx))
            if s is None:
                if not partial:
                    return None
                out_structs[i] = None
            else:
                out_structs[i] = tuple(s.shape)
        return {"vars": var_structs, "outs": out_structs, "structs": vals}

    # -- binding -------------------------------------------------------------
    def simple_bind(self, ctx=None, grad_req="write", type_dict=None,
                    shared_exec=None, shared_buffer=None, group2ctx=None,
                    **kwargs):
        """Infer shapes, allocate arrays, return a bound Executor
        (reference: symbol.py:1250 → MXExecutorSimpleBind →
        GraphExecutor::Init, graph_executor.cc:934)."""
        from ..executor import Executor
        from ..ndarray import NDArray, zeros as nd_zeros

        arg_shapes, out_shapes, aux_shapes = self.infer_shape(**kwargs)
        arg_names = self.list_arguments()
        aux_names = self.list_auxiliary_states()
        type_dict = type_dict or {}

        def _shared(pool_attr, name, shape, dtype):
            # share same-name/shape/dtype arrays with the shared executor:
            # bucketing executors must see ONE set of parameter/grad
            # buffers (reference: shared data pool, graph_executor.cc:879)
            if shared_exec is None:
                return None
            arr = getattr(shared_exec, pool_attr).get(name)
            if arr is not None and tuple(arr.shape) == tuple(shape) \
                    and str(arr.dtype) == str(jnp.dtype(dtype)):
                return arr
            return None

        args = {}
        for name, shape in zip(arg_names, arg_shapes):
            dt = type_dict.get(name, "float32")
            arr = _shared("arg_dict", name, shape, dt)
            args[name] = arr if arr is not None else nd_zeros(shape, dtype=dt)
        aux = {}
        for name, shape in zip(aux_names, aux_shapes):
            dt = type_dict.get(name, "float32")
            arr = _shared("aux_dict", name, shape, dt)
            aux[name] = arr if arr is not None else nd_zeros(shape, dtype=dt)
        if isinstance(grad_req, str):
            grad_req = {n: grad_req for n in arg_names}
        elif isinstance(grad_req, (list, tuple)):
            grad_req = dict(zip(arg_names, grad_req))
        # storage-type inference for gradients: sparse_grad Embedding
        # weights get a row_sparse grad array up front, so the executor
        # writes through the bound array without changing its stype
        # (reference: MXExecutorSimpleBind infers grad stypes before
        # allocating, c_api_executor.cc:219)
        from ..executor import _is_placed, _sparse_grad_specs
        # the multi-device placed path keeps every gradient dense
        sparse_specs = ([] if _is_placed(group2ctx)
                        else _sparse_grad_specs(self, grad_req))
        rsp_grad_names = {s["w"] for s in sparse_specs}
        grads = {}
        for n, r in grad_req.items():
            if r == "null":
                continue
            arr = _shared("grad_dict", n, args[n].shape, str(args[n].dtype))
            if arr is not None:
                grads[n] = arr
            elif n in rsp_grad_names:
                from ..ndarray import sparse as _sparse
                grads[n] = _sparse.zeros("row_sparse", tuple(args[n].shape),
                                         dtype=str(args[n].dtype))
            else:
                grads[n] = nd_zeros(
                    args[n].shape, dtype=str(args[n].dtype))
        return Executor(self, ctx, args, grads, grad_req, aux,
                        shared_exec=shared_exec, group2ctx=group2ctx,
                        sparse_specs=sparse_specs)

    def bind(self, ctx=None, args=None, args_grad=None, grad_req="write",
             aux_states=None, group2ctx=None, shared_exec=None):
        """Bind with caller-provided arrays (reference: symbol.py:1514)."""
        from ..executor import Executor
        from ..ndarray import zeros as nd_zeros

        arg_names = self.list_arguments()
        aux_names = self.list_auxiliary_states()
        if isinstance(args, (list, tuple)):
            args = dict(zip(arg_names, args))
        if isinstance(args_grad, (list, tuple)):
            args_grad = dict(zip(arg_names, args_grad))
        if isinstance(aux_states, (list, tuple)):
            aux_states = dict(zip(aux_names, aux_states))
        args = dict(args or {})
        missing = set(arg_names) - set(args)
        if missing:
            raise MXNetError(f"bind missing arguments: {sorted(missing)}")
        if isinstance(grad_req, str):
            grad_req = {n: grad_req for n in arg_names}
        elif isinstance(grad_req, (list, tuple)):
            grad_req = dict(zip(arg_names, grad_req))
        if args_grad is None:
            # auto-allocated grads follow inferred storage types, like
            # simple_bind: sparse_grad Embedding weights get rsp arrays
            from ..executor import _is_placed, _sparse_grad_specs
            from ..ndarray import sparse as _sparse
            rsp_names = set() if _is_placed(group2ctx) else {
                s["w"] for s in _sparse_grad_specs(self, grad_req)}
            args_grad = {}
            for n, r in grad_req.items():
                if r == "null":
                    continue
                if n in rsp_names:
                    args_grad[n] = _sparse.zeros(
                        "row_sparse", tuple(args[n].shape),
                        dtype=str(args[n].dtype))
                else:
                    args_grad[n] = nd_zeros(args[n].shape,
                                            dtype=str(args[n].dtype))
        aux_states = dict(aux_states or {})
        for n in aux_names:
            if n not in aux_states:
                raise MXNetError(f"bind missing auxiliary state {n}")
        return Executor(self, ctx, args, args_grad, grad_req, aux_states,
                        shared_exec=shared_exec, group2ctx=group2ctx)

    # -- gradient graph ------------------------------------------------------
    def gradient(self, wrt: Sequence[str]) -> "Symbol":
        raise MXNetError("symbolic gradient graphs are implicit: bind and use "
                         "Executor.backward (jax.vjp under jit)")

    # -- serialization (MXNet graph-JSON compatible structure) ---------------
    def tojson(self) -> str:
        nodes = self._topo_nodes()
        nid = {id(n): i for i, n in enumerate(nodes)}
        out_nodes = []
        for node in nodes:
            entry = {
                "op": "null" if node.is_variable else node.op.name,
                "name": node.name,
                "inputs": [[nid[id(p)], i, 0] for p, i in node.inputs],
            }
            if node.op is not None:
                attrs = node.op.attr_spec.serialize(node.attrs)
            else:
                attrs = {k: str(v) for k, v in node.attrs.items()}
            if node.scope_attrs:
                attrs.update(node.scope_attrs)
            if attrs:
                entry["attrs"] = attrs
            out_nodes.append(entry)
        graph = {
            "nodes": out_nodes,
            "arg_nodes": [i for i, n in enumerate(nodes) if n.is_variable],
            "node_row_ptr": list(range(len(nodes) + 1)),
            "heads": [[nid[id(n)], i, 0] for n, i in self._outputs],
            "attrs": {"mxnet_version": ["int", 1100],
                      "mxnet_tpu_version": ["str", _libinfo_version()]},
        }
        return json.dumps(graph, indent=2)

    def save(self, fname: str):
        with open(fname, "w") as f:
            f.write(self.tojson())

    def debug_str(self):
        lines = []
        for node in self._topo_nodes():
            kind = "Variable" if node.is_variable else node.op.name
            ins = ", ".join(p.name for p, _ in node.inputs)
            lines.append(f"{kind} {node.name}({ins})")
        return "\n".join(lines)

    # -- eval convenience ----------------------------------------------------
    def eval(self, ctx=None, **kwargs):
        ex = self.bind(ctx=ctx, args=kwargs, grad_req="null")
        return ex.forward()


def Variable(name: str, attr=None, shape=None, lr_mult=None, wd_mult=None,
             dtype=None, init=None, stype=None, **kwargs) -> Symbol:
    """Create a symbolic variable (reference: symbol.py var/Variable)."""
    if not isinstance(name, str):
        raise TypeError("Expect a string for variable name")
    attrs = {}
    if shape is not None:
        attrs["__shape__"] = str(tuple(shape))
    if dtype is not None:
        attrs["__dtype__"] = str(dtype)
    if lr_mult is not None:
        attrs["__lr_mult__"] = str(lr_mult)
    if wd_mult is not None:
        attrs["__wd_mult__"] = str(wd_mult)
    if init is not None:
        attrs["__init__"] = init if isinstance(init, str) else init.dumps()
    if stype is not None:
        attrs["__storage_type__"] = str(stype)
    node = SymbolNode(None, name, attrs, [])
    if attr:
        node.scope_attrs.update({k: str(v) for k, v in attr.items()})
    node.scope_attrs.update({k: str(v) for k, v in kwargs.items()})
    return Symbol([(node, 0)])


var = Variable


def Group(symbols: Sequence[Symbol]) -> Symbol:
    outputs = []
    for s in symbols:
        outputs.extend(s._outputs)
    return Symbol(outputs)


def _libinfo_version() -> str:
    from ..libinfo import __version__ as v
    return v


def symbol_invoke(opdef: OpDef, inputs: Sequence[Symbol], attrs: Dict,
                  name: Optional[str]) -> Symbol:
    """Compose a new symbol node; auto-creates variables for missing
    parameter inputs (reference: nnvm symbol composition — missing inputs
    become variables named '{node}_{input}', e.g. 'fc1_weight')."""
    parsed = opdef.parse_attrs(attrs or {})
    name = NameManager.current.get(name, opdef.name)
    entries: List[Tuple[SymbolNode, int]] = []
    for s in inputs:
        if len(s._outputs) != 1:
            raise MXNetError(
                f"cannot compose {opdef.name} with a grouped symbol input")
        entries.append(s._outputs[0])

    input_names = opdef.input_names
    if input_names is None:
        # ops with attr-dependent arity (Custom: prop.list_arguments)
        dyn = getattr(opdef, "dynamic_input_names", None)
        if dyn is not None:
            input_names = dyn(parsed)
    if input_names and not opdef.key_var_num_args:
        n_expected = len(input_names)
        fill_names = input_names
        if opdef.num_inputs is None and opdef.input_names is not None:
            # variadic by attrs (e.g. no_bias drops bias; prelu adds gamma)
            n_expected = _expected_inputs(opdef, parsed)
            # attr-gated OPTIONAL inputs (CTCLoss lengths): positional
            # fill names would mislabel, e.g. use_label_lengths alone
            # must auto-name slot 2 'label_lengths', not 'data_lengths'
            dyn_fill = getattr(opdef, "dynamic_input_names", None)
            if dyn_fill is not None:
                fill_names = dyn_fill(parsed) or input_names
        while len(entries) < n_expected:
            in_name = fill_names[len(entries)]
            v = Variable(f"{name}_{in_name}")
            entries.append(v._outputs[0])
    if opdef.key_var_num_args and not parsed.get(opdef.key_var_num_args):
        parsed[opdef.key_var_num_args] = len(entries)
    node = SymbolNode(opdef, name, parsed, entries)
    return Symbol([(node, i) for i in range(node.num_outputs())])


def _expected_inputs(opdef: OpDef, attrs: Dict) -> int:
    # an op that names its inputs by its attrs has as many as it names
    names_by_attrs = getattr(opdef, "dynamic_input_names", None)
    if names_by_attrs is not None:
        return len(names_by_attrs(attrs))
    if opdef.name in ("Convolution", "Deconvolution", "FullyConnected"):
        return 2 if attrs.get("no_bias") else 3
    if opdef.name == "LeakyReLU":
        return 2 if attrs.get("act_type") == "prelu" else 1
    if opdef.name in ("SequenceLast", "SequenceMask", "SequenceReverse"):
        return 2 if attrs.get("use_sequence_length") else 1
    if opdef.name == "UpSampling":
        return int(attrs.get("num_args", 1) or 1)
    if opdef.name == "GroupedQueryAttention":
        return 4 if attrs.get("gated") else 3
    return len(opdef.input_names or ["data"])


def load_json(json_str: str) -> Symbol:
    """Parse a symbol JSON string, accepting both this package's output and
    the reference's on-disk formats: post-NNVM v0.11 ("attrs") and the
    pre-NNVM legacy layout ("param" for op params + "attr" for user attrs,
    upgraded there by src/nnvm/legacy_json_util.cc:203 LoadLegacyJSON;
    fixture: tests/python/unittest/save_000800.json)."""
    graph = json.loads(json_str)
    nodes: List[SymbolNode] = []
    for entry in graph["nodes"]:
        attrs = dict(entry.get("attrs") or entry.get("param") or {})
        # legacy user attrs (ctx_group, lr_mult, ...) ride separately
        attrs.update(entry.get("attr") or {})
        if entry["op"] == "null":
            # variables: dunder keys (__dtype__ etc.) are structural
            # attrs; everything else (ctx_group, lr_mult) is a user attr
            # read from scope_attrs (e.g. by PlaceDevice) — keep the
            # split symmetric with the op-node branch below
            node_attrs = {k: v for k, v in attrs.items()
                          if k.startswith("__")}
            node = SymbolNode(None, entry["name"], node_attrs, [])
            node.scope_attrs.update(
                {k: v for k, v in attrs.items() if not k.startswith("__")})
        else:
            opdef = get_op(entry["op"])
            known = {k: v for k, v in attrs.items()
                     if k in opdef.attr_spec.fields}
            scope = {k: v for k, v in attrs.items()
                     if k not in opdef.attr_spec.fields}
            parsed = opdef.parse_attrs(known)
            inputs = [(nodes[nid], out_idx)
                      for nid, out_idx, *_ in entry["inputs"]]
            node = SymbolNode(opdef, entry["name"], parsed, inputs)
            node.scope_attrs.update(scope)
        nodes.append(node)
    heads = [(nodes[nid], idx) for nid, idx, *_ in graph["heads"]]
    return Symbol(heads)


def load(fname: str) -> Symbol:
    with open(fname) as f:
        return load_json(f.read())
