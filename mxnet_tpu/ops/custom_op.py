"""The ``Custom`` operator: frontend-defined ops with python callbacks.

Reference surface: src/operator/custom/custom.cc (+ custom-inl.h) and
python/mxnet/operator.py — ``CustomOp``/``CustomOpProp`` subclasses
registered by name, invoked as ``mx.nd.Custom(..., op_type=name)`` or
``mx.sym.Custom``. The reference runs the python callbacks on a dedicated
worker thread inside the engine; the TPU-native equivalent is
``jax.pure_callback`` (host callback with declared output shapes, so the
op embeds in jitted XLA programs), wrapped in ``jax.custom_vjp`` so the
user's ``backward`` drives autograd exactly like the reference's
FGradient hook.
"""
from __future__ import annotations

from typing import Dict, Type

import jax
import jax.numpy as jnp
import numpy as np

from ..base import MXNetError
from .registry import OP_TABLE, OpDef

CUSTOM_OP_REGISTRY: Dict[str, Type] = {}


def _as_ndarrays(np_arrays):
    from .. import ndarray as nd
    return [nd.array(a) for a in np_arrays]


_PROP_CACHE: Dict[tuple, object] = {}


def _instantiate(op_type: str, kwargs):
    if op_type not in CUSTOM_OP_REGISTRY:
        raise MXNetError(
            f"Custom op type {op_type!r} not registered; known: "
            f"{sorted(CUSTOM_OP_REGISTRY)}")
    # the reference passes all kwargs to the prop as strings (custom.cc
    # stores them as key/value strings); props are declarative, so one
    # instance per (type, kwargs) signature is reused across calls
    key = (op_type, tuple(sorted((k, str(v)) for k, v in kwargs.items())))
    prop = _PROP_CACHE.get(key)
    if prop is None or CUSTOM_OP_REGISTRY[op_type] is not type(prop):
        prop = CUSTOM_OP_REGISTRY[op_type](
            **{k: str(v) for k, v in kwargs.items()})
        _PROP_CACHE[key] = prop
    return prop


class _CustomCall:
    """Resolved shapes/types + the two numpy-level callbacks for one call.

    ``op_state``: a per-invocation holder dict (tape-carried for the
    imperative path) in which the created operator instance lives, so
    state stashed on ``self`` in forward() is visible in that same call's
    backward() — the reference's OpStatePtr semantics. Without a holder the
    instance is kept on this object (one per trace for the symbolic path).
    """

    def __init__(self, op_type, kwargs, in_shapes, in_types, is_train,
                 op_state=None):
        self.prop = _instantiate(op_type, kwargs)
        self.op_type = op_type
        self.op_state = op_state if op_state is not None else {}
        if self.prop.list_auxiliary_states():
            raise MXNetError(
                f"Custom({op_type}): auxiliary states "
                f"({self.prop.list_auxiliary_states()}) are not supported "
                "by the Custom bridge — keep state on the operator instance "
                "or pass it as an explicit input")
        self.n_in = len(self.prop.list_arguments())
        self.n_out = len(self.prop.list_outputs())
        if len(in_shapes) != self.n_in:
            raise MXNetError(
                f"Custom({op_type}): expected {self.n_in} inputs "
                f"({self.prop.list_arguments()}), got {len(in_shapes)}")
        self.in_shapes = [tuple(s) for s in in_shapes]
        self.in_types = list(in_types)
        shapes = self.prop.infer_shape(self.in_shapes)
        self.out_shapes = [tuple(s) for s in shapes[1]]
        types = self.prop.infer_type(self.in_types)
        self.out_types = list(types[1])
        self.is_train = bool(is_train)

    def _operator(self):
        op = self.op_state.get("op")
        if op is None:
            op = self.prop.create_operator(None, self.in_shapes,
                                           self.in_types)
            self.op_state["op"] = op
        return op

    def fwd_cb(self, *np_in):
        from .. import ndarray as nd
        out_nd = [nd.zeros(s, dtype=t)
                  for s, t in zip(self.out_shapes, self.out_types)]
        self._operator().forward(
            is_train=self.is_train, req=["write"] * self.n_out,
            in_data=_as_ndarrays(np_in), out_data=out_nd, aux=[])
        return tuple(o.asnumpy().astype(t, copy=False)
                     for o, t in zip(out_nd, self.out_types))

    def bwd_cb(self, *arrs):
        from .. import ndarray as nd
        a = list(arrs)
        ig_nd = [nd.zeros(s, dtype=t)
                 for s, t in zip(self.in_shapes, self.in_types)]
        self._operator().backward(
            req=["write"] * self.n_in,
            in_data=_as_ndarrays(a[:self.n_in]),
            out_data=_as_ndarrays(a[self.n_in:self.n_in + self.n_out]),
            out_grad=_as_ndarrays(a[self.n_in + self.n_out:]),
            in_grad=ig_nd, aux=[])
        return tuple(g.asnumpy() for g in ig_nd)


def _split_attrs(attrs):
    kwargs = {k: v for k, v in attrs.items()
              if k not in ("op_type", "_is_train", "_op_state")}
    return attrs["op_type"], kwargs, attrs.get("_is_train", False)


def _custom_fn(*inputs, op_type, _is_train=False, _op_state=None, **kwargs):
    call = _CustomCall(op_type, kwargs, [x.shape for x in inputs],
                       [x.dtype for x in inputs], _is_train,
                       op_state=_op_state)
    n_out = call.n_out
    traced = any(isinstance(x, jax.core.Tracer) for x in inputs)
    if not traced:
        # eager path: run the python callback directly, no host callback
        outs = tuple(jnp.asarray(o)
                     for o in call.fwd_cb(*[np.asarray(x) for x in inputs]))
        return outs if n_out > 1 else outs[0]

    # traced path (symbolic executor / jit): embed as a host callback with
    # declared result shapes; custom_vjp routes autodiff to the user's
    # backward. Host callbacks run on CPU and TPU alike (chip_smoke.py
    # drives one traced CustomOp on the chip).
    out_sds = tuple(jax.ShapeDtypeStruct(s, np.dtype(t))
                    for s, t in zip(call.out_shapes, call.out_types))
    in_sds = tuple(jax.ShapeDtypeStruct(s, np.dtype(t))
                   for s, t in zip(call.in_shapes, call.in_types))

    @jax.custom_vjp
    def run(*xs):
        return jax.pure_callback(call.fwd_cb, out_sds, *xs)

    def run_fwd(*xs):
        outs = run(*xs)
        return outs, (xs, outs)

    def run_bwd(res, gouts):
        xs, outs = res
        gin = jax.pure_callback(call.bwd_cb, in_sds, *xs, *outs, *gouts)
        return tuple(gin)

    run.defvjp(run_fwd, run_bwd)
    outs = run(*inputs)
    return outs if n_out > 1 else outs[0]


def _custom_grad_fn(attrs, rng, input_vals, out_vals, out_cts):
    """Direct tape gradient (autograd hook): runs the user's backward
    callback on concrete values, sidestepping jax.vjp retracing — this is
    what lets Custom ops train on backends without host callbacks."""
    op_type, kwargs, is_train = _split_attrs(attrs)
    call = _CustomCall(op_type, kwargs, [x.shape for x in input_vals],
                       [x.dtype for x in input_vals], is_train,
                       op_state=attrs.get("_op_state"))
    arrs = [np.asarray(x) for x in (*input_vals, *out_vals, *out_cts)]
    return tuple(jnp.asarray(g) for g in call.bwd_cb(*arrs))


class _CustomOpDef(OpDef):
    """OpDef whose attrs pass through (arbitrary kwargs go to the prop)."""

    def parse_attrs(self, raw_attrs):
        if "op_type" not in raw_attrs:
            raise MXNetError("Custom requires op_type=<registered name>")
        return dict(raw_attrs)

    def num_outputs(self, attrs):
        op_type, kwargs, _ = _split_attrs(attrs)
        return len(_instantiate(op_type, kwargs).list_outputs())

    def dynamic_input_names(self, attrs):
        """Input arity/names come from the registered prop — lets symbol
        composition auto-create missing inputs (reference: the composer
        creates e.g. 'softmax_label' for Custom loss layers)."""
        op_type, kwargs, _ = _split_attrs(attrs)
        return list(_instantiate(op_type, kwargs).list_arguments())


def _custom_param_shapes(attrs, shapes):
    """Fill auto-created input shapes (e.g. the label of a loss-style
    Custom op) from the prop's infer_shape — the symbol-side half of the
    reference's two-way InferShape for Custom (custom-inl.h)."""
    op_type, kwargs, _ = _split_attrs(attrs)
    prop = _instantiate(op_type, kwargs)
    known = [s for s in shapes if s is not None]
    if not known:
        return shapes
    probe = [tuple(s) if s is not None else tuple(known[0])
             for s in shapes]
    in_shapes = prop.infer_shape(probe)[0]
    return [tuple(s) if s is not None else tuple(in_shapes[i])
            for i, s in enumerate(shapes)]


def _register_custom():
    op = _CustomOpDef(
        "Custom", _custom_fn, num_inputs=None, needs_is_train=True,
        output_names=["output"], grad_fn=_custom_grad_fn, stateful=True,
        param_shapes=_custom_param_shapes)
    OP_TABLE["Custom"] = op


_register_custom()
