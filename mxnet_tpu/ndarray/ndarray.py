"""NDArray: the framework's single value type, wrapping a jax.Array.

Reference analogue: include/mxnet/ndarray.h + src/ndarray/ndarray.cc — a
ref-counted asynchronous tensor whose Chunk owns a storage handle and an
engine variable. On TPU the engine collapses into XLA's async dispatch: a
jax.Array IS an async handle (dispatch returns immediately, forcing a value
blocks), so ``wait_to_read`` maps to ``block_until_ready`` and the
ThreadedVar versioning maps to this wrapper swapping in new immutable arrays
on mutation ("handle-with-version", SURVEY.md §7.3#1).
"""
from __future__ import annotations

from typing import Optional

import operator

import jax
import jax.numpy as jnp
import numpy as _np

from .. import autograd, random as _random
from ..base import MXNetError, numeric_types
from ..context import Context, current_context
from ..ops.registry import get_op

__all__ = ["NDArray", "imperative_invoke", "array", "empty", "zeros", "ones",
           "full", "arange", "concatenate", "moveaxis", "onehot_encode",
           "save", "load", "waitall", "zeros_like", "ones_like",
           "imdecode"]

_DTYPE_ALIASES = {
    None: jnp.float32,
}


def _as_jax(value, dtype=None, ctx: Optional[Context] = None):
    if isinstance(value, NDArray):
        arr = value._data
    elif isinstance(value, jax.Array):
        arr = value
    else:
        npv = _np.asarray(value, dtype=dtype)
        if npv.dtype == _np.float64 and dtype is None:
            npv = npv.astype(_np.float32)
        elif npv.dtype == _np.int64 and dtype is None:
            npv = npv.astype(_np.int32)
        arr = jnp.asarray(npv)
    if dtype is not None and arr.dtype != jnp.dtype(dtype):
        arr = arr.astype(jnp.dtype(dtype))
    if ctx is not None:
        dev = ctx.jax_device
        if dev is not None and arr.sharding.device_set != {dev}:
            arr = jax.device_put(arr, dev)
    return arr


def _ndarray_from_numpy(npv):
    return NDArray(jnp.asarray(npv))


class NDArray:
    """Multi-dimensional array with MXNet semantics over immutable jax arrays."""

    __slots__ = ("_data", "_ctx", "_grad_buf", "_grad_req", "_ag_node",
                 "_ag_out_index", "_version", "_fresh_grad", "__weakref__")

    # ensure ndarray <op> NDArray dispatches to us
    __array_priority__ = 100.0

    def __init__(self, data, ctx: Optional[Context] = None):
        if isinstance(data, NDArray):
            data = data._data
        self._data = data
        self._ctx = ctx
        self._grad_buf: Optional["NDArray"] = None
        self._grad_req = "null"
        self._ag_node = None
        self._ag_out_index = 0

    # -- core properties ----------------------------------------------------
    @property
    def shape(self):
        return tuple(self._data.shape)

    @property
    def dtype(self):
        return _np.dtype(str(self._data.dtype))

    @property
    def size(self):
        return int(self._data.size)

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def stype(self):
        return "default"

    @property
    def context(self) -> Context:
        if self._ctx is not None:
            return self._ctx
        try:
            dev = list(self._data.sharding.device_set)[0]
        except Exception:
            return current_context()
        if dev.platform == "cpu":
            return Context("cpu", dev.id)
        return Context("tpu", dev.id)

    ctx = context

    @property
    def grad(self) -> Optional["NDArray"]:
        return self._grad_buf

    @property
    def T(self) -> "NDArray":
        return self.transpose()

    # -- engine bridge ------------------------------------------------------
    def wait_to_read(self):
        """Reference: NDArray::WaitToRead (ndarray.h:336) — block until the
        async value is materialized."""
        self._data.block_until_ready()
        return self

    wait_to_write = wait_to_read

    def _set_data(self, new_data):
        # write-version counter: the python-level analogue of ThreadedVar's
        # version list (threaded_engine.h:95-213); used e.g. for stale-grad
        # detection in gluon.Trainer
        self._data = new_data
        self._version = self.version + 1

    @property
    def version(self) -> int:
        try:
            return self._version
        except AttributeError:
            return 0

    # -- conversion ---------------------------------------------------------
    def asnumpy(self) -> _np.ndarray:
        a = _np.asarray(jax.device_get(self._data))
        if not a.flags.writeable:
            # jax may hand back a read-only view of its host buffer; the
            # reference's asnumpy always yields an owned, writable copy
            # (callers mutate it, e.g. CustomOp backward)
            a = a.copy()
        return a

    def asscalar(self):
        if self.size != 1:
            raise MXNetError("The current array is not a scalar")
        return self.asnumpy().reshape(()).item()

    def astype(self, dtype) -> "NDArray":
        return imperative_invoke("cast", [self],
                                 {"dtype": _np.dtype(dtype).name})[0]

    def copy(self) -> "NDArray":
        return NDArray(self._data)

    def copyto(self, other):
        if isinstance(other, NDArray):
            if other is self:
                return other
            other._set_data(_as_jax(self._data, dtype=other.dtype,
                                    ctx=other._ctx))
            return other
        if isinstance(other, Context):
            return NDArray(_as_jax(self._data, ctx=other), ctx=other)
        raise MXNetError(f"cannot copy to {type(other)}")

    def as_in_context(self, ctx: Context) -> "NDArray":
        if ctx == self.context:
            return self
        return self.copyto(ctx)

    def tostype(self, stype):
        if stype == "default":
            return self
        from .sparse import cast_storage
        return cast_storage(self, stype)

    # -- autograd -----------------------------------------------------------
    def attach_grad(self, grad_req="write", stype=None):
        """Reference: gluon Parameter/NDArray.attach_grad — allocate a grad
        buffer and mark this array as a differentiation leaf."""
        self._ag_node = None
        self._mark_variable(zeros_like(self), grad_req)

    def _mark_variable(self, grad_nd, grad_req):
        self._grad_buf = grad_nd
        self._grad_req = grad_req

    def detach(self) -> "NDArray":
        return NDArray(self._data)

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        autograd.backward([self], [out_grad] if out_grad is not None else None,
                          retain_graph=retain_graph, train_mode=train_mode)

    # -- mutation -----------------------------------------------------------
    def __setitem__(self, key, value):
        if isinstance(value, NDArray):
            val = value._data
        elif isinstance(value, numeric_types):
            val = value
        else:
            val = _as_jax(value)
        if isinstance(key, slice) and key == slice(None):
            if isinstance(val, (int, float)):
                self._set_data(jnp.full_like(self._data, val))
            else:
                self._set_data(jnp.broadcast_to(
                    jnp.asarray(val, dtype=self._data.dtype), self.shape))
            return
        self._set_data(self._data.at[key].set(val))

    def __getitem__(self, key):
        # route the common indexing forms through taped ops so gradients
        # flow when indexing inside autograd.record() (reference: slicing
        # is an op — slice/slice_axis/take — not a raw view); outside
        # recording the raw jnp path is cheaper and bounds-checked the
        # numpy way
        if isinstance(key, NDArray):
            if autograd.is_recording():
                return imperative_invoke("take", [self, key], {"axis": 0})[0]
            return NDArray(self._data[key._data.astype(jnp.int32)])
        if autograd.is_recording() and 0 not in self.shape:
            taped = self._getitem_taped(key)
            if taped is not None:
                return taped
        return NDArray(self._data[key])  # fancy/stepped/eager: raw

    def _index_axis(self, ax, k):
        i = int(k)
        n = self.shape[ax]
        if i < -n or i >= n:
            raise IndexError(
                f"index {i} is out of bounds for axis {ax} with size {n}")
        return i + (n if i < 0 else 0)

    def _getitem_taped(self, key):
        if isinstance(key, (bool, _np.bool_)):
            if key:
                # x[True] == x[None]: new leading axis, taped
                return imperative_invoke("expand_dims", [self],
                                         {"axis": 0})[0]
            return None  # x[False]: empty result, raw path (no grads)
        if isinstance(key, (int, _np.integer)):
            i = self._index_axis(0, key)
            out = imperative_invoke("slice_axis", [self],
                                    {"axis": 0, "begin": i,
                                     "end": i + 1})[0]
            if self.ndim > 1:
                return out.reshape(self.shape[1:])
            # 1-D: scalar result; sum of the 1-element slice keeps the tape
            return imperative_invoke("sum", [out], {})[0]
        if isinstance(key, slice) and key.step in (None, 1):
            b, e, _ = key.indices(self.shape[0])
            return imperative_invoke("slice_axis", [self],
                                     {"axis": 0, "begin": b, "end": e})[0]
        if isinstance(key, tuple) and all(
                (isinstance(k, (int, _np.integer))
                 and not isinstance(k, (bool, _np.bool_)))
                or (isinstance(k, slice) and k.step in (None, 1))
                for k in key) and len(key) <= self.ndim:
            begin, end, drop = [], [], []
            for ax, k in enumerate(key):
                if isinstance(k, (int, _np.integer)):
                    i = self._index_axis(ax, k)
                    begin.append(i)
                    end.append(i + 1)
                    drop.append(ax)
                else:
                    b, e, _ = k.indices(self.shape[ax])
                    if e <= b:
                        return None  # empty slice: numpy-shaped raw path
                    begin.append(b)
                    end.append(e)
            out = imperative_invoke("slice", [self],
                                    {"begin": tuple(begin),
                                     "end": tuple(end)})[0]
            if drop:
                shape = [s for ax, s in enumerate(out.shape)
                         if ax not in drop]
                if not shape:
                    # scalar: taped sum of the 1-element slice
                    return imperative_invoke("sum", [out], {})[0]
                out = imperative_invoke("reshape", [out],
                                        {"shape": tuple(shape)})[0]
            return out
        return None

    # -- python protocol ----------------------------------------------------
    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __bool__(self):
        if self.size == 1:
            return bool(self.asscalar())
        raise ValueError("ambiguous truth value of multi-element NDArray")

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __repr__(self):
        return f"{self.asnumpy()!r}\n<NDArray {'x'.join(map(str, self.shape))} @{self.context}>"

    def __hash__(self):
        return id(self)

    def __reduce__(self):
        # pickle via numpy (used by optimizer-state checkpointing; reference:
        # Updater.get_states pickling for kvstore servers)
        return (_ndarray_from_numpy, (self.asnumpy(),))

    # -- arithmetic (dispatches through the op table so autograd tapes it) ---
    def _binop(self, other, op, scalar_op, reverse=False):
        if isinstance(other, NDArray):
            a, b = (other, self) if reverse else (self, other)
            name = op if a.shape == b.shape else "broadcast_" + op.split("_")[-1]
            return imperative_invoke(name, [a, b], {})[0]
        if isinstance(other, numeric_types):
            return imperative_invoke(scalar_op, [self], {"scalar": other})[0]
        return NotImplemented

    def __add__(self, other):
        return self._binop(other, "elemwise_add", "_plus_scalar")

    __radd__ = __add__

    def __sub__(self, other):
        return self._binop(other, "elemwise_sub", "_minus_scalar")

    def __rsub__(self, other):
        if isinstance(other, numeric_types):
            return imperative_invoke("_rminus_scalar", [self], {"scalar": other})[0]
        return self._binop(other, "elemwise_sub", "_minus_scalar", reverse=True)

    def __mul__(self, other):
        return self._binop(other, "elemwise_mul", "_mul_scalar")

    __rmul__ = __mul__

    def __truediv__(self, other):
        return self._binop(other, "elemwise_div", "_div_scalar")

    __div__ = __truediv__

    def __rtruediv__(self, other):
        if isinstance(other, numeric_types):
            return imperative_invoke("_rdiv_scalar", [self], {"scalar": other})[0]
        return self._binop(other, "elemwise_div", "_div_scalar", reverse=True)

    __rdiv__ = __rtruediv__

    def __mod__(self, other):
        return self._binop(other, "broadcast_mod", "_mod_scalar")

    def __rmod__(self, other):
        if isinstance(other, numeric_types):
            return imperative_invoke("_rmod_scalar", [self], {"scalar": other})[0]
        return self._binop(other, "broadcast_mod", "_mod_scalar", reverse=True)

    def __pow__(self, other):
        return self._binop(other, "_power", "_power_scalar")

    def __rpow__(self, other):
        if isinstance(other, numeric_types):
            return imperative_invoke("_rpower_scalar", [self], {"scalar": other})[0]
        return NotImplemented

    def __neg__(self):
        return imperative_invoke("negative", [self], {})[0]

    def __abs__(self):
        return imperative_invoke("abs", [self], {})[0]

    def _cmp(self, other, op):
        if isinstance(other, NDArray):
            return imperative_invoke("broadcast_" + op, [self, other], {})[0]
        return imperative_invoke(f"_{op}_scalar", [self], {"scalar": other})[0]

    def __eq__(self, other):
        if other is None:
            return False
        return self._cmp(other, "equal")

    def __ne__(self, other):
        if other is None:
            return True
        return self._cmp(other, "not_equal")

    def __gt__(self, other):
        return self._cmp(other, "greater")

    def __ge__(self, other):
        return self._cmp(other, "greater_equal")

    def __lt__(self, other):
        return self._cmp(other, "lesser")

    def __le__(self, other):
        return self._cmp(other, "lesser_equal")

    # in-place mutate the handle (reference: engine write on the same var)
    def __iadd__(self, other):
        out = self.__add__(other)
        self._set_data(out._data)
        return self

    def __isub__(self, other):
        out = self.__sub__(other)
        self._set_data(out._data)
        return self

    def __imul__(self, other):
        out = self.__mul__(other)
        self._set_data(out._data)
        return self

    def __itruediv__(self, other):
        out = self.__truediv__(other)
        self._set_data(out._data)
        return self

    __idiv__ = __itruediv__

    # -- convenience method forms of common ops -----------------------------
    def reshape(self, shape=None, *args):
        if args:
            shape = (shape,) + args
        if isinstance(shape, int):
            shape = (shape,)
        # route through the op so the autograd tape sees it
        return imperative_invoke("reshape", [self], {"shape": shape})[0]

    def broadcast_to(self, shape):
        return imperative_invoke("broadcast_to", [self], {"shape": shape})[0]

    def transpose(self, axes=None):
        return imperative_invoke("transpose", [self],
                                 {"axes": tuple(axes) if axes else ()})[0]

    def swapaxes(self, dim1, dim2):
        return imperative_invoke("swapaxes", [self],
                                 {"dim1": dim1, "dim2": dim2})[0]

    def flatten(self):
        return imperative_invoke("Flatten", [self], {})[0]

    def expand_dims(self, axis):
        return imperative_invoke("expand_dims", [self], {"axis": axis})[0]

    def slice_axis(self, axis, begin, end):
        return imperative_invoke("slice_axis", [self],
                                 {"axis": axis, "begin": begin, "end": end})[0]

    def _reduce(self, name, axis=None, keepdims=False):
        if isinstance(axis, int):
            axis = (axis,)
        return imperative_invoke(name, [self],
                                 {"axis": axis, "keepdims": keepdims})[0]

    def sum(self, axis=None, keepdims=False, **kw):
        return self._reduce("sum", axis, keepdims)

    def mean(self, axis=None, keepdims=False, **kw):
        return self._reduce("mean", axis, keepdims)

    def prod(self, axis=None, keepdims=False, **kw):
        return self._reduce("prod", axis, keepdims)

    def max(self, axis=None, keepdims=False, **kw):
        return self._reduce("max", axis, keepdims)

    def min(self, axis=None, keepdims=False, **kw):
        return self._reduce("min", axis, keepdims)

    def argmax(self, axis=None, keepdims=False):
        return imperative_invoke("argmax", [self],
                                 {"axis": axis, "keepdims": keepdims})[0]

    def argmin(self, axis=None, keepdims=False):
        return imperative_invoke("argmin", [self],
                                 {"axis": axis, "keepdims": keepdims})[0]

    def clip(self, a_min, a_max):
        return imperative_invoke("clip", [self],
                                 {"a_min": a_min, "a_max": a_max})[0]

    def abs(self):
        return self.__abs__()

    def square(self):
        return imperative_invoke("square", [self], {})[0]

    def sqrt(self):
        return imperative_invoke("sqrt", [self], {})[0]

    def norm(self):
        return imperative_invoke("norm", [self], {})[0]

    def sign(self):
        return imperative_invoke("sign", [self], {})[0]

    def log(self):
        return imperative_invoke("log", [self], {})[0]

    def exp(self):
        return imperative_invoke("exp", [self], {})[0]

    def split(self, num_outputs, axis=1, squeeze_axis=False):
        out = imperative_invoke("SliceChannel", [self],
                                {"num_outputs": num_outputs, "axis": axis,
                                 "squeeze_axis": squeeze_axis})
        return list(out) if len(out) > 1 else out[0]

    def take(self, indices, axis=0, mode="clip"):
        return imperative_invoke("take", [self, indices],
                                 {"axis": axis, "mode": mode})[0]

    def one_hot(self, depth, **kw):
        return imperative_invoke("one_hot", [self], dict(depth=depth, **kw))[0]

    def as_nd_ndarray(self):
        return self

    def tolist(self):
        return self.asnumpy().tolist()


# ---------------------------------------------------------------------------
# imperative invoke — the rebuild of MXImperativeInvoke
# (src/c_api/c_api_ndarray.cc:553 → ImperativeInvokeImpl:486): parse attrs,
# run the jax computation (async dispatch), wrap outputs, tape for autograd.
# ---------------------------------------------------------------------------


def imperative_invoke(op_name, inputs, attrs, out=None):
    opdef = get_op(op_name) if isinstance(op_name, str) else op_name
    parsed = opdef.parse_attrs(attrs or {})
    vals = [x._data if isinstance(x, NDArray) else _as_jax(x) for x in inputs]

    call_attrs = dict(parsed)
    if opdef.key_var_num_args and not call_attrs.get(opdef.key_var_num_args):
        call_attrs[opdef.key_var_num_args] = len(inputs)
    is_train = autograd.is_training()
    if opdef.needs_is_train:
        call_attrs["_is_train"] = is_train
    if opdef.stateful:
        call_attrs["_op_state"] = {}
    rng = None
    from .. import profiler as _profiler
    with _profiler.span(opdef.name, cat="operator", kind="imperative"):
        if opdef.needs_rng:
            rng = _random.next_key()
            outputs = opdef.fn(rng, *vals, **call_attrs)
        else:
            outputs = opdef.fn(*vals, **call_attrs)
    if not isinstance(outputs, tuple):
        outputs = (outputs,)

    # write back auxiliary-state updates (e.g. BatchNorm moving stats)
    if opdef.aux_update and is_train:
        for out_idx, in_idx in opdef.aux_update.items():
            tgt = inputs[in_idx]
            if isinstance(tgt, NDArray):
                tgt._set_data(outputs[out_idx])

    n_visible = opdef.num_outputs(parsed)
    visible = outputs[:n_visible] if len(outputs) > n_visible else outputs

    out_arrays = [NDArray(o) for o in visible]

    if autograd.is_recording() and opdef.differentiable:
        nd_inputs = [x if isinstance(x, NDArray) else NDArray(v)
                     for x, v in zip(inputs, vals)]
        # record the FULL output list (incl. hidden aux outputs, e.g.
        # BatchNorm moving stats) so backward's vjp cotangent structure
        # matches fn's return; heads only ever index the visible prefix
        node = autograd.AGNode(opdef, call_attrs, rng, nd_inputs, vals,
                               len(outputs), list(outputs))
        for i, o in enumerate(out_arrays):
            o._ag_node = node
            o._ag_out_index = i

    if out is not None:
        outs = out if isinstance(out, (list, tuple)) else [out]
        for tgt, src in zip(outs, out_arrays):
            tgt._set_data(_as_jax(src._data, dtype=tgt.dtype))
        return list(outs)
    return out_arrays


# ---------------------------------------------------------------------------
# creation / io functions (reference: python/mxnet/ndarray/ndarray.py
# module-level functions + MXNDArraySave/Load in src/c_api/c_api.cc)
# ---------------------------------------------------------------------------


def array(source_array, ctx=None, dtype=None) -> NDArray:
    return NDArray(_as_jax(source_array, dtype=dtype, ctx=ctx), ctx=ctx)


def empty(shape, ctx=None, dtype=None) -> NDArray:
    return zeros(shape, ctx=ctx, dtype=dtype)


def zeros(shape, ctx=None, dtype=None, **kw) -> NDArray:
    if isinstance(shape, int):
        shape = (shape,)
    return NDArray(jnp.zeros(shape, dtype=jnp.dtype(dtype or "float32")), ctx=ctx)


def ones(shape, ctx=None, dtype=None, **kw) -> NDArray:
    if isinstance(shape, int):
        shape = (shape,)
    return NDArray(jnp.ones(shape, dtype=jnp.dtype(dtype or "float32")), ctx=ctx)


def full(shape, val, ctx=None, dtype=None) -> NDArray:
    if isinstance(shape, int):
        shape = (shape,)
    return NDArray(jnp.full(shape, val, dtype=jnp.dtype(dtype or "float32")), ctx=ctx)


def zeros_like(other: NDArray) -> NDArray:
    return NDArray(jnp.zeros_like(other._data))


def ones_like(other: NDArray) -> NDArray:
    return NDArray(jnp.ones_like(other._data))


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype=None) -> NDArray:
    return imperative_invoke("_arange", [], {
        "start": start, "stop": stop, "step": step, "repeat": repeat,
        "dtype": dtype or "float32"})[0]


def concatenate(arrays, axis=0, always_copy=True) -> NDArray:
    return NDArray(jnp.concatenate([a._data for a in arrays], axis=axis))


def moveaxis(tensor, source, destination) -> NDArray:
    return NDArray(jnp.moveaxis(tensor._data, source, destination))


def onehot_encode(indices, out):
    depth = out.shape[1]
    res = imperative_invoke("one_hot", [indices], {"depth": depth})[0]
    out._set_data(res._data)
    return out


def waitall():
    """Reference: MXNDArrayWaitAll — drain the async engine."""
    jax.effects_barrier()


def imdecode(str_img, clip_rect=(0, 0, 0, 0), out=None, index=0, channels=3,
             mean=None):
    """Decode an image buffer (reference: mx.nd.imdecode, src/io/image_io.cc)."""
    import io as _io
    try:
        from PIL import Image
    except ImportError as e:  # pragma: no cover
        raise MXNetError("imdecode requires PIL") from e
    img = Image.open(_io.BytesIO(str_img))
    if channels == 3:
        img = img.convert("RGB")
    arr = _np.asarray(img, dtype=_np.float32)
    nd = array(arr)
    if out is not None:
        out._set_data(nd._data)
        return out
    return nd


# -- serialization ----------------------------------------------------------


def save(fname: str, data):
    """Save NDArrays (reference: mx.nd.save / MXNDArraySave). Uses the .npz
    container; the reference's binary container format is CUDA-era and is
    deliberately not reproduced."""
    if isinstance(data, NDArray):
        arrays = {"0": data.asnumpy()}
    elif isinstance(data, (list, tuple)):
        arrays = {str(i): d.asnumpy() for i, d in enumerate(data)}
    elif isinstance(data, dict):
        arrays = {k: v.asnumpy() for k, v in data.items()}
    else:
        raise MXNetError("save expects NDArray, list or dict")
    # pass a file object so np.savez keeps the exact filename (it appends
    # .npz to bare paths, breaking reference-style ``prefix-0000.params``)
    with open(fname, "wb") as f:
        _np.savez(f, **arrays)


def load(fname: str):
    with _np.load(fname if fname.endswith(".npz") else fname) as f:
        keys = list(f.keys())
        if all(k.isdigit() for k in keys):
            return [array(f[k]) for k in sorted(keys, key=int)]
        return {k: array(f[k]) for k in keys}


# ---------------------------------------------------------------------------
# Module-level arithmetic helpers (reference ndarray.py: add/subtract/... via
# _ufunc_helper — array·array dispatches to the broadcast op, array·scalar to
# the scalar op, scalar·scalar to the python operator).
# ---------------------------------------------------------------------------

def _table_op(name):
    from ..ops.registry import OP_TABLE
    opdef = OP_TABLE[name]

    def f(*args, **kw):
        res = imperative_invoke(opdef, list(args), kw)
        return res[0] if len(res) == 1 else res
    return f


def _ufunc_helper(lhs, rhs, fn_array, fn_scalar, lfn_scalar,
                  rfn_scalar=None):
    """Dispatch helper mirroring reference ndarray.py:_ufunc_helper."""
    if isinstance(lhs, numeric_types):
        if isinstance(rhs, numeric_types):
            return fn_scalar(lhs, rhs)
        if rfn_scalar is None:
            # commutative
            return _table_op(lfn_scalar)(rhs, scalar=float(lhs))
        return _table_op(rfn_scalar)(rhs, scalar=float(lhs))
    if isinstance(rhs, numeric_types):
        return _table_op(lfn_scalar)(lhs, scalar=float(rhs))
    if isinstance(rhs, NDArray):
        return _table_op(fn_array)(lhs, rhs)
    raise TypeError(f"type {type(rhs)} not supported")


def add(lhs, rhs):
    """Element-wise sum with broadcasting (reference ndarray.py add)."""
    return _ufunc_helper(lhs, rhs, "broadcast_add", operator.add,
                         "_plus_scalar")


def subtract(lhs, rhs):
    return _ufunc_helper(lhs, rhs, "broadcast_sub", operator.sub,
                         "_minus_scalar", "_rminus_scalar")


def multiply(lhs, rhs):
    return _ufunc_helper(lhs, rhs, "broadcast_mul", operator.mul,
                         "_mul_scalar")


def divide(lhs, rhs):
    return _ufunc_helper(lhs, rhs, "broadcast_div", operator.truediv,
                         "_div_scalar", "_rdiv_scalar")


true_divide = divide


def modulo(lhs, rhs):
    return _ufunc_helper(lhs, rhs, "broadcast_mod", operator.mod,
                         "_mod_scalar", "_rmod_scalar")


def power(base, exp):
    return _ufunc_helper(base, exp, "broadcast_power", operator.pow,
                         "_power_scalar", "_rpower_scalar")


def maximum(lhs, rhs):
    return _ufunc_helper(lhs, rhs, "broadcast_maximum",
                         lambda x, y: x if x > y else y, "_maximum_scalar")


def minimum(lhs, rhs):
    return _ufunc_helper(lhs, rhs, "broadcast_minimum",
                         lambda x, y: x if x < y else y, "_minimum_scalar")


def equal(lhs, rhs):
    return _ufunc_helper(lhs, rhs, "broadcast_equal",
                         lambda x, y: 1.0 if x == y else 0.0,
                         "_equal_scalar")


def not_equal(lhs, rhs):
    return _ufunc_helper(lhs, rhs, "broadcast_not_equal",
                         lambda x, y: 1.0 if x != y else 0.0,
                         "_not_equal_scalar")


def greater(lhs, rhs):
    return _ufunc_helper(lhs, rhs, "broadcast_greater",
                         lambda x, y: 1.0 if x > y else 0.0,
                         "_greater_scalar", "_lesser_scalar")


def greater_equal(lhs, rhs):
    return _ufunc_helper(lhs, rhs, "broadcast_greater_equal",
                         lambda x, y: 1.0 if x >= y else 0.0,
                         "_greater_equal_scalar", "_lesser_equal_scalar")


def lesser(lhs, rhs):
    return _ufunc_helper(lhs, rhs, "broadcast_lesser",
                         lambda x, y: 1.0 if x < y else 0.0,
                         "_lesser_scalar", "_greater_scalar")


def lesser_equal(lhs, rhs):
    return _ufunc_helper(lhs, rhs, "broadcast_lesser_equal",
                         lambda x, y: 1.0 if x <= y else 0.0,
                         "_lesser_equal_scalar", "_greater_equal_scalar")
