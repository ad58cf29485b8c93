"""Python half of the training C ABI.

Reference surface: include/mxnet/c_api.h (146 flat functions; the
NDArray / imperative-invoke / Symbol / Executor / KVStore groups are the
training core every non-Python frontend binds — cpp-package/include/
mxnet-cpp/MxNetCpp.h, the scala/R/perl bindings). ``libmxtpu.so``
(src/capi/c_api.cc) embeds CPython and drives this module: the C layer
holds PyObject handles to the objects returned here and marshals
float32 buffers / strings / shape vectors at the boundary.

Design: same embedding pattern as the predict ABI (src/capi/
c_predict_api.cc) — one function here per C entry point group, shaped
so the C side stays thin. Since round 4 the data boundary is
dtype-native (raw bytes of the array's dtype, the reference's
contract), with dtype code 7 = bfloat16 extending the mshadow enum so
foreign frontends can train on the MXU-native dtype.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import ndarray as nd
from . import optimizer as _opt_mod
from . import symbol as _sym_mod
from .base import MXNetError
from .context import Context
from .kvstore import create as _kv_create
from .ndarray import NDArray
from .ops.registry import OP_TABLE

__all__ = [
    "nd_create", "nd_copy_from", "nd_copy_to", "nd_shape", "nd_save",
    "nd_load", "nd_wait", "nd_assign", "list_op_names",
    "imperative_invoke",
    "sym_create_variable", "sym_create_atomic", "sym_compose",
    "sym_from_json", "sym_to_json", "sym_list_arguments",
    "sym_list_outputs", "sym_list_aux", "sym_infer_shape", "executor_bind",
    "executor_forward", "executor_backward", "executor_outputs",
    "kv_create", "kv_init", "kv_push", "kv_pull", "kv_type",
    "kv_set_optimizer", "random_seed",
]


def _ctx(dev_type: int, dev_id: int) -> Context:
    # reference dev_type codes: 1 = cpu, 2 = gpu (here: the accelerator)
    return Context("cpu" if dev_type == 1 else "tpu", dev_id)


# -- NDArray group ---------------------------------------------------------

def nd_create(shape: Sequence[int], dev_type: int, dev_id: int) -> NDArray:
    return nd.zeros(tuple(int(s) for s in shape),
                    ctx=_ctx(dev_type, dev_id), dtype="float32")


def nd_copy_from(arr: NDArray, buf) -> None:
    """MXNDArraySyncCopyFromCPU: overwrite from a host float32 buffer.

    Goes through the standard write path (``arr[:] =``) so the value is
    device-placed exactly like every other mutation (a raw numpy store
    into ``_data`` would break wait_to_read and TPU placement)."""
    host = np.frombuffer(buf, np.float32).reshape(arr.shape)
    arr[:] = np.array(host)


def nd_assign(dst: NDArray, src: NDArray) -> None:
    """MXNDArrayAssign: device-to-device value copy (no host hop)."""
    dst._set_data(src._data.astype(dst._data.dtype))


def nd_copy_to(arr: NDArray) -> bytes:
    """MXNDArraySyncCopyToCPU: float32 bytes (this is the WaitToRead
    sync point — a host read forces completion)."""
    return np.ascontiguousarray(arr.asnumpy(), np.float32).tobytes()


def nd_shape(arr: NDArray) -> Tuple[int, ...]:
    return tuple(int(s) for s in arr.shape)


def nd_wait(arr: Optional[NDArray] = None) -> None:
    """MXNDArrayWaitToRead / MXNDArrayWaitAll."""
    if arr is not None:
        arr.wait_to_read()


def nd_save(fname: str, arrays: List[NDArray], keys: List[str]) -> None:
    nd.save(fname, dict(zip(keys, arrays)) if keys else list(arrays))


def nd_load(fname: str):
    """-> (keys, arrays); keys are '' for list-style files."""
    loaded = nd.load(fname)
    if isinstance(loaded, dict):
        ks = list(loaded)
        return ks, [loaded[k] for k in ks]
    return [""] * len(loaded), list(loaded)


# -- imperative invoke (MXImperativeInvoke) --------------------------------

def list_op_names() -> List[str]:
    return sorted(OP_TABLE)


def imperative_invoke(op_name: str, inputs: List[NDArray],
                      keys: List[str], vals: List[str]) -> List[NDArray]:
    """Invoke a registered op by name with string-form parameters
    (reference: MXImperativeInvoke, c_api_ndarray.cc:553 — parameters
    always cross the C boundary as strings and are parsed by the op's
    declared parameter struct; AttrSpec plays that role here)."""
    fn = getattr(nd, op_name, None)
    if fn is None:
        raise MXNetError(f"unknown operator {op_name!r}")
    out = fn(*inputs, **dict(zip(keys, vals)))
    return list(out) if isinstance(out, (list, tuple)) else [out]


# -- Symbol group ----------------------------------------------------------

class AtomicSymbol:
    """An op creator before composition (reference:
    MXSymbolCreateAtomicSymbol's AtomicSymbolCreator + the stored
    kwargs; composed into a graph node by MXSymbolCompose)."""

    def __init__(self, op_name: str, keys: List[str], vals: List[str]):
        if op_name not in OP_TABLE and not hasattr(_sym_mod, op_name):
            raise MXNetError(f"unknown operator {op_name!r}")
        self.op_name = op_name
        self.attrs = dict(zip(keys, vals))


def sym_create_variable(name: str):
    return _sym_mod.Variable(name)


def sym_create_atomic(op_name: str, keys: List[str], vals: List[str]):
    return AtomicSymbol(op_name, keys, vals)


def sym_compose(atomic: AtomicSymbol, name: str, arg_names: List[str],
                args: list):
    fn = getattr(_sym_mod, atomic.op_name)
    kwargs = dict(atomic.attrs)
    if name:
        kwargs["name"] = name
    if arg_names and any(arg_names):
        for n, a in zip(arg_names, args):
            kwargs[n] = a
        return fn(**kwargs)
    return fn(*args, **kwargs)


def sym_from_json(json_str: str):
    return _sym_mod.load_json(json_str)


def sym_to_json(sym) -> str:
    return sym.tojson()


def sym_list_arguments(sym) -> List[str]:
    return list(sym.list_arguments())


def sym_list_outputs(sym) -> List[str]:
    return list(sym.list_outputs())


def sym_list_aux(sym) -> List[str]:
    return list(sym.list_auxiliary_states())


def sym_infer_shape(sym, names: List[str], shapes: List[Sequence[int]]):
    """-> (arg_shapes, out_shapes, aux_shapes), each a list of tuples."""
    known = {n: tuple(int(x) for x in s) for n, s in zip(names, shapes)}
    arg, out, aux = sym.infer_shape(**known)
    return ([tuple(s) for s in arg], [tuple(s) for s in out],
            [tuple(s) for s in aux])


# -- Executor group --------------------------------------------------------

def executor_bind(sym, dev_type: int, dev_id: int, args: List[NDArray],
                  arg_grads: List[Optional[NDArray]],
                  grad_reqs: List[str], aux: List[NDArray]):
    """MXExecutorBindEX: caller-provided arrays, positional in
    list_arguments / list_auxiliary_states order."""
    grads = {n: g for n, g in zip(sym.list_arguments(), arg_grads)
             if g is not None}
    return sym.bind(ctx=_ctx(dev_type, dev_id), args=list(args),
                    args_grad=grads, grad_req=list(grad_reqs),
                    aux_states=list(aux))


def executor_forward(ex, is_train: int) -> None:
    ex.forward(is_train=bool(is_train))


def executor_backward(ex, head_grads: List[NDArray]) -> None:
    ex.backward(out_grads=list(head_grads) if head_grads else None)


def executor_outputs(ex) -> List[NDArray]:
    return list(ex.outputs)


# -- KVStore group ---------------------------------------------------------

def kv_create(kv_type: str):
    return _kv_create(kv_type)


def kv_type(kv) -> str:
    return kv.type


def kv_init(kv, keys: List[str], vals: List[NDArray]) -> None:
    kv.init(list(keys), list(vals))


def kv_push(kv, keys: List[str], vals: List[NDArray], priority: int) -> None:
    kv.push(list(keys), list(vals), priority=priority)


def kv_pull(kv, keys: List[str], outs: List[NDArray], priority: int) -> None:
    kv.pull(list(keys), out=list(outs), priority=priority)


def kv_set_optimizer(kv, opt_name: str, keys: List[str],
                     vals: List[str]) -> None:
    """MXKVStoreSetOptimizer analog: create a registered optimizer from
    string params and install it store-side (the reference pickles the
    optimizer to the servers; here the store runs it directly)."""
    params = {k: _parse_param_str(v) for k, v in zip(keys, vals)}
    kv.set_optimizer(_opt_mod.create(opt_name, **params))


def _parse_param_str(v: str):
    """String → typed optimizer param (reference: dmlc::Parameter typed
    field parsing). Booleans must be handled before the numeric guess —
    "False" is truthy as a string."""
    low = v.strip().lower()
    if low in ("true", "false"):
        return low == "true"
    try:
        return int(v)
    except ValueError:
        pass
    try:
        return float(v)
    except ValueError:
        return v


def random_seed(seed: int) -> None:
    from . import random as _random
    _random.seed(seed)


# =========================================================================
# Round-3 surface: autograd, CachedOp, DataIter, sparse NDArray, RecordIO,
# and the NDArray/Symbol/Executor/KVStore query tails — the groups every
# reference frontend binds (reference: c_api.h:717-760 autograd,
# :764-797 CachedOp, :1402-1461 DataIter, :298 sparse).
# =========================================================================

from . import autograd as _ag

# reference dtype codes (mshadow/base.h type enum, mirrored by every
# frontend's DType mapping)
_DTYPE_TO_CODE = {"float32": 0, "float64": 1, "float16": 2, "uint8": 3,
                  "int32": 4, "int8": 5, "int64": 6}
_CODE_TO_DTYPE = {v: k for k, v in _DTYPE_TO_CODE.items()}

# reference storage-type codes (python/mxnet/ndarray/ndarray.py
# _STORAGE_TYPE_STR_TO_ID)
_STYPE_TO_CODE = {"default": 0, "row_sparse": 1, "csr": 2}


def version() -> int:
    """MXGetVersion: MAJOR*10000 + MINOR*100 + PATCH."""
    from . import __version__
    parts = (__version__.split(".") + ["0", "0"])[:3]
    nums = [int("".join(c for c in p if c.isdigit()) or 0) for p in parts]
    return nums[0] * 10000 + nums[1] * 100 + nums[2]


# -- NDArray query/view tail ----------------------------------------------

def nd_dtype(arr: NDArray) -> int:
    return _DTYPE_TO_CODE[str(np.dtype(arr.dtype))]


def nd_context(arr: NDArray) -> Tuple[int, int]:
    ctx = arr.context
    return (1 if ctx.device_type == "cpu" else 2), ctx.device_id


def nd_reshape(arr: NDArray, shape: Sequence[int]) -> NDArray:
    return arr.reshape(tuple(int(s) for s in shape))


def nd_slice(arr: NDArray, start: int, stop: int) -> NDArray:
    return arr[int(start):int(stop)]


def nd_at(arr: NDArray, idx: int) -> NDArray:
    return arr[int(idx)]


def nd_get_grad(arr: NDArray) -> NDArray:
    g = arr.grad
    if g is None:
        raise MXNetError("NDArray has no gradient buffer: call "
                         "MXAutogradMarkVariables first")
    return g


def nd_detach(arr: NDArray) -> NDArray:
    return arr.detach()


def nd_to_bytes(arr: NDArray) -> bytes:
    """MXNDArraySaveRawBytes. Opaque round-trip format: little-endian
    header (ndim, dims..., dtype code) + raw buffer."""
    a = arr.asnumpy()
    code = _DTYPE_TO_CODE[str(a.dtype)]
    head = np.array([a.ndim] + list(a.shape) + [code], np.int64)
    return head.tobytes() + np.ascontiguousarray(a).tobytes()


def nd_from_bytes(buf) -> NDArray:
    raw = bytes(buf)
    ndim = int(np.frombuffer(raw[:8], np.int64)[0])
    head = np.frombuffer(raw[: 8 * (ndim + 2)], np.int64)
    shape = tuple(int(s) for s in head[1:1 + ndim])
    dtype = _CODE_TO_DTYPE[int(head[ndim + 1])]
    data = np.frombuffer(raw[8 * (ndim + 2):], dtype).reshape(shape)
    return nd.array(np.array(data), dtype=dtype)


# -- sparse NDArray group -------------------------------------------------

def nd_create_sparse(storage_type: int, shape: Sequence[int], dev_type: int,
                     dev_id: int, dtype: int,
                     aux_shapes: List[Sequence[int]]) -> NDArray:
    """MXNDArrayCreateSparseEx: an empty sparse array whose components are
    sized by ``aux_shapes`` (filled via nd_sync_copy_from_nd, the same
    create-then-fill flow the reference python frontend uses)."""
    from .ndarray import sparse as _sp
    dt = _CODE_TO_DTYPE[int(dtype)]
    shape = tuple(int(s) for s in shape)
    if storage_type == _STYPE_TO_CODE["row_sparse"]:
        nnz = int(aux_shapes[0][0]) if aux_shapes else 0
        return _sp.RowSparseNDArray(
            np.zeros((nnz,) + shape[1:], dt), np.zeros((nnz,), np.int64),
            shape)
    if storage_type == _STYPE_TO_CODE["csr"]:
        # aux order matches the reference: 0 = indptr, 1 = indices
        nnz = int(aux_shapes[1][0]) if len(aux_shapes) > 1 else 0
        return _sp.CSRNDArray(np.zeros((nnz,), dt),
                              np.zeros((nnz,), np.int64),
                              np.zeros((shape[0] + 1,), np.int64), shape)
    raise MXNetError(f"unknown sparse storage type code {storage_type}")


def nd_storage_type(arr: NDArray) -> int:
    return _STYPE_TO_CODE[getattr(arr, "stype", "default")]


def nd_data_component(arr: NDArray) -> NDArray:
    if nd_storage_type(arr) == 0:
        raise MXNetError("dense NDArray has no data component handle")
    return arr.data


def nd_aux_component(arr: NDArray, i: int) -> NDArray:
    from .ndarray.sparse import CSRNDArray, RowSparseNDArray
    if isinstance(arr, RowSparseNDArray):
        if i != 0:
            raise MXNetError("row_sparse has one aux array (0 = indices)")
        return arr.indices
    if isinstance(arr, CSRNDArray):
        if i == 0:
            return arr.indptr
        if i == 1:
            return arr.indices
        raise MXNetError("csr aux arrays: 0 = indptr, 1 = indices")
    raise MXNetError("dense NDArray has no aux components")


def nd_sync_copy_from_nd(dst: NDArray, src: NDArray, i: int) -> None:
    """MXNDArraySyncCopyFromNDArray: fill dst's data (i == -1) or aux
    component i from a dense src array."""
    import jax.numpy as jnp
    from .ndarray.sparse import CSRNDArray, RowSparseNDArray
    val = src._data
    if isinstance(dst, RowSparseNDArray):
        if i == -1:
            dst._d = jnp.asarray(val).astype(dst._sp_dtype)
        elif i == 0:
            dst._i = jnp.asarray(val, dtype=jnp.int32)
        else:
            raise MXNetError("row_sparse aux index must be 0")
        dst._dense = None
        return
    if isinstance(dst, CSRNDArray):
        if i == -1:
            dst._d = jnp.asarray(val).astype(dst._sp_dtype)
        elif i == 0:
            dst._p = jnp.asarray(val, dtype=jnp.int32)
        elif i == 1:
            dst._i = jnp.asarray(val, dtype=jnp.int32)
        else:
            raise MXNetError("csr aux index must be 0 (indptr) or 1")
        dst._dense = None
        return
    if i != -1:
        raise MXNetError("dense NDArray has no aux components")
    nd_assign(dst, src)


# -- autograd group -------------------------------------------------------

_GRAD_REQ_CODES = {0: "null", 1: "write", 2: "inplace", 3: "add"}


def autograd_set_recording(flag: int) -> int:
    return int(_ag.set_recording(bool(flag)))


def autograd_set_training(flag: int) -> int:
    return int(_ag.set_training(bool(flag)))


def autograd_is_recording() -> int:
    return int(_ag.is_recording())


def autograd_is_training() -> int:
    return int(_ag.is_training())


def autograd_mark_variables(variables: List[NDArray], reqs: List[int],
                            grads: List[NDArray]) -> None:
    _ag.mark_variables(variables, grads,
                       [_GRAD_REQ_CODES.get(int(r), "write") for r in reqs])


def autograd_backward(heads: List[NDArray], head_grads: List[NDArray],
                      retain_graph: int, is_train: int) -> None:
    hg = list(head_grads) if any(g is not None for g in head_grads) else None
    _ag.backward(list(heads), hg, retain_graph=bool(retain_graph),
                 train_mode=bool(is_train))


# -- CachedOp group -------------------------------------------------------

class CachedOp:
    """Reference: MXCreateCachedOp / MXInvokeCachedOp (c_api.h:764-797) —
    the per-block compiled graph behind gluon's hybridize. Here the symbol
    is traced once into one XLA program (jit cache keyed on input shapes
    by jax); inputs arrive positionally in list_arguments + aux order.

    Differentiable through the imperative tape: when autograd is
    recording, the invocation is taped as a single AGNode whose vjp is
    the whole compiled graph's vjp (the reference tapes each internal op;
    one fused node is the XLA-era equivalent)."""

    def __init__(self, sym):
        import jax as _jax
        from .executor import _ambient_mesh_key, build_graph_eval
        self.sym = sym
        self.arg_names = sym.list_arguments()
        self.aux_names = sym.list_auxiliary_states()
        self.n_outputs = len(sym.list_outputs())
        raw = build_graph_eval(sym)

        def eval_outputs(arg_vals, aux_vals, rng, is_train, mesh_key=None):
            outs, _aux = raw(arg_vals, aux_vals, rng, is_train)
            return outs

        self._fn = _jax.jit(eval_outputs, static_argnums=(3, 4))
        self._mesh_key = _ambient_mesh_key

    def _run(self, flat_vals, is_train, rng):
        n = len(self.arg_names)
        arg_vals = dict(zip(self.arg_names, flat_vals[:n]))
        aux_vals = dict(zip(self.aux_names, flat_vals[n:]))
        return self._fn(arg_vals, aux_vals, rng, bool(is_train),
                        self._mesh_key())

    def __call__(self, inputs: List[NDArray]) -> List[NDArray]:
        expected = len(self.arg_names) + len(self.aux_names)
        if len(inputs) != expected:
            raise MXNetError(
                f"CachedOp expects {expected} inputs "
                f"({len(self.arg_names)} args + {len(self.aux_names)} aux), "
                f"got {len(inputs)}")
        is_train = _ag.is_training()
        vals = [x._data for x in inputs]
        from . import random as _random
        rng = _random.next_key()
        outs = self._run(vals, is_train, rng)
        arrays = [NDArray(o) for o in outs]
        if _ag.is_recording():
            op = self

            class _CachedOpDef:
                name = "CachedOp"
                # the backward replay must see the SAME key the forward
                # used (dropout masks etc.); AGNode saves it because
                # needs_rng is set
                needs_rng = True
                differentiable = True
                grad_fn = None

                @staticmethod
                def fn(rng_key, *flat_vals):
                    return tuple(op._run(list(flat_vals), is_train,
                                         rng_key))

            node = _ag.AGNode(_CachedOpDef, {}, rng, list(inputs),
                              vals, len(arrays), [a._data for a in arrays])
            for i, a in enumerate(arrays):
                a._ag_node = node
                a._ag_out_index = i
        return arrays


def cached_op_create(sym) -> CachedOp:
    return CachedOp(sym)


def cached_op_invoke(op: CachedOp, inputs: List[NDArray]) -> List[NDArray]:
    return op(list(inputs))


# -- DataIter group -------------------------------------------------------

def _parse_iter_param(v: str):
    s = v.strip()
    if s.startswith("(") or s.startswith("["):
        from .base import AttrSpec
        return AttrSpec.PARSERS["tuple"](s)
    return _parse_param_str(s)


# name -> (factory, description). The reference's MXListDataIters surfaces
# the C++-registered iterators (MXNET_REGISTER_IO_ITER); these are the
# same user-facing set.
def _iter_registry():
    from . import io as _io
    return {
        "MNISTIter": (_io.MNISTIter, "MNIST ubyte-file iterator"),
        "CSVIter": (_io.CSVIter, "CSV file iterator"),
        "LibSVMIter": (_io.LibSVMIter, "LibSVM sparse-format iterator"),
        "ImageRecordIter": (_io.ImageRecordIter,
                            "RecordIO image iterator with augmentation"),
    }


def list_data_iters() -> List[str]:
    return sorted(_iter_registry())


def data_iter_info(name: str):
    import inspect
    fac, desc = _iter_registry()[name]
    params = inspect.signature(fac).parameters
    names, types, descs = [], [], []
    for p in params.values():
        if p.kind in (p.VAR_POSITIONAL, p.VAR_KEYWORD):
            continue
        names.append(p.name)
        default = "" if p.default is p.empty else f", default={p.default!r}"
        types.append(f"any{default}")
        descs.append("")
    return name, desc, names, types, descs


class _CIter:
    """C-side iterator state: the underlying DataIter + current batch."""

    def __init__(self, it):
        self.it = it
        self.batch = None


def data_iter_create(name: str, keys: List[str], vals: List[str]) -> _CIter:
    fac, _ = _iter_registry()[name]
    params = {k: _parse_iter_param(v) for k, v in zip(keys, vals)}
    return _CIter(fac(**params))


def data_iter_next(ci: _CIter) -> int:
    try:
        ci.batch = ci.it.next()
        return 1
    except StopIteration:
        ci.batch = None
        return 0


def data_iter_reset(ci: _CIter) -> None:
    ci.it.reset()
    ci.batch = None


def _current_batch(ci: _CIter):
    if ci.batch is None:
        raise MXNetError("no current batch: call MXDataIterNext first")
    return ci.batch


def data_iter_data(ci: _CIter) -> NDArray:
    return _current_batch(ci).data[0]


def data_iter_label(ci: _CIter) -> NDArray:
    return _current_batch(ci).label[0]


def data_iter_pad(ci: _CIter) -> int:
    return int(_current_batch(ci).pad or 0)


def data_iter_index(ci: _CIter) -> List[int]:
    idx = _current_batch(ci).index
    return [int(i) for i in idx] if idx is not None else []


# -- RecordIO group -------------------------------------------------------

def recordio_writer_create(uri: str):
    from .recordio import MXRecordIO
    return MXRecordIO(uri, "w")


def recordio_reader_create(uri: str):
    from .recordio import MXRecordIO
    return MXRecordIO(uri, "r")


def recordio_close(rec) -> None:
    rec.close()


def recordio_write(rec, buf) -> None:
    rec.write(bytes(buf))


def recordio_tell(rec) -> int:
    return int(rec.tell())


def recordio_read(rec):
    """-> bytes or None at EOF."""
    return rec.read()


def recordio_seek(rec, pos: int) -> None:
    rec.record.seek(int(pos))


# -- Symbol query tail ----------------------------------------------------

def sym_op_info(op_name: str):
    """MXSymbolGetAtomicSymbolInfo: (name, description, arg_names,
    arg_type_infos, arg_descriptions, key_var_num_args, return_type) —
    the metadata frontends use to code-generate their op namespaces
    (reference: every binding's op generator reads this)."""
    op = OP_TABLE.get(op_name)
    if op is None:
        raise MXNetError(f"unknown operator {op_name!r}")
    names, types, descs = [], [], []
    for k, (typ, default) in op.attr_spec.fields.items():
        names.append(k)
        from .base import AttrSpec
        if default is AttrSpec._REQUIRED:
            types.append(f"{typ}, required")
        else:
            types.append(f"{typ}, optional, default={default!r}")
        descs.append("")
    doc = (op.fn.__doc__ or "").strip().split("\n")[0]
    return (op_name, doc, names, types, descs,
            op.key_var_num_args or "", "NDArray-or-Symbol")


def sym_copy(sym):
    return sym.__copy__() if hasattr(sym, "__copy__") else _copy_sym(sym)


def _copy_sym(sym):
    return _sym_mod.load_json(sym.tojson())


def sym_get_name(sym) -> str:
    return sym.name or ""


def sym_get_attr(sym, key: str) -> Optional[str]:
    v = sym.attr(key)
    return None if v is None else str(v)


def sym_set_attr(sym, key: str, value: str) -> None:
    sym._set_attr(**{key: value})


def sym_list_attr(sym) -> List[str]:
    """Flattened [k0, v0, k1, v1, ...] of the output node's attributes
    (scope attrs + serialized op params, like the reference's
    MXSymbolListAttrShallow)."""
    node = sym._outputs[0][0]
    d = dict(node.scope_attrs)
    if node.op is not None:
        d.update(node.op.attr_spec.serialize(node.attrs))
    else:
        d.update({k: str(v) for k, v in node.attrs.items()})
    flat = []
    for k, v in sorted(d.items()):
        flat.extend([str(k), str(v)])
    return flat


def sym_get_internals(sym):
    return sym.get_internals()


def sym_get_output(sym, index: int):
    return sym[int(index)]


def sym_group(syms: list):
    return _sym_mod.Group(list(syms))


def sym_infer_type(sym, names: List[str], type_codes: List[int]):
    """-> (arg_codes, out_codes, aux_codes)."""
    known = {n: _CODE_TO_DTYPE[int(c)] for n, c in zip(names, type_codes)}
    arg, out, aux = sym.infer_type(**known)
    to_code = lambda ts: [_DTYPE_TO_CODE[str(np.dtype(t))] for t in ts]
    return to_code(arg), to_code(out), to_code(aux)


# -- Executor / KVStore tails ---------------------------------------------

def executor_print(ex) -> str:
    return ex.debug_str()


def kv_barrier(kv) -> None:
    kv.barrier()


def kv_rank(kv) -> int:
    return int(kv.rank)


def kv_group_size(kv) -> int:
    return int(kv.num_workers)


def kv_num_dead_node(kv, node_id: int, timeout_sec: int) -> int:
    return int(kv.num_dead_node(node_id, timeout_sec))


def kv_pull_row_sparse(kv, keys: List[str], outs: List[NDArray],
                       row_id_arrays: List[NDArray], priority: int) -> None:
    for k, out, rid in zip(keys, outs, row_id_arrays):
        kv.row_sparse_pull(k, out=out, priority=priority, row_ids=rid)


# =========================================================================
# Round-4 surface: the last third of the reference name set — dtype
# through the boundary (bf16 training from C), SimpleBind, the legacy
# Function group, profiler, Symbol file IO / queries, RTC, custom ops
# via C callbacks, monitor/updater callbacks, PS env.
# Reference: c_api.h:207-230 (profiler), :286-298 (CreateEx), :446-520
# (Function group), :972-1105 (Symbol IO/partial), :1149 (SimpleBind),
# :1236 (monitor), :1697 (CustomOp).
# =========================================================================

import ctypes as _ct
import os as _os

# TPU extension to the mshadow dtype enum: bfloat16 = 7 (codes 0-6 are
# the reference's; bf16 is the MXU-native training dtype so foreign
# frontends need it at the boundary)
_DTYPE_TO_CODE["bfloat16"] = 7
_CODE_TO_DTYPE[7] = "bfloat16"


def _np_dtype(name: str):
    if name == "bfloat16":
        import ml_dtypes
        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(name)


def nd_dtype_size(arr: NDArray) -> int:
    """Element size in bytes (the C side scales buffer lengths by it)."""
    return int(_np_dtype(str(arr.dtype) if not isinstance(arr.dtype, str)
                         else arr.dtype).itemsize)


def nd_create_ex(shape: Sequence[int], dev_type: int, dev_id: int,
                 dtype_code: int) -> NDArray:
    """MXNDArrayCreateEx: dtype carried through the boundary."""
    return nd.zeros(tuple(int(s) for s in shape),
                    ctx=_ctx(dev_type, dev_id),
                    dtype=_CODE_TO_DTYPE[int(dtype_code)])


def nd_create_none() -> NDArray:
    """MXNDArrayCreateNone: placeholder handle (0-d empty)."""
    return nd.zeros((), dtype="float32")


def nd_copy_from_ex(arr: NDArray, buf) -> None:
    """Dtype-honoring MXNDArraySyncCopyFromCPU: ``buf`` holds raw bytes
    of the array's own dtype (f32 arrays keep the old ABI behavior)."""
    dt = _np_dtype(str(np.dtype(arr.dtype)) if not isinstance(arr.dtype, str)
                   else arr.dtype)
    host = np.frombuffer(buf, dt).reshape(arr.shape)
    arr[:] = np.array(host)


def nd_copy_to_ex(arr: NDArray) -> bytes:
    """Dtype-honoring MXNDArraySyncCopyToCPU: bytes in the array's own
    dtype (bf16 arrays produce 2-byte elements)."""
    a = arr.asnumpy()
    return np.ascontiguousarray(a).tobytes()


def nd_aux_type(arr: NDArray, i: int) -> int:
    aux = nd_aux_component(arr, int(i))
    return _DTYPE_TO_CODE[str(np.dtype(aux.dtype))]


def nd_grad_state(arr: NDArray) -> int:
    """MXNDArrayGetGradState: the 'fresh gradient' flag the reference
    keeps per-array (ndarray.h entry state)."""
    return int(getattr(arr, "_fresh_grad", 0))


def nd_set_grad_state(arr: NDArray, state: int) -> None:
    arr._fresh_grad = int(state)


# -- legacy Function group (reference c_api.h:446-520) ---------------------
# FunctionHandle == the op registry entry; invoke writes results into the
# caller's mutate_vars, the old pre-imperative-invoke convention.

def func_describe(op_name: str):
    """-> (num_use_vars, num_scalars, num_mutate_vars, type_mask)."""
    entry = OP_TABLE.get(op_name)
    if entry is None:
        raise MXNetError(f"unknown function {op_name!r}")
    n_in = entry.num_inputs if isinstance(entry.num_inputs, int) else 1
    try:
        n_out = entry.num_outputs({})
    except Exception:
        n_out = 1
    return n_in, 0, n_out, 1  # kNDArrayArgBeforeScalar


def func_invoke(op_name: str, used: List[NDArray], scalars: List[float],
                mutated: List[NDArray], keys: List[str],
                vals: List[str]) -> None:
    """MXFuncInvoke(Ex): run the op on used_vars, store into
    mutate_vars (value assignment, preserving the caller's handles)."""
    outs = imperative_invoke(op_name, used, keys, vals)
    if len(outs) != len(mutated):
        raise MXNetError(
            f"{op_name}: {len(outs)} outputs for {len(mutated)} "
            "mutate_vars")
    for dst, src in zip(mutated, outs):
        nd_assign(dst, src)


# -- Symbol file IO + query tails ------------------------------------------

def sym_from_file(path: str):
    with open(path, "r") as f:
        return _sym_mod.load_json(f.read())


def sym_save_file(sym, path: str) -> None:
    with open(path, "w") as f:
        f.write(sym.tojson())


def sym_get_children(sym):
    """MXSymbolGetChildren: the direct inputs of the output node(s) as a
    grouped symbol (reference c_api_symbolic.cc sym->GetChildren)."""
    from .symbol.symbol import Symbol
    children = []
    seen = set()
    for node, _ in sym._outputs:
        if node.is_variable:
            continue
        for parent, idx in node.inputs:
            key = (id(parent), idx)
            if key in seen:
                continue
            seen.add(key)
            children.append(Symbol([(parent, idx)]))
    return _sym_mod.Group(children)


def sym_list_attr_full(sym) -> List[str]:
    """MXSymbolListAttr: recursive attr walk, flattened
    [name$key, val, ...] (the reference qualifies keys with the node
    name)."""
    out = []
    for node in sym._topo_nodes():
        merged = dict(node.scope_attrs)
        merged.update({k: str(v) for k, v in (node.attrs or {}).items()
                       if isinstance(v, (str, int, float, bool))})
        for k, v in sorted(merged.items()):
            out.extend([f"{node.name}${k}", str(v)])
    return out


def sym_print(sym) -> str:
    return sym.debug_str() if hasattr(sym, "debug_str") else str(sym)


def sym_infer_shape_partial(sym, names: List[str],
                            shapes: List[Sequence[int]]):
    """MXSymbolInferShapePartial: best-effort inference — unknown shapes
    come back empty instead of raising (reference c_api.h:1105)."""
    known = {n: tuple(int(x) for x in s) for n, s in zip(names, shapes)}
    try:
        arg, out, aux = sym.infer_shape_partial(**known)
    except AttributeError:
        try:
            arg, out, aux = sym.infer_shape(**known)
        except MXNetError:
            n_arg = len(sym.list_arguments())
            n_aux = len(sym.list_auxiliary_states())
            n_out = len(sym.list_outputs())
            return ([()] * n_arg, [()] * n_out, [()] * n_aux)
    def fix(ss):
        # unknown dims/shapes -> 0 entries / empty tuples (the
        # reference's 0-for-unknown convention)
        out_list = []
        for shp in ss:
            if not shp:
                out_list.append(())
            else:
                out_list.append(tuple(int(x) if x else 0 for x in shp))
        return out_list
    return fix(arg), fix(out), fix(aux)


def autograd_get_symbol(arr: NDArray):
    """MXAutogradGetSymbol: reconstruct a Symbol from the autograd tape
    behind ``arr`` (reference c_api.h:757). Leaf arrays become variables
    named var<k> in first-visit order."""
    node = getattr(arr, "_ag_node", None)
    if node is None:
        raise MXNetError("array is not the output of a recorded graph")
    memo = {}
    var_count = [0]

    def to_sym(nd_arr):
        ag = getattr(nd_arr, "_ag_node", None)
        if ag is None:
            key = id(nd_arr)
            if key not in memo:
                memo[key] = _sym_mod.Variable(f"var{var_count[0]}")
                var_count[0] += 1
            return memo[key]
        ag_node = ag
        out_idx = int(getattr(nd_arr, "_ag_out_index", 0) or 0)
        key = id(ag_node)
        if key not in memo:
            op_name = ag_node.opdef.name
            fn = getattr(_sym_mod, op_name, None)
            if fn is None:
                raise MXNetError(
                    f"op {op_name} has no symbol counterpart")
            ins = [to_sym(i) for i in ag_node.inputs]
            attrs = {k: v for k, v in (ag_node.attrs or {}).items()
                     if not k.startswith("_")}
            memo[key] = fn(*ins, **attrs)
        s = memo[key]
        return s[out_idx] if ag_node.n_outputs > 1 else s
    return to_sym(arr)


# -- Executor tails --------------------------------------------------------

def executor_backward_ex(ex, head_grads: List[NDArray],
                         is_train: int) -> None:
    # the executor's vjp always recomputes in train mode (matching
    # MXExecutorBackward); is_train=0 is accepted for ABI parity
    ex.backward(out_grads=list(head_grads) if head_grads else None)


def executor_simple_bind(sym, dev_type: int, dev_id: int,
                         shape_names: List[str],
                         shapes: List[Sequence[int]],
                         dtype_names: List[str], dtype_codes: List[int],
                         grad_req_names: List[str],
                         grad_req_types: List[str]):
    """MXExecutorSimpleBind: infer + allocate everything from provided
    shapes (reference c_api.h:1149 — the bind entry every frontend
    actually calls). grad reqs arrive as strings like the reference
    ("null"/"write"/"add"); a single unnamed entry sets the default.
    -> (executor, arg_names, args, grads_or_None, aux_names, auxs)."""
    kwargs = {n: tuple(int(x) for x in s)
              for n, s in zip(shape_names, shapes)}
    type_attrs = {n: _CODE_TO_DTYPE[int(c)]
                  for n, c in zip(dtype_names, dtype_codes)}
    grad_req = "write"
    named = {n: t for n, t in zip(grad_req_names, grad_req_types) if n}
    unnamed = [t for n, t in zip(grad_req_names, grad_req_types) if not n]
    if named:
        grad_req = named
    elif unnamed:
        grad_req = unnamed[0]
    ex = sym.simple_bind(_ctx(dev_type, dev_id), grad_req=grad_req,
                         type_dict=type_attrs or None, **kwargs)
    arg_names = list(sym.list_arguments())
    aux_names = list(sym.list_auxiliary_states())
    args = [ex.arg_dict[n] for n in arg_names]
    grads = [ex.grad_dict.get(n) for n in arg_names]
    auxs = [ex.aux_dict[n] for n in aux_names]
    return ex, arg_names, args, grads, aux_names, auxs


def executor_internal_outputs(ex):
    """(names, arrays) of every op output after the last forward — the
    MXExecutorSetMonitorCallback feed (the repo Monitor's mechanism)."""
    internals = ex.internal_outputs()
    names = list(internals)
    return names, [internals[n] for n in names]


# -- KVStore tails ---------------------------------------------------------

def kv_role() -> str:
    return _os.environ.get("DMLC_ROLE", "worker")


def kv_run_server(kv) -> None:
    """MXKVStoreRunServer: blocking server loop. The XLA-collective
    design has no separate server processes (SURVEY §2.5 — dist_sync
    runs reduce-scatter/all-gather over ICI/DCN); for non-worker roles
    this parks the process like the reference's server loop."""
    from .kvstore_server import KVStoreServer
    KVStoreServer(kv).run()


def kv_send_command(kv, head: int, body: str) -> None:
    """MXKVStoreSendCommmandToServers: optimizer/state commands. The
    collective design has no servers; commands that matter
    (set_optimizer) have first-class entry points, the rest are
    accepted and recorded."""
    if hasattr(kv, "send_command_to_servers"):
        kv.send_command_to_servers(head, body)


def _abi_lib():
    """Handle to libmxtpu.so for resolving its exported helpers. When
    the embedding host loaded it RTLD_GLOBAL (perl/C++ frontends),
    CDLL(None) finds the symbols; otherwise re-dlopen the library file
    (same handle, refcounted)."""
    try:
        lib = _ct.CDLL(None)
        lib.MXTPUWrapNDArrayForCallback
        return lib
    except (AttributeError, OSError):
        pass
    path = _os.path.join(_os.path.dirname(_os.path.abspath(__file__)),
                         "_lib", "libmxtpu.so")
    return _ct.CDLL(path)


def kv_set_updater(kv, fn_addr: int, user_addr: int) -> None:
    """MXKVStoreSetUpdater: install a C updater callback
    void (*)(int key, NDArrayHandle recv, NDArrayHandle local, void*).
    Handles are minted through the embedding library's exported
    MXTPUWrapNDArrayForCallback so the C callback sees real ABI handles
    it can pass to any MXNDArray* function (ownership stays here; the
    wrapper handles are freed after the callback returns)."""
    lib = _abi_lib()
    wrap = lib.MXTPUWrapNDArrayForCallback
    wrap.restype = _ct.c_void_p
    wrap.argtypes = [_ct.py_object]
    free = lib.MXNDArrayFree
    free.argtypes = [_ct.c_void_p]
    cb = _ct.CFUNCTYPE(None, _ct.c_int, _ct.c_void_p, _ct.c_void_p,
                       _ct.c_void_p)(fn_addr)

    def updater(key, recv, local):
        # the kvstore passes _str_to_int(key): ints stay ints, non-
        # numeric names stay strings -> map those through a stable crc
        try:
            ikey = int(key)
        except (TypeError, ValueError):
            import zlib
            ikey = zlib.crc32(str(key).encode()) & 0x7fffffff
        hr = wrap(recv)
        hl = wrap(local)
        try:
            cb(ikey, hr, hl, user_addr or None)
        finally:
            free(hr)
            free(hl)

    kv.set_updater(updater)


def init_ps_env(keys: List[str], vals: List[str]) -> None:
    for k, v in zip(keys, vals):
        _os.environ[str(k)] = str(v)


# -- profiler / misc -------------------------------------------------------

def profiler_set_config(mode: int, filename: str) -> None:
    """mode: reference mode2int — 0 = symbolic only, 1 = all."""
    from . import profiler
    profiler.profiler_set_config("all" if mode else "symbolic", filename)


def profiler_set_state(state: int) -> None:
    from . import profiler
    profiler.profiler_set_state("run" if state else "stop")


def profiler_dump(finished: int) -> None:
    from . import profiler
    profiler.dump_profile()


def set_num_omp_threads(n: int) -> None:
    _os.environ["OMP_NUM_THREADS"] = str(int(n))


def notify_shutdown() -> None:
    nd.waitall()


# -- RTC (reference c_api.h:1657-1692; Pallas playing NVRTC's role) --------

def rtc_create(name: str, in_names: List[str], out_names: List[str],
               in_arrays: List[NDArray], out_arrays: List[NDArray],
               kernel: str):
    from .rtc import Rtc
    return Rtc(name, list(zip(in_names, in_arrays)),
               list(zip(out_names, out_arrays)), kernel)


def rtc_push(rtc, ins: List[NDArray], outs: List[NDArray],
             gridx: int, gridy: int, gridz: int,
             blockx: int, blocky: int, blockz: int) -> None:
    rtc.push(list(ins), list(outs), (gridx, gridy, gridz),
             (blockx, blocky, blockz))


# -- custom ops from C callbacks (reference c_api.h:1697) ------------------
# Own callback protocol (the reference's MXCallbackList dance is CUDA-
# pointer-shaped); the semantics match: a C caller registers shape
# inference + forward (+ optional backward) and the op becomes available
# to every surface (imperative, Symbol, Executor, CachedOp). The host
# callbacks run under XLA via jax.pure_callback; backward is wired with
# jax.custom_vjp so the op trains.

_MAX_CUSTOM_NDIM = 8

_INFER_T = _ct.CFUNCTYPE(_ct.c_int, _ct.c_void_p, _ct.c_int,
                         _ct.POINTER(_ct.c_int), _ct.POINTER(_ct.c_uint),
                         _ct.POINTER(_ct.c_int), _ct.POINTER(_ct.c_uint))
_FWD_T = _ct.CFUNCTYPE(_ct.c_int, _ct.c_void_p, _ct.c_int,
                       _ct.POINTER(_ct.POINTER(_ct.c_float)),
                       _ct.POINTER(_ct.c_int), _ct.c_int,
                       _ct.POINTER(_ct.POINTER(_ct.c_float)),
                       _ct.POINTER(_ct.c_int))
_BWD_T = _ct.CFUNCTYPE(_ct.c_int, _ct.c_void_p, _ct.c_int,
                       _ct.POINTER(_ct.POINTER(_ct.c_float)),
                       _ct.POINTER(_ct.POINTER(_ct.c_float)),
                       _ct.POINTER(_ct.POINTER(_ct.c_float)),
                       _ct.POINTER(_ct.c_int), _ct.POINTER(_ct.c_int))


def _as_float_ptrs(arrays):
    bufs = [np.ascontiguousarray(a, np.float32) for a in arrays]
    ptrs = (_ct.POINTER(_ct.c_float) * len(bufs))(
        *[b.ctypes.data_as(_ct.POINTER(_ct.c_float)) for b in bufs])
    sizes = (_ct.c_int * len(bufs))(*[b.size for b in bufs])
    return bufs, ptrs, sizes


def custom_op_register(op_type: str, num_inputs: int, num_outputs: int,
                       infer_addr: int, fwd_addr: int, bwd_addr: int,
                       user_addr: int) -> None:
    """Register a C-callback op (MXCustomOpRegister). The host callbacks
    run under XLA via jax.pure_callback, on CPU and TPU alike."""
    import jax
    import jax.numpy as jnp
    from .ops.registry import register
    from .base import AttrSpec

    infer_cb = _INFER_T(infer_addr)
    fwd_cb = _FWD_T(fwd_addr)
    bwd_cb = _BWD_T(bwd_addr) if bwd_addr else None
    user = user_addr or None

    def infer_out_shapes(in_shapes):
        n = len(in_shapes)
        in_ndims = (_ct.c_int * n)(*[len(s) for s in in_shapes])
        flat = [d for s in in_shapes for d in s]
        in_flat = (_ct.c_uint * max(len(flat), 1))(*flat)
        out_ndims = (_ct.c_int * num_outputs)()
        out_flat = (_ct.c_uint * (num_outputs * _MAX_CUSTOM_NDIM))()
        rc = infer_cb(user, n, in_ndims, in_flat, out_ndims, out_flat)
        if rc != 0:
            raise MXNetError(f"{op_type}: infer_shape callback failed "
                             f"({rc})")
        shapes, k = [], 0
        for i in range(num_outputs):
            nd_i = out_ndims[i]
            # trace-time shape inference over host ctypes buffers — these
            # ints are static metadata, never tracer values
            shapes.append(tuple(int(out_flat[k + j]) for j in range(nd_i)))  # tpu-lint: disable=host-sync-under-trace
            k += _MAX_CUSTOM_NDIM
        return shapes

    def host_forward(*ins):
        in_bufs, in_ptrs, in_sizes = _as_float_ptrs(
            [np.asarray(a) for a in ins])
        out_shapes = infer_out_shapes([a.shape for a in ins])
        outs = [np.zeros(s, np.float32) for s in out_shapes]
        _, out_ptrs, out_sizes = _as_float_ptrs(outs)
        rc = fwd_cb(user, len(in_bufs), in_ptrs, in_sizes,
                    len(outs), out_ptrs, out_sizes)
        if rc != 0:
            raise MXNetError(f"{op_type}: forward callback failed ({rc})")
        return tuple(outs)

    def host_backward(ins, ograds):
        in_bufs, in_ptrs, in_sizes = _as_float_ptrs(
            [np.asarray(a) for a in ins])
        og_bufs, og_ptrs, og_sizes = _as_float_ptrs(
            [np.asarray(g) for g in ograds])
        igrads = [np.zeros(np.asarray(a).shape, np.float32) for a in ins]
        _, ig_ptrs, _ = _as_float_ptrs(igrads)
        rc = bwd_cb(user, len(in_bufs), in_ptrs, og_ptrs, ig_ptrs,
                    in_sizes, og_sizes)
        if rc != 0:
            raise MXNetError(f"{op_type}: backward callback failed ({rc})")
        return tuple(igrads)

    def impl(*ins):
        out_shapes = infer_out_shapes([tuple(a.shape) for a in ins])
        result_shape = tuple(
            jax.ShapeDtypeStruct(s, jnp.float32) for s in out_shapes)
        outs = jax.pure_callback(host_forward, result_shape,
                                 *[a.astype(jnp.float32) for a in ins])
        return tuple(outs)

    if bwd_cb is not None:
        core = jax.custom_vjp(impl)

        def fwd_rule(*ins):
            return impl(*ins), tuple(ins)

        def bwd_rule(res, cts):
            ins = res
            ig_shape = tuple(jax.ShapeDtypeStruct(tuple(a.shape),
                                                  jnp.float32) for a in ins)
            igs = jax.pure_callback(host_backward, ig_shape, ins,
                                    tuple(cts))
            return tuple(igs)

        core.defvjp(fwd_rule, bwd_rule)
        fn = core
    else:
        fn = impl

    def op_fn(*ins, **kw):
        out = fn(*ins)
        return out if num_outputs > 1 else out[0]

    register(op_type, num_inputs=num_inputs, num_outputs=num_outputs,
             attrs=AttrSpec(),
             differentiable=bwd_cb is not None)(op_fn)

    # late registration: the nd/sym namespace export loops ran at import,
    # so surface the new op on both frontends now
    from .ops.registry import OP_TABLE as _table
    opdef = _table[op_type]
    nd_mod = __import__("mxnet_tpu.ndarray", fromlist=["_make_op_func"])
    sym_mod = __import__("mxnet_tpu.symbol", fromlist=["_make_sym_func"])
    setattr(nd_mod, op_type, nd_mod._make_op_func(opdef, op_type))
    setattr(sym_mod, op_type, sym_mod._make_sym_func(opdef, op_type))


# -- custom autograd Function from C (reference c_api.h:1716) --------------

def custom_function_record(inputs: List[NDArray], outputs: List[NDArray],
                           bwd_addr: int, user_addr: int) -> List[NDArray]:
    """MXCustomFunctionRecord: tape a caller-computed mapping
    inputs -> outputs whose backward is a C callback with the _BWD_T
    layout (inputs, output grads, input grads). Returns the NEW taped
    output arrays — the C side re-points the caller's handles at them
    (the reference mutates the handles in place the same way)."""
    from . import autograd as ag
    bwd_cb = _BWD_T(bwd_addr)
    user = user_addr or None
    n_in = len(inputs)

    class _CFunction(ag.Function):
        def forward(self, *ins):
            return tuple(outputs)

        def backward(self, *ograds):
            in_np = [i.asnumpy() for i in inputs]
            og_np = [g.asnumpy() for g in ograds]
            # keep every cast buffer referenced until the C call returns
            in_bufs, in_ptrs, in_sizes = _as_float_ptrs(in_np)
            og_bufs, og_ptrs, og_sizes = _as_float_ptrs(og_np)
            igrads = [np.zeros(a.shape, np.float32) for a in in_np]
            ig_bufs, ig_ptrs, _ = _as_float_ptrs(igrads)
            igrads = ig_bufs
            rc = bwd_cb(user, n_in, in_ptrs, og_ptrs, ig_ptrs,
                        in_sizes, og_sizes)
            if rc != 0:
                raise MXNetError(
                    f"custom function backward failed ({rc})")
            return tuple(nd.array(g) for g in igrads)

    out = _CFunction()(*inputs)
    return list(out) if isinstance(out, tuple) else [out]
