"""Model backends the serving runtime can front.

A backend is anything with ``load()`` (parse/bind, may raise
:class:`~mxnet_tpu.base.MXNetError` on corrupt artifacts — the server
guards it behind the ``serving.load`` fault site + retry policy) and
``infer(arrays) -> [np.ndarray, ...]`` where ``arrays`` maps input name
to a host batch whose leading axis is the batch dimension.

Three adapters cover the tree's inference surfaces:

- :class:`CallableBackend` — any python callable (tests, toy smoke).
- :class:`PredictorBackend` — the C predict ABI surface
  (:class:`~mxnet_tpu.c_predict.Predictor`): one bound executor per
  declared bucket size, created at ``load()``/warm-up so live requests
  never compile.
- :class:`ModuleBackend` — a bound :class:`~mxnet_tpu.module.Module`
  driven forward-only (also reachable as
  ``module.as_serving_backend()``).
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from ..base import MXNetError

__all__ = ["CallableBackend", "PredictorBackend", "ModuleBackend",
           "SymbolicJitBackend"]


class CallableBackend:
    """Wrap ``fn(arrays: dict) -> list[np.ndarray] | np.ndarray``.

    The keyword-only flags are the *ragged capability declarations*
    (mxnet_tpu/serving/ragged.py) any backend object may carry — the
    server only activates a ragged rung on backends that declare it:

    - ``accepts_mask``/``mask_name`` — the forward consumes a 0/1 row
      mask input (pad rows are mask-dead, not zero-compute-full-cost);
    - ``pack_axis``/``accepts_segment_ids``/``segment_name`` — the
      forward consumes packed rows along ``pack_axis`` (>= 1, an axis
      of the *batched* arrays) with an int32 segment-id plane, enabling
      sequence packing in the coalescer;
    - ``lengths_name`` — which input carries per-row real lengths, so
      pad-waste accounting can count tokens on the dense leg;
    - ``supports_symbolic_batch`` — the forward runs ANY row count
      through one program (no per-batch-size specialization), so the
      server can skip batch-axis padding and collapse bucket warm-up;
    - ``input_dtypes`` — per-input dtype overrides for warm-up probes
      (default float32), e.g. int32 lengths.
    """

    def __init__(self, fn: Callable, input_name: str = "data",
                 input_specs: Optional[Dict[str, Sequence[int]]] = None,
                 input_dtypes: Optional[Dict[str, object]] = None,
                 accepts_mask: bool = False, mask_name: str = "mask",
                 pack_axis: Optional[int] = None,
                 accepts_segment_ids: bool = False,
                 segment_name: str = "segment_ids",
                 lengths_name: Optional[str] = None,
                 supports_symbolic_batch: bool = False):
        self.fn = fn
        self.input_name = input_name
        # name -> per-row shape, used by bucketed warm-up probes
        self.input_specs = ({k: tuple(v) for k, v in input_specs.items()}
                            if input_specs else {input_name: ()})
        if input_dtypes:
            self.input_dtypes = {k: np.dtype(v)
                                 for k, v in input_dtypes.items()}
        self.accepts_mask = accepts_mask
        self.mask_name = mask_name
        self.pack_axis = pack_axis
        self.accepts_segment_ids = accepts_segment_ids
        self.segment_name = segment_name
        self.lengths_name = lengths_name
        self.supports_symbolic_batch = supports_symbolic_batch

    def load(self):
        pass

    def infer(self, arrays: Dict[str, np.ndarray]) -> List[np.ndarray]:
        out = self.fn(arrays)
        if isinstance(out, np.ndarray):
            return [out]
        return list(out)


class SymbolicJitBackend:
    """Serve a jax-jittable ``fn({name: array}) -> [array, ...]``
    through ONE symbolic-batch program
    (:class:`~mxnet_tpu.compiler.symbolic.SymbolicBatchProgram`).

    ``load()`` exports the program with the leading dim symbolic up to
    ``max_rows`` and sets ``supports_symbolic_batch``; a function that
    cannot be exported that way raises there."""

    def __init__(self, fn: Callable, max_rows: int,
                 input_specs: Dict[str, Sequence[int]],
                 input_dtypes: Optional[Dict[str, object]] = None,
                 input_name: Optional[str] = None):
        self.fn = fn
        self.max_rows = int(max_rows)
        self.input_specs = {k: tuple(v) for k, v in input_specs.items()}
        self.input_name = input_name or sorted(self.input_specs)[0]
        if input_dtypes:
            self.input_dtypes = {k: np.dtype(v)
                                 for k, v in input_dtypes.items()}
        self.supports_symbolic_batch = False
        self.program = None

    def load(self):
        from ..compiler.symbolic import SymbolicBatchProgram
        self.program = SymbolicBatchProgram(
            self.fn, self.input_specs, self.max_rows,
            input_dtypes=getattr(self, "input_dtypes", None))
        self.supports_symbolic_batch = True

    def infer(self, arrays: Dict[str, np.ndarray]) -> List[np.ndarray]:
        if self.program is None:
            self.load()
        return self.program(arrays)


class PredictorBackend:
    """Serve a symbol-JSON + .params artifact through the C predict ABI
    python half. Each batch-size bucket gets its own bound
    :class:`~mxnet_tpu.c_predict.Predictor` (fixed shapes are the whole
    point of bucketed warm-up); ``load()`` validates the artifact bytes
    eagerly so corruption surfaces at startup, not mid-traffic."""

    def __init__(self, symbol_json: str, param_bytes: bytes,
                 row_shape: Sequence[int], input_name: str = "data",
                 dev_type: int = 1, dev_id: int = 0):
        self.symbol_json = symbol_json
        self.param_bytes = param_bytes
        self.row_shape = tuple(int(d) for d in row_shape)
        self.input_name = input_name
        self.input_specs = {input_name: self.row_shape}
        self.dev_type = dev_type
        self.dev_id = dev_id
        self._predictors: Dict[int, object] = {}
        self._loaded = False

    def load(self):
        """Validate the artifact (symbol JSON + param bytes). Raises
        MXNetError on corrupt/truncated inputs."""
        from .. import c_predict
        from .. import symbol as _sym
        c_predict._params_from_bytes(self.param_bytes)
        _sym.load_json(self.symbol_json)
        self._loaded = True

    def bind_bucket(self, batch_size: int):
        """Create (or return) the bound executor for one bucket size —
        this is where the trace+compile cost lands, at warm-up."""
        from .. import c_predict
        if batch_size not in self._predictors:
            self._predictors[batch_size] = c_predict.Predictor(
                self.symbol_json, self.param_bytes,
                self.dev_type, self.dev_id,
                {self.input_name: (batch_size,) + self.row_shape})
        return self._predictors[batch_size]

    def infer(self, arrays: Dict[str, np.ndarray]) -> List[np.ndarray]:
        batch = arrays[self.input_name]
        pred = self.bind_bucket(int(batch.shape[0]))
        buf = np.ascontiguousarray(batch, np.float32)
        pred.set_input(self.input_name, memoryview(buf.reshape(-1)),
                       buf.shape)
        pred.forward()
        outs = []
        for i in range(pred.num_outputs()):
            shape = pred.output_shape(i)
            out = np.empty(int(np.prod(shape, dtype=np.int64)), np.float32)
            pred.get_output(i, memoryview(out))
            outs.append(out.reshape(shape))
        return outs


class ModuleBackend:
    """Forward-only adapter over a bound, initialized Module."""

    def __init__(self, module, input_name: Optional[str] = None):
        self.module = module
        names = [d[0] for d in module.data_shapes]
        self.input_names = names
        self.input_name = input_name or names[0]
        # every declared input, so multi-input modules warm up whole
        self.input_specs = {d[0]: tuple(d[1][1:])
                            for d in module.data_shapes}
        self.row_shape = self.input_specs[self.input_name]

    def load(self):
        if not (self.module.binded and self.module.params_initialized):
            raise MXNetError(
                "ModuleBackend needs a bound module with initialized "
                "params (bind + init_params/set_params first)")

    def infer(self, arrays: Dict[str, np.ndarray]) -> List[np.ndarray]:
        from .. import ndarray as nd
        from ..io import DataBatch
        data = [nd.array(np.ascontiguousarray(arrays[name], np.float32))
                for name in self.input_names]
        self.module.forward(DataBatch(data=data), is_train=False)
        return [o.asnumpy() for o in self.module.get_outputs()]
