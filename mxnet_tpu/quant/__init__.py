"""The low-precision tier (docs/how_to/quantization.md).

Two halves, one motivation: halving precision doubles effective TFLOPS
(the direct lever on the ROADMAP MFU gap) and quarters the bytes the
serving tier queues, pads, and dispatches.

* **Int8 post-training-quantized serving** (:mod:`.ptq`,
  :mod:`.calibration`): calibrate per-tensor scales from a handful of
  representative batches (sidecar-snapshotted, manifest-covered),
  quantize weights + activations at ``as_serving_backend()``/Predictor
  load through the compiler's annotate slot (the quant signature joins
  every persistent program key), and gate on *measured* accuracy — a
  model beyond the threshold ships fp32 with a typed
  :class:`QuantAccuracyWarning`, never silently wrong. fp8-ready: the
  format registry (:data:`~.core.FORMATS`) carries ``fp8_e4m3`` too.
* **Measured low-precision training** (:mod:`.loss_scale` + the
  ``MXTPU_PRECISION=bf16`` mode in :mod:`mxnet_tpu.perf` /
  ``SPMDTrainer``): the bf16-master-weight compute cast as a
  first-class training mode with a dynamic loss-scale guard traced into
  the donated step — finite streaks grow the scale, overflow backs it
  off, and a non-finite step is SKIPPED (params/state bitwise
  unchanged), all device-side.
"""
from __future__ import annotations

from .calibration import (CalibrationStats, calibrate,  # noqa: F401
                          load_stats, save_stats)
from .core import (DEFAULT_MAX_DELTA, FORMATS, QuantConfig,  # noqa: F401
                   QuantFormat, dequantize, host_scale, quant_scope,
                   quantize, quantize_host, scale_for)
from .loss_scale import DynamicLossScale, LossScaleConfig  # noqa: F401
from .ptq import (QuantAccuracyWarning, QuantizedModuleBackend,  # noqa: F401
                  QuantReport, integer_semantics_inputs,
                  measure_accuracy_delta, quantize_backend,
                  quantized_backend_from_artifact)

__all__ = ["QuantConfig", "QuantFormat", "FORMATS", "quantize",
           "quantize_host", "host_scale",
           "dequantize", "scale_for", "quant_scope", "DEFAULT_MAX_DELTA",
           "CalibrationStats", "calibrate", "save_stats", "load_stats",
           "QuantAccuracyWarning", "QuantReport", "QuantizedModuleBackend",
           "quantize_backend", "quantized_backend_from_artifact",
           "integer_semantics_inputs", "measure_accuracy_delta",
           "LossScaleConfig", "DynamicLossScale"]
