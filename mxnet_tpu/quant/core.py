"""Quantization core: formats, config, and the compiler annotate hook.

The low-precision tier's nncase-shaped contract (PAPERS.md, arxiv
2512.21571): post-training quantization is a *deployment* decision —
made once at ``as_serving_backend()``/Predictor load, calibrated from a
handful of representative batches, and gated on measured accuracy —
never a per-model hand edit. The rewrite therefore rides the compiler's
``annotate`` pass slot (PR 7 built exactly this hook, the TVM-style
seam of arxiv 1802.04799): :class:`quant_scope` makes a
:class:`QuantConfig` ambient around ``compiler.optimize``, the
registered annotator stamps which parameters quantize (and the config
signature) into the IR annotations, and
``OptimizeResult.transform_sig`` carries ``quant=<sig>`` into every
persistent program key built from it — the compilation cache can never
serve a stale-precision executable, exactly as PR 9's ``sharding_sig``
guarantees for layouts.

Formats are a registry (:data:`FORMATS`) so the int8 path and a future
fp8 path share every seam: per-tensor symmetric scales, saturating
round-to-nearest quantize, widening dequantize. ``int8`` is the shipped
format; ``fp8_e4m3`` registers when the jax build exposes the dtype and
reuses the same scale/clip machinery (fp8-ready by design, not by
forking the pipeline).
"""
from __future__ import annotations

import hashlib
import threading
from typing import Dict, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from ..base import MXNetError, getenv

__all__ = ["QuantFormat", "FORMATS", "QuantConfig", "quantize",
           "dequantize", "scale_for", "quant_scope", "current_quant",
           "DEFAULT_MAX_DELTA"]

# the accuracy gate's default bound: mean relative output error of the
# quantized path vs fp32 on the calibration batches (MXTPU_QUANT_MAX_DELTA
# overrides; docs/how_to/quantization.md)
DEFAULT_MAX_DELTA = 0.05


class QuantFormat:
    """One low-precision number format: storage dtype + symmetric range.

    ``qmax`` is the largest representable magnitude after scaling
    (symmetric: the quantized range is [-qmax, qmax], keeping zero
    exact and negation lossless — int8 uses 127, not 128, for that
    reason). ``bits`` drives the padded-bytes arithmetic the serving
    coalescer benefits from (an int8 row is 4x cheaper to pad and
    dispatch than the fp32 row it replaces)."""

    def __init__(self, name: str, dtype, qmax: float, bits: int):
        self.name = name
        self.dtype = jnp.dtype(dtype)
        self.qmax = float(qmax)
        self.bits = int(bits)

    @property
    def itemsize(self) -> int:
        return self.dtype.itemsize

    def __repr__(self):
        return f"QuantFormat({self.name!r})"


FORMATS: Dict[str, QuantFormat] = {
    "int8": QuantFormat("int8", np.int8, 127.0, 8),
    # fp8: same scale/clip machinery, different storage dtype
    "fp8_e4m3": QuantFormat("fp8_e4m3", jnp.float8_e4m3fn, 448.0, 8),
}


def get_format(name: str) -> QuantFormat:
    fmt = FORMATS.get(name)
    if fmt is None:
        raise MXNetError(
            f"unknown quantization format {name!r}; available: "
            f"{sorted(FORMATS)}")
    return fmt


def host_scale(absmax: float, fmt: QuantFormat) -> float:
    """THE symmetric per-tensor scale rule, host form: ``absmax/qmax``,
    with an all-zero tensor falling back to 1.0 (quantizing zeros must
    stay exact rather than divide by zero). One definition — the
    calibration stats, the weight quantizer, and the traced
    :func:`scale_for` all route through this rule so server-side and
    client-side quantization can never drift."""
    return absmax / fmt.qmax if absmax > 0 else 1.0


def scale_for(absmax, fmt: QuantFormat):
    """Traced form of :func:`host_scale`."""
    absmax = jnp.asarray(absmax, jnp.float32)
    return jnp.where(absmax > 0, absmax / fmt.qmax, 1.0)


def quantize(x, scale, fmt: QuantFormat):
    """Saturating quantize (traceable), format-aware: integer formats
    round to the integer grid then clip; float formats (fp8) clip to
    the representable range and let the dtype CAST do round-to-nearest
    onto the format's own mantissa grid — rounding fp8 values to
    integers first would throw away nearly all of e4m3's fractional
    resolution."""
    scaled = jnp.asarray(x, jnp.float32) / scale
    if jnp.issubdtype(fmt.dtype, jnp.integer):
        scaled = jnp.round(scaled)
    q = jnp.clip(scaled, -fmt.qmax, fmt.qmax)
    return q.astype(fmt.dtype)


def quantize_host(arr: np.ndarray, scale: float, fmt: QuantFormat
                  ) -> np.ndarray:
    """Host (numpy) twin of :func:`quantize` — the CANONICAL quantizer:
    the weight quantizer and the client/server ``quantize_inputs`` path
    both use it, which is what makes fp32-submitted and pre-quantized
    rows land bitwise identical. Integer formats match the traced form
    bit-for-bit. Float formats (fp8) agree to within one representable
    step: ml_dtypes' numpy cast is round-to-nearest-even, while this
    jax line's XLA f32->f8 convert rounds a hair differently near grid
    midpoints (observed on 0.4.37 CPU) — the traced :func:`quantize` is
    therefore NOT on the serving path; it exists for in-program
    (fp8-era) use where one program quantizes and dequantizes with the
    same convert."""
    scaled = np.asarray(arr, np.float32) / np.float32(scale)
    np_dtype = np.dtype(fmt.dtype)
    if np.issubdtype(np_dtype, np.integer):
        scaled = np.round(scaled)
    return np.clip(scaled, -fmt.qmax, fmt.qmax).astype(np_dtype)


def dequantize(q, scale):
    """Widen back to fp32 (traceable; the in-program form the quantized
    forward uses for weights and activations alike)."""
    return q.astype(jnp.float32) * jnp.asarray(scale, jnp.float32)


class QuantConfig:
    """What to quantize and how strictly to gate it.

    ``fmt`` names a :data:`FORMATS` entry. ``max_accuracy_delta`` is the
    measured-output-error bound the accuracy gate enforces before a
    quantized backend is allowed to ship (``MXTPU_QUANT_MAX_DELTA``
    default). ``min_ndim`` selects which parameters quantize — 2-D+
    matches the bf16 compute-cast rule (matmul/conv weights and
    embedding tables; biases and norms stay fp32). ``calib_batches``
    bounds how many representative batches calibration consumes.
    """

    def __init__(self, fmt: str = "int8",
                 max_accuracy_delta: Optional[float] = None,
                 min_ndim: int = 2, calib_batches: Optional[int] = None):
        self.format = get_format(fmt)
        if max_accuracy_delta is None:
            max_accuracy_delta = getenv("MXTPU_QUANT_MAX_DELTA",
                                        DEFAULT_MAX_DELTA, float)
        self.max_accuracy_delta = float(max_accuracy_delta)
        self.min_ndim = int(min_ndim)
        if calib_batches is None:
            calib_batches = getenv("MXTPU_QUANT_CALIB_BATCHES", 8, int)
        self.calib_batches = int(calib_batches)

    def quantizes_param(self, shape, dtype) -> bool:
        """The per-parameter rule: fp32, ``min_ndim``-D or higher."""
        return (len(tuple(shape)) >= self.min_ndim
                and str(dtype) in ("float32", "<f4"))

    def signature(self, param_names: Sequence[str] = ()) -> str:
        """Stable identity of the quantization *decision* (format + gated
        parameter set + selection rule). Scales are runtime inputs of
        the traced program — two calibrations share one executable — so
        they deliberately do not join."""
        return (f"qfmt={self.format.name};ndim>={self.min_ndim};"
                f"params={sorted(param_names)}")

    def signature_hash(self, param_names: Sequence[str] = ()) -> str:
        return hashlib.sha256(
            self.signature(param_names).encode()).hexdigest()[:16]


# ---------------------------------------------------------------------------
# compiler hook: the annotate-slot provider (mirrors parallel/sharding.py)
# ---------------------------------------------------------------------------

class _QuantTLS(threading.local):
    def __init__(self):
        self.stack: List[tuple] = []


_QUANT_TLS = _QuantTLS()
_ANNOTATOR_REGISTERED = False


def current_quant():
    """The innermost active :class:`quant_scope` (config, param_names)
    on this thread, or None."""
    stack = _QUANT_TLS.stack
    return stack[-1] if stack else None


def _quant_annotator(ir, ctx):
    """The ``annotate``-slot provider (compiler.register_annotator):
    with a config ambient, stamp each quantized parameter's format into
    the IR annotations plus the config signature. The signature joins
    ``OptimizeResult.transform_sig`` and therefore every persistent
    program key built from it — a precision change can never serve a
    stale executable (the ``sharding_sig`` pattern, PR 9). No config
    ambient -> None (no-op slot)."""
    active = current_quant()
    if active is None:
        return None
    config, param_names = active
    quantized = {}
    names = set(param_names)
    for node in ir.nodes:
        if not node.is_variable or node.name not in names:
            continue
        shape = ctx.input_shapes.get(node.name)
        dtype = ctx.input_dtypes.get(node.name, "float32")
        if shape is None or not config.quantizes_param(shape, dtype):
            continue
        quantized[node.name] = config.format.name
    return {"quant": quantized,
            "quant_sig": config.signature_hash(sorted(quantized))}


def _ensure_annotator():
    # lazy registration keeps import order acyclic (compiler never
    # imports quant); idempotent per process
    global _ANNOTATOR_REGISTERED
    if not _ANNOTATOR_REGISTERED:
        from .. import compiler as _compiler
        _compiler.register_annotator(_quant_annotator)
        _ANNOTATOR_REGISTERED = True


class quant_scope:
    """Make ``config`` ambient for the bind-time graph passes, so the
    quant annotator stamps the decision into the IR the quantized
    forward is about to trace::

        with quant_scope(config, param_names):
            opt_res = compiler.optimize(symbol, for_training=False, ...)
    """

    def __init__(self, config: Optional[QuantConfig],
                 param_names: Sequence[str] = ()):
        self.config = config
        self.param_names = tuple(param_names)

    def __enter__(self):
        _ensure_annotator()
        _QUANT_TLS.stack.append(
            None if self.config is None
            else (self.config, self.param_names))
        return self.config

    def __exit__(self, *exc):
        _QUANT_TLS.stack.pop()
        return False
