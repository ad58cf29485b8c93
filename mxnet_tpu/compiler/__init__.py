"""The compile-time intelligence layer: graph passes + persistent
compilation cache.

Reference analogue: the NNVM pass pipeline that sat between MXNet's
symbolic frontend and its executor (SURVEY.md §3.2), reclaimed in the
shape TVM (arxiv 1802.04799) and Relay (arxiv 1810.00952) standardized
— a small pass framework over a typed graph IR, with compilation
artifacts cached and reused. Two halves (docs/how_to/compiler.md):

- :mod:`.passes` over :mod:`.ir` — ``Pass``/``PassManager`` running at
  bind time in ``Executor``/``FusedStep``/``SPMDTrainer`` construction:
  dead-op elimination, CSE, the remat (memory-vs-recompute) policy fed
  by profiled per-op costs, and the no-op-safe ``annotate`` slot where
  sharding specs and quantization rewrites plug in.
- :mod:`.fingerprint` + :mod:`.cache` + :mod:`.aot` — a stable graph
  fingerprint keying serialized compiled executables under
  ``<jax cache dir>/mxtpu-executables`` (atomic writes, SHA-256 manifests, corrupt
  fallback to recompile, LRU size bound), so serving cold start, CI,
  ``fit(resume='auto')`` and bench rounds skip retrace+recompile of
  unchanged programs. ``MXTPU_COMPILE_CACHE=0`` kills the disk layer;
  ``MXTPU_GRAPH_PASSES=0`` kills the pass pipeline.

``compiler.stats()`` mirrors ``retry.stats()``: one snapshot of cache
hit/miss/invalidation counters, program compile/load/bypass counters,
and per-pass change counters.
"""
from __future__ import annotations

from typing import Dict

from . import aot, cache, fingerprint, ir, memory, passes, symbolic  # noqa: F401
from .aot import PersistentJit, ProgramRegistry  # noqa: F401
from .cache import CompilationCache, cache_enabled, default_cache  # noqa: F401
from .fingerprint import (batch_signature, code_salt,  # noqa: F401
                          graph_fingerprint, mesh_signature, program_key)
from .ir import GraphIR  # noqa: F401
from .memory import (MemoryBudgetError, MemoryEstimate,  # noqa: F401
                     estimate_peak_bytes)
from .passes import (Annotate, CommonSubexpressionElimination,  # noqa: F401
                     DeadOpElimination, OptimizeResult, Pass, PassContext,
                     PassManager, RematPolicy, default_pass_manager,
                     optimize, register_annotator)
from .symbolic import (SymbolicBatchProgram,  # noqa: F401
                       symbolic_transform_sig)

__all__ = ["ir", "passes", "fingerprint", "cache", "aot", "memory",
           "symbolic", "SymbolicBatchProgram",
           "symbolic_transform_sig",
           "MemoryBudgetError", "MemoryEstimate", "estimate_peak_bytes",
           "GraphIR",
           "Pass", "PassContext", "PassManager", "OptimizeResult",
           "DeadOpElimination", "CommonSubexpressionElimination",
           "RematPolicy", "Annotate", "register_annotator",
           "default_pass_manager", "optimize", "graph_fingerprint",
           "code_salt", "mesh_signature", "batch_signature", "program_key",
           "CompilationCache", "default_cache", "cache_enabled",
           "PersistentJit", "ProgramRegistry", "stats", "reset_stats"]


def stats() -> Dict[str, Dict]:
    """One snapshot of the compiler layer's counters — cache hits/misses/
    invalidations, program compiles/loads/bypasses, per-pass changes.
    Mirrors ``resilience.retry.stats()``."""
    return {"cache": cache.cache_stats(),
            "programs": aot.program_stats(),
            "passes": passes.pass_stats()}


def reset_stats():
    cache.reset_cache_stats()
    aot.reset_program_stats()
    passes.reset_pass_stats()
