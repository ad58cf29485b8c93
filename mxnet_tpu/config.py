"""Runtime environment-variable config registry.

Reference surface: docs/how_to/env_var.md — 28 documented ``MXNET_*`` knobs
read via ``dmlc::GetEnv`` at point of use. Here every knob is declared in
one registry with type, default, and doc; readers call ``config.get(name)``
(or ``base.getenv`` directly for hot paths). ``MXTPU_`` is the canonical
prefix; a matching ``MXNET_`` spelling is accepted for familiarity
(base.py getenv).
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple

from .base import MXNetError, getenv

__all__ = ["register_knob", "get", "describe", "KNOBS"]


class Knob(NamedTuple):
    name: str
    typ: type
    default: Any
    doc: str


KNOBS: Dict[str, Knob] = {}


def register_knob(name: str, typ, default, doc: str):
    KNOBS[name] = Knob(name, typ, default, doc)
    return KNOBS[name]


def get(name: str):
    """Read a declared knob from the environment (typed, defaulted)."""
    if name not in KNOBS:
        raise MXNetError(f"unknown config knob {name}; see config.describe()")
    k = KNOBS[name]
    return getenv(k.name, k.default, k.typ)


def describe() -> str:
    """Human-readable table of every knob (env_var.md analogue)."""
    lines = ["{:<36} {:<8} {:<12} {}".format("name", "type", "default",
                                             "doc")]
    for k in sorted(KNOBS.values()):
        lines.append("{:<36} {:<8} {:<12} {}".format(
            k.name, k.typ.__name__, repr(k.default), k.doc))
    return "\n".join(lines)


# -- declared knobs ---------------------------------------------------------
# (reference mapping noted per knob; engine/memory knobs that XLA subsumes
# are deliberately absent — buffer assignment, bulk exec, workspace sizes)

register_knob("MXTPU_PROFILER_AUTOSTART", int, 0,
              "start the profiler at import (ref MXNET_PROFILER_AUTOSTART)")
register_knob("MXTPU_PROFILER_MODE", str, "all",
              "profiler mode: symbolic|imperative|api|all "
              "(ref MXNET_PROFILER_MODE)")
register_knob("MXTPU_NO_NATIVE", int, 0,
              "disable the native C++ IO library, pure-python fallback")
register_knob("MXTPU_DEFAULT_DTYPE", str, "float32",
              "dtype of newly created NDArrays")
register_knob("MXTPU_COMPUTE_DTYPE", str, "bfloat16",
              "matmul/conv compute dtype on TPU (bf16 keeps the MXU fed)")
register_knob("MXTPU_EXEC_EAGER", int, 0,
              "run symbol executors un-jitted for debugging "
              "(ref MXNET_ENGINE_TYPE=NaiveEngine)")
register_knob("MXTPU_KVSTORE_BIGARRAY_BOUND", int, 1000000,
              "array size above which dist push/pull shards over hosts "
              "(ref MXNET_KVSTORE_BIGARRAY_BOUND)")
register_knob("MXTPU_CPU_WORKER_NTHREADS", int, 4,
              "worker threads for the host IO/augment pipeline "
              "(ref MXNET_CPU_WORKER_NTHREADS)")
register_knob("MXTPU_BACKWARD_DO_MIRROR", int, 0,
              "trade FLOPs for memory via jax.checkpoint rematerialization "
              "in executor backward (ref MXNET_BACKWARD_DO_MIRROR)")
register_knob("MXTPU_GRAPH_PASSES", int, 1,
              "run the bind-time graph-pass pipeline (DCE/CSE/remat "
              "policy; mxnet_tpu/compiler) — 0 disables")
register_knob("MXTPU_COMPILE_CACHE", int, 1,
              "persist compiled executables under "
              "MXTPU_COMPILE_CACHE_DIR so later processes skip "
              "recompilation — 0 disables the disk layer")
register_knob("MXTPU_COMPILE_CACHE_DIR", str, None,
              "root of the executable store; default "
              "<jax cache dir>/mxtpu-executables, where the jax cache dir "
              "is JAX_COMPILATION_CACHE_DIR if set, else "
              "<checkout>/.cache/jax")
register_knob("MXTPU_COMPILE_CACHE_MB", float, 512,
              "LRU size bound of the compilation cache, megabytes")
register_knob("MXTPU_REMAT_MB", float, None,
              "activation-memory budget: a training bind whose estimated "
              "forward activations exceed it gets jax.checkpoint remat "
              "(the remat-policy pass decision)")
register_knob("MXTPU_HBM_BUDGET_MB", float, None,
              "per-device peak-HBM budget: a FusedStep/SPMDTrainer bind "
              "whose estimated footprint (compiler/memory.py: params + "
              "grads + optimizer state + live activations) exceeds it "
              "raises a typed MemoryBudgetError naming the top "
              "contributors and the knobs that would fit it (ZeRO, "
              "MXTPU_REMAT_MB, int8) instead of dying in XLA allocation")
register_knob("MXTPU_OP_COSTS", str, None,
              "json file of measured per-op ms (profile harness output) "
              "pricing the remat-policy recompute estimate")
register_knob("MXTPU_PROGRAM_REGISTRY_CAP", int, 64,
              "max fingerprint-keyed executor program bundles shared "
              "in-process (LRU; eviction only costs sharing)")
register_knob("MXTPU_ZERO", int, 0,
              "default ZeRO-1 mode for mesh trainers: shard optimizer "
              "state + the weight-update math over the data axis, "
              "re-gathering params via the ICI inside the donated step "
              "(docs/how_to/multichip.md; arxiv 2004.13336)")
register_knob("MXTPU_PARTITION_RULES", str, None,
              "ordered partition rules as JSON [[regex, spec], ...] or "
              "@/path/to/rules.json — resolved by the rule engine in "
              "parallel/sharding.py (docs/how_to/multichip.md)")
register_knob("MXTPU_SUPERVISOR", int, 0,
              "arm the preemption-aware training supervisor in every "
              "fit() (signal handlers, stall watchdog, crash-loop "
              "guard; docs/how_to/preemption.md)")
register_knob("MXTPU_STALL_TIMEOUT", float, None,
              "seconds a step heartbeat may go stale before the "
              "watchdog raises StepStalled and walks the escalation "
              "ladder (unset = watchdog off)")
register_knob("MXTPU_STALL_POLL", float, None,
              "watchdog thread poll period, seconds (default: "
              "stall timeout / 4)")
register_knob("MXTPU_CRASH_LOOP_LIMIT", int, 3,
              "consecutive resume attempts at one (epoch, batch) before "
              "that batch is quarantined as poison")
register_knob("MXTPU_CRASH_BACKOFF_BASE", float, 1.0,
              "first crash-loop resume backoff, seconds (doubles per "
              "repeat attempt)")
register_knob("MXTPU_CRASH_BACKOFF_CAP", float, 60.0,
              "upper bound on one crash-loop resume backoff, seconds")
register_knob("MXTPU_PRECISION", str, "fp32",
              "training precision mode: 'bf16' defaults every trainer's "
              "compute_dtype to bfloat16 (fp32 master weights, 2-D+ "
              "cast in-step) and arms the dynamic loss-scale guard "
              "inside the donated step (non-finite steps skipped, not "
              "applied; docs/how_to/quantization.md)")
register_knob("MXTPU_QUANT", int, 0,
              "default as_serving_backend() to int8 post-training "
              "quantization (calibration + accuracy gate; "
              "docs/how_to/quantization.md) — callers must still "
              "provide calibration data")
register_knob("MXTPU_QUANT_MAX_DELTA", float, 0.05,
              "accuracy gate: largest mean relative output error the "
              "quantized path may show vs fp32 on the calibration "
              "batches before it is refused (fp32 fallback + typed "
              "QuantAccuracyWarning)")
register_knob("MXTPU_QUANT_CALIB_BATCHES", int, 8,
              "representative batches consumed by PTQ calibration and "
              "the accuracy gate")
register_knob("MXTPU_MAX_BATCH", int, 1,
              "total rows one coalesced serving dispatch may carry "
              "(mxnet_tpu/serving/batching.py) — 1 disables continuous "
              "batching; warm-up then pre-traces every bucket at 1, "
              "max, and the powers of two between")
register_knob("MXTPU_BATCH_WAIT_MS", float, 2.0,
              "milliseconds a threaded serving worker may hold the "
              "first request open for more traffic to coalesce "
              "(bounded by every member's remaining deadline; the "
              "deterministic workers=0 mode never waits)")
register_knob("MXTPU_RAGGED", int, 1,
              "master switch for the ragged serving rungs "
              "(mxnet_tpu/serving/ragged.py): length-masked compute, "
              "symbolic-dim programs, and sequence packing — each only "
              "activates on backends that declare support; 0 restores "
              "the dense padded path bitwise (pad-waste observability "
              "stays on either way)")
register_knob("MXTPU_PACK_MAX_SEGMENTS", int, 0,
              "cap on requests sharing one packed row in the sequence "
              "packer (segment-masked attention pays per resident "
              "segment); 0 = unbounded — first-fit packs until the row "
              "is full")
register_knob("MXTPU_TENANT_QUOTAS", str, None,
              "per-tenant serving admission quotas + fair-share "
              "weights: 'name:quota[:weight],...' (quota '*' = "
              "unbounded) or JSON {name: {quota, weight}} — unset "
              "disables quotas (docs/how_to/serving.md)")
register_knob("MXTPU_ASYNC_CKPT", int, 0,
              "write fit() checkpoints through the background "
              "AsyncCheckpointer (resilience/async_checkpoint.py): the "
              "step loop pays only a host snapshot and a single writer "
              "thread commits atomically behind it; preemption flushes "
              "the pending snapshot (docs/how_to/fault_tolerance.md)")
register_knob("MXTPU_CKPT_FLUSH_TIMEOUT", float, 60.0,
              "seconds AsyncCheckpointer.flush()/submit back-pressure "
              "waits for the background writer before raising a typed "
              "AsyncCheckpointError (bounds the preemption deadline "
              "on a dead filesystem)")
register_knob("MXTPU_FLEET_REPLICAS", int, 3,
              "default ACTIVE replica count of a serving FleetRouter "
              "(mxnet_tpu/serving/fleet.py, docs/how_to/fleet.md)")
register_knob("MXTPU_FLEET_PROBE_PERIOD", float, 1.0,
              "seconds between fleet replica-health probe passes on "
              "the router's injectable clock (FleetRouter.tick)")
register_knob("MXTPU_FLEET_EVICT_AFTER", int, 3,
              "consecutive failed health probes after which a fleet "
              "replica is evicted and a warm standby promoted")
register_knob("MXTPU_CKPT_KEEP", int, 1,
              "mid-epoch checkpoints retained as a rollback window: the "
              "newest K superseded stems survive the stale sweep and "
              "the trainer's rolling rmtree so a divergence detected N "
              "steps late can roll back past contaminated saves "
              "(docs/how_to/integrity.md)")
register_knob("MXTPU_INTEGRITY_PERIOD", int, 0,
              "steps between cross-replica parameter-checksum voting "
              "rounds in the integrity guard "
              "(resilience/integrity.py) — 0 disables the guard "
              "entirely (sentinels included), bitwise-identical "
              "programs")
register_knob("MXTPU_INTEGRITY_ZMAX", float, 6.0,
              "divergence sentinel: z-score of the current grad-norm "
              "against the running (Welford) statistics beyond which "
              "DivergenceDetected is raised at the next host boundary")
register_knob("MXTPU_INTEGRITY_GRAD_MAX", float, None,
              "divergence sentinel: absolute grad-norm bound; any step "
              "whose global grad norm exceeds it (or is non-finite) "
              "breaches the guard regardless of the z-score")
register_knob("MXTPU_INTEGRITY_WARMUP", int, 8,
              "steps of sentinel statistics collected before the "
              "z-score test arms (absolute/non-finite bounds are "
              "always live)")
register_knob("MXTPU_FLEET_HEDGE_MAX", int, 4,
              "gray-failure hedging: max concurrent hedged dispatches a "
              "FleetRouter may have outstanding (0 disables hedging "
              "entirely; docs/how_to/fleet.md)")
register_knob("MXTPU_FLEET_HEDGE_FACTOR", float, 2.0,
              "a request whose elapsed time crosses this multiple of "
              "the fleet p95 dispatch latency is hedged onto the "
              "next-best replica (first settle wins, exactly-once)")
register_knob("MXTPU_FLEET_HEDGE_MIN_SAMPLES", int, 16,
              "recorded fleet dispatch latencies required before the "
              "hedge threshold arms (no hedging on a cold histogram)")
register_knob("MXTPU_FLEET_SLOW_FACTOR", float, 4.0,
              "slow-eviction rung: a replica whose windowed p95 sits at "
              "or above this multiple of the fleet-median p95 is "
              "evicted like an error-rate breach (0 disables)")
register_knob("MXTPU_FLEET_SLOW_MIN_SAMPLES", int, 16,
              "dispatches a replica's latency window must hold before "
              "the slow-eviction comparison runs")
register_knob("MXTPU_RETRY_JITTER", str, "uniform",
              "RetryPolicy backoff jitter mode: 'uniform' (+/- jitter "
              "fraction around the exponential schedule) or "
              "'decorrelated' (AWS-style seedable decorrelated jitter "
              "so workers retrying the same failed site spread out "
              "instead of waking in lockstep)")
register_knob("MXTPU_SLOW_STEP", int, 0,
              "arm the supervisor's host-side step-time sentinel: "
              "persistent slow steps walk the retry -> rebind -> "
              "re-mesh ladder (docs/how_to/preemption.md) — 0 disables")
register_knob("MXTPU_SLOW_STEP_ZMAX", float, 6.0,
              "slow-step sentinel: z-score of a step's wall time "
              "against the running (Welford) statistics beyond which "
              "the step counts as slow")
register_knob("MXTPU_SLOW_STEP_FACTOR", float, 0.0,
              "slow-step sentinel: absolute bound — wall time above "
              "this multiple of the running mean counts as slow "
              "(0 = z-score only)")
register_knob("MXTPU_SLOW_STEP_WARMUP", int, 8,
              "clean step-time samples folded before the slow-step "
              "sentinel arms")
register_knob("MXTPU_SLOW_STEP_STREAK", int, 3,
              "consecutive slow steps at which the supervisor escalates "
              "to elastic re-mesh (rungs below: 1 logs+retries, "
              "2 rebinds)")
