"""Silent-failure integrity guard (docs/how_to/integrity.md).

Elastic training (elastic.py) and the supervisor (supervisor.py) handle
failures that ANNOUNCE themselves — a dead collective, a stalled step, a
delivered SIGTERM. This module handles the chip that lies: a flaky
device whose health probes all pass while it silently computes wrong
bits (TPU "silent data corruption" — the fleet-scale failure mode
neither checkpoints nor re-meshing can see, because nothing raises).

Three detection layers, one recovery ladder:

- **In-trace divergence sentinels** — a six-scalar Welford accumulator
  over the global gradient norm rides the donated step exactly like the
  loss-scale state (perf/step_runtime.py seam): the z-score and
  absolute/non-finite tests run IN the traced program, a sticky breach
  flag is carried device-side, and the host reads it only once per
  ``MXTPU_INTEGRITY_PERIOD`` steps — zero per-step host syncs.
- **Cross-replica checksum voting** — every period, a ``shard_map``
  program folds each replica's parameter shards to one uint32 checksum
  per device (order-independent wraparound sum over the raw float
  bits), all-gathers the per-device grid, and majority-votes on the
  host: replicas that hold the same logical shard must hold the same
  bits. The dissenting replica IS the bad chip — localization for free.
- **Deterministic replay classification** — on divergence, roll back to
  the last checksum-validated checkpoint and replay: a transient upset
  vanishes, a poison batch diverges again at the same position (and is
  quarantined under the :class:`~.data.DataGuardPolicy` budget), a bad
  chip dissents in the next vote (and is quarantined through
  :class:`~.elastic.MeshHealth` so the elastic controller re-meshes
  without it).

Recovery extends the supervisor's escalation ladder one rung deeper:
replay -> re-mesh -> rollback -> abort (``EXIT_INTEGRITY``). The guard
also gates the async checkpointer (``AsyncCheckpointer(gate=...)``) so a
breached run can never commit diverged state to disk, and the
``MXTPU_CKPT_KEEP`` rollback window keeps enough superseded mid-epoch
checkpoints that a divergence detected N steps late can roll back PAST
the contaminated saves.

Fault sites: ``mesh.silent_corrupt`` injects a deterministic
single-device bitflip into the live parameters (the lying chip, seeded
and replayable); ``integrity.checksum`` fails the voting round itself
(vote-infrastructure failure — it must propagate, never be mistaken
for a clean vote).

``MXTPU_INTEGRITY_PERIOD=0`` (the default) disables everything: no
sentinel state enters the donated step, no extra outputs, bitwise- and
program-identical to a build without this module.
"""
from __future__ import annotations

import logging
import os
import random
import re
import shutil
import threading
from dataclasses import dataclass
from typing import Dict, Optional

import numpy as np

from ..base import MXNetError
from . import faults
from .elastic import DeviceLost

__all__ = ["IntegrityConfig", "IntegrityGuard", "DivergenceDetected",
           "ChecksumMismatch", "IntegrityAbort", "resolve_config",
           "init_sentinel", "update_sentinel", "sentinel_stats",
           "corruption_point", "stats", "reset_stats",
           "SITE_CORRUPT", "SITE_CHECKSUM"]

SITE_CORRUPT = "mesh.silent_corrupt"
SITE_CHECKSUM = "integrity.checksum"

#: exit code for an integrity abort (ladder exhausted) — joins the
#: supervisor's typed exits (EXIT_PREEMPTED/EXIT_ABORTED/EXIT_STALLED)
EXIT_INTEGRITY = 86


class DivergenceDetected(MXNetError):
    """The in-trace divergence sentinel breached: the gradient norm went
    non-finite, exceeded ``MXTPU_INTEGRITY_GRAD_MAX``, or z-scored past
    ``MXTPU_INTEGRITY_ZMAX`` against its own running statistics. Raised
    at the amortized host boundary (never mid-step); ``fit`` recovers by
    rolling back to the last validated checkpoint and replaying."""

    def __init__(self, msg, epoch=-1, nbatch=-1, code=0, breach_step=-1):
        super().__init__(msg)
        self.epoch = epoch
        self.nbatch = nbatch
        self.code = int(code)           # 1 = z-score, 2 = abs/non-finite
        self.breach_step = int(breach_step)


class ChecksumMismatch(DeviceLost):
    """A cross-replica checksum vote split: at least one replica holds
    different parameter bits than its peers. A :class:`DeviceLost`
    subtype on purpose — ``fit``'s elastic recovery path (re-mesh onto
    survivors + restore + rewind) is exactly the right reaction, and
    ``already_marked`` tells the controller the vote already named (and
    quarantined) the victim, so no seeded guess is layered on top."""

    def __init__(self, msg, device_id=None, already_marked=False):
        super().__init__(msg)
        self.device_id = device_id
        self.already_marked = bool(already_marked)


class IntegrityAbort(MXNetError):
    """The integrity recovery ladder is exhausted (replay, re-mesh and
    rollback all failed, or no checkpoint exists to roll back to).
    Carries ``exit_code = EXIT_INTEGRITY`` for supervised launchers."""

    exit_code = EXIT_INTEGRITY


# -- configuration -----------------------------------------------------------

@dataclass(frozen=True)
class IntegrityConfig:
    """Static sentinel/vote parameters; everything here enters the
    traced program identity via :meth:`signature` (a period change is a
    host-side cadence change only, but zmax/grad_max/warmup are traced
    constants, so they key the persistent program)."""

    period: int = 1
    zmax: float = 6.0
    grad_max: Optional[float] = None
    warmup: int = 8

    def signature(self) -> str:
        gm = "-" if self.grad_max is None else repr(float(self.grad_max))
        return (f"ig=z{float(self.zmax)!r};g{gm};w{int(self.warmup)}")


def resolve_config(req=None) -> Optional[IntegrityConfig]:
    """Resolve a trainer's ``integrity=`` request against the env knobs:
    ``None`` defers to ``MXTPU_INTEGRITY_PERIOD`` (0 = disabled),
    ``True`` forces the guard on (period >= 1), ``False`` forces it off,
    an :class:`IntegrityConfig` is taken as-is (period <= 0 disables)."""
    if req is False:
        return None
    if isinstance(req, IntegrityConfig):
        return req if req.period > 0 else None
    from .. import config
    period = int(config.get("MXTPU_INTEGRITY_PERIOD"))
    if req is True and period <= 0:
        period = 1
    if period <= 0:
        return None
    gm = config.get("MXTPU_INTEGRITY_GRAD_MAX")
    return IntegrityConfig(
        period=period,
        zmax=float(config.get("MXTPU_INTEGRITY_ZMAX")),
        grad_max=None if gm is None else float(gm),
        warmup=int(config.get("MXTPU_INTEGRITY_WARMUP")))


# -- counters ----------------------------------------------------------------

_lock = threading.Lock()
_counters: Dict[str, int] = {
    "checksum_rounds": 0, "votes": 0, "divergences": 0,
    "quarantines": 0, "replays": 0, "rollbacks": 0}


def _count(key: str, n: int = 1):
    with _lock:
        _counters[key] += n


def stats() -> Dict[str, int]:
    """Integrity counters (surfaced under
    ``resilience.stats()["integrity"]`` and by ResilienceMonitor)."""
    with _lock:
        return dict(_counters)


def reset_stats():
    with _lock:
        for k in _counters:
            _counters[k] = 0


# -- in-trace divergence sentinel --------------------------------------------
#
# State: six replicated f32 scalars (count, mean, m2, flag, breach_t,
# last) donated through the step exactly like the loss-scale (scale,
# streak) pair. The z-test MUST run in-trace against the PRE-fold
# statistics: folding the spike first inflates the running std to
# ~spike/sqrt(n), capping any detectable z at ~sqrt(n) — a host-side
# post-hoc test over folded stats is mathematically blind to exactly
# the one-step spikes it exists to catch. Breaching samples are never
# folded, the flag is sticky (max of breach codes), and breach_t
# records the FIRST breaching update counter so rollback knows how far
# the contamination reaches back.

def init_sentinel():
    """Fresh sentinel state: 6 host f32 scalars, ready to device_put."""
    return tuple(np.float32(0.0) for _ in range(6))


def update_sentinel(cfg: IntegrityConfig, state, grads, t, applied=None):
    """Traced sentinel update (called INSIDE the donated step).

    ``applied`` is the loss-scale guard's finiteness predicate when that
    guard is armed: a step the guard skipped is neither a breach nor a
    sample (non-finite grads are the loss-scale schedule's business
    there, not an integrity event)."""
    import jax
    import jax.numpy as jnp
    count, mean, m2, flag, breach_t, last = state
    sq = None
    for g in jax.tree_util.tree_leaves(grads):
        term = jnp.sum(jnp.square(g.astype(jnp.float32)))
        sq = term if sq is None else sq + term
    x = jnp.sqrt(sq) if sq is not None else jnp.float32(0.0)
    finite = jnp.isfinite(x)
    skipped = (jnp.logical_not(applied) if applied is not None
               else jnp.bool_(False))
    # absolute tier: always live (no warmup) — non-finite or over the
    # hard bound is a breach no statistics are needed for
    abs_bad = jnp.logical_and(jnp.logical_not(finite),
                              jnp.logical_not(skipped))
    if cfg.grad_max is not None:
        abs_bad = abs_bad | (finite & (x > jnp.float32(cfg.grad_max)))
    # z tier: armed after warmup samples, tested against the PRE-fold
    # running stats (see the block comment above)
    var = m2 / jnp.maximum(count - 1.0, 1.0)
    std = jnp.sqrt(jnp.maximum(var, 1e-12))
    z = jnp.abs(x - mean) / std
    z_bad = (jnp.logical_not(skipped) & finite
             & (count >= jnp.float32(cfg.warmup))
             & (z > jnp.float32(cfg.zmax)))
    code = jnp.where(abs_bad, jnp.float32(2.0),
                     jnp.where(z_bad, jnp.float32(1.0), jnp.float32(0.0)))
    ok = (code == 0.0) & finite & jnp.logical_not(skipped)
    # Welford fold of clean samples only. The fold MUST be selected via
    # where (not masked arithmetic): with x non-finite, `mean + 0*delta`
    # is NaN (0 * NaN = NaN) and would poison the statistics forever.
    n1 = count + 1.0
    delta = x - mean
    mean_f = mean + delta / n1
    m2_f = m2 + delta * (x - mean_f)
    new_count = jnp.where(ok, n1, count)
    new_mean = jnp.where(ok, mean_f, mean)
    new_m2 = jnp.where(ok, m2_f, m2)
    new_flag = jnp.maximum(flag, code)
    new_breach_t = jnp.where((flag == 0.0) & (code > 0.0),
                             jnp.asarray(t, jnp.float32), breach_t)
    new_last = jnp.asarray(x, jnp.float32)
    return (new_count, new_mean, new_m2, new_flag, new_breach_t, new_last)


def sentinel_stats(state) -> Optional[Dict]:
    """Host snapshot of a sentinel state tuple — a boundary read (one
    device->host transfer per integrity period), never per-step."""
    if state is None:
        return None
    count, mean, m2, flag, breach_t, last = (
        float(np.asarray(x)) for x in state)
    var = m2 / max(count - 1.0, 1.0) if count > 1 else 0.0
    return {"samples": int(count), "mean": mean,
            "std": float(var) ** 0.5 if var > 0 else 0.0,
            "flag": int(flag), "breach_step": int(breach_t),
            "last": last}


# -- silent-corruption injection (the lying chip) ----------------------------

#: diagnostics of the most recent injected bitflip (tests assert the
#: vote localizes exactly this device): {"device", "param", "word",
#: "bit"} or None
_last_injected: Optional[Dict] = None


def corruption_point(trainer):
    """Fault site ``mesh.silent_corrupt``: called at the end of every
    SPMDTrainer step. Disarmed this is one ``active_plan() is None``
    check. When an armed plan fires here, NOTHING raises — that is the
    whole point: a seeded single-bit flip lands in one device's copy of
    one parameter shard, every health probe keeps passing, and only the
    checksum vote can see it. An ``InjectedKill`` still propagates (a
    chip can die here like anywhere else)."""
    if faults.active_plan() is None:
        return
    try:
        faults.fault_point(SITE_CORRUPT)
    except (faults.InjectedFault, faults.InjectedTimeout):
        _inject_bitflip(trainer)


def _inject_bitflip(trainer):
    """Deterministic single-device, single-bit parameter corruption:
    the plan seed picks the victim parameter, shard, word and bit —
    replayable byte-for-byte. The flipped bit is a LOW mantissa bit, so
    the value stays finite and numerically boring: invisible to the
    divergence sentinel by construction, detectable only bitwise."""
    global _last_injected
    import jax
    plan = faults.active_plan()
    seed = plan.seed if plan is not None else 0
    rng = random.Random(seed * 7654321 + 1)
    names = sorted(n for n in trainer.params
                   if jax.tree_util.tree_leaves(trainer.params[n])
                   and jax.tree_util.tree_leaves(
                       trainer.params[n])[0].dtype == np.float32)
    if not names:
        return
    name = names[rng.randrange(len(names))]
    leaves, treedef = jax.tree_util.tree_flatten(trainer.params[name])
    leaf = leaves[0]
    shards = list(leaf.addressable_shards)
    victim = rng.randrange(len(shards))
    data = np.array(shards[victim].data)        # a host copy
    words = data.view(np.uint32).reshape(-1)
    word = rng.randrange(words.size)
    bit = rng.randrange(20)                     # low mantissa: stays finite
    words[word] ^= np.uint32(1 << bit)
    bufs = [jax.device_put(data if i == victim else np.asarray(s.data),
                           s.device)
            for i, s in enumerate(shards)]
    leaves[0] = jax.make_array_from_single_device_arrays(
        leaf.shape, leaf.sharding, bufs)
    trainer.params[name] = jax.tree_util.tree_unflatten(treedef, leaves)
    _last_injected = {"device": shards[victim].device.id, "param": name,
                      "word": int(word), "bit": int(bit)}
    logging.debug("integrity: injected bitflip on device %d (%s word %d "
                  "bit %d)", _last_injected["device"], name, word, bit)


# -- the guard ---------------------------------------------------------------

class IntegrityGuard:
    """Host-side orchestrator: periodic sentinel reads + checksum votes,
    contamination pruning, rollback-and-replay classification, and the
    commit gate for the async checkpointer.

    Built by ``SPMDTrainer.fit`` when ``MXTPU_INTEGRITY_PERIOD`` (or the
    trainer's ``integrity=`` request) arms the guard; shares the elastic
    controller's :class:`~.elastic.MeshHealth` so a localized bad chip
    is quarantined through the SAME device-exclusion path a probed loss
    takes, and the controller re-meshes without it."""

    def __init__(self, trainer, cfg: IntegrityConfig, health=None,
                 checkpoint_dir: Optional[str] = None, data_policy=None):
        from .data import DataGuardPolicy
        self.trainer = trainer
        self.cfg = cfg
        self.health = health
        self.checkpoint_dir = checkpoint_dir
        self.policy = data_policy or DataGuardPolicy()
        #: sticky breach latch: flipped on detection, cleared only by
        #: on_recovered(); while set, gate() refuses checkpoint commits
        self.breached = False
        self._since = 0
        #: newest update counter a clean checksum round validated —
        #: everything after it is contamination-suspect on a breach
        self._last_good_update = 0
        self._replays: Dict[tuple, int] = {}
        self._quarantined = set()
        self._ck_fn = None
        self._ck_key = None

    # -- checkpoint commit gate ---------------------------------------------

    def gate(self) -> bool:
        """``AsyncCheckpointer(gate=...)`` hook: False while breached —
        diverged state must never reach disk."""
        return not self.breached

    # -- per-step boundary ---------------------------------------------------

    def after_step(self, epoch: int, nbatch: int):
        """Called once per completed step, BEFORE that step's checkpoint
        is written. Cheap ``period - 1`` times out of ``period``; on the
        period boundary it reads the sentinel flag (one host transfer)
        and runs a checksum vote."""
        self._since += 1
        if self._since < self.cfg.period:
            return
        self._since = 0
        self.check_now(epoch, nbatch)

    def check_now(self, epoch: int = -1, nbatch: int = -1):
        """One integrity round: sentinel flag, then checksum vote."""
        sen = sentinel_stats(getattr(self.trainer, "_ig_state", None))
        if sen is not None and sen["flag"]:
            self.breached = True
            _count("divergences")
            raise DivergenceDetected(
                f"divergence sentinel breached at update "
                f"{sen['breach_step']} (code {sen['flag']}: "
                f"{'abs/non-finite' if sen['flag'] >= 2 else 'z-score'}, "
                f"last grad norm {sen['last']:.4g}, running mean "
                f"{sen['mean']:.4g} over {sen['samples']} samples)",
                epoch=epoch, nbatch=nbatch, code=sen["flag"],
                breach_step=sen["breach_step"])
        verdict, device_id = self.checksum_round()
        if verdict == "ok":
            self._last_good_update = self.trainer._num_update
            return
        self.breached = True
        self._prune_contaminated()
        if device_id is not None and self.health is not None:
            self.health.mark_device(device_id)
            _count("quarantines")
            raise ChecksumMismatch(
                f"cross-replica checksum vote split: device {device_id} "
                f"dissents from the majority (validated through update "
                f"{self._last_good_update}); device quarantined",
                device_id=device_id, already_marked=True)
        raise ChecksumMismatch(
            "cross-replica checksum vote split with no localizable "
            "dissenter (fewer than 3 replicas per shard group, or "
            "multiple dissenters); falling back to seeded victim "
            "selection", device_id=None, already_marked=False)

    # -- checksum vote -------------------------------------------------------

    def _checksum_fn(self):
        """Build (and cache, keyed by mesh+plan+param shapes) the traced
        per-device checksum program: a full-mesh ``shard_map`` whose
        in_specs are each leaf's OWN plan spec (so under ZeRO each
        replica checksums exactly the shard it owns) and whose out_spec
        lays one uint32 per device on the mesh grid — the all-gather of
        the vote is the output layout itself."""
        import jax
        tr = self.trainer
        mesh, plan = tr._mesh, tr._plan
        names = sorted(tr.params)
        shapes = tuple(
            (n, tuple(leaf.shape), str(leaf.dtype))
            for n in names
            for leaf in jax.tree_util.tree_leaves(tr.params[n]))
        key = (tuple(sorted(mesh.shape.items())),
               tuple(d.id for d in mesh.devices.flat),
               plan.signature_hash() if plan is not None else "-", shapes)
        if self._ck_fn is not None and self._ck_key == key:
            return self._ck_fn, names
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from jax import shard_map
        axes = tuple(mesh.axis_names)
        naxes = len(axes)
        in_specs = []
        for n in names:
            for leaf in jax.tree_util.tree_leaves(tr.params[n]):
                spec = (plan.param_spec(n, leaf.shape) if plan is not None
                        else P())
                in_specs.append(spec)

        def leaf_sum(x):
            # order-independent wraparound sum over the raw bits: any
            # reduction order gives the same uint32, so the checksum is
            # deterministic across topologies and compiler versions
            if x.dtype == jnp.float32:
                w = jax.lax.bitcast_convert_type(x, jnp.uint32)
            elif x.dtype == jnp.float64:
                w64 = jax.lax.bitcast_convert_type(x, jnp.uint64)
                w = ((w64 & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
                     + (w64 >> jnp.uint64(32)).astype(jnp.uint32))
            elif x.dtype in (jnp.bfloat16, jnp.float16):
                w = jax.lax.bitcast_convert_type(
                    x, jnp.uint16).astype(jnp.uint32)
            else:
                w = x.astype(jnp.uint32)
            return jnp.sum(w.reshape(-1), dtype=jnp.uint32)

        def body(*leaves):
            s = jnp.uint32(0)
            for x in leaves:
                s = s + leaf_sum(x)
            return s.reshape((1,) * naxes)

        # plain jax.jit on purpose: this is a sidecar program, not the
        # training step — it must not charge the trainer's CompileGuard
        # (MXTPU_RETRACE_STRICT stays quiet) and it recompiles only on
        # an actual topology change (the cache key above)
        self._ck_fn = jax.jit(shard_map(
            body, mesh=mesh, in_specs=tuple(in_specs),
            out_specs=P(*axes), check_vma=False))
        self._ck_key = key
        return self._ck_fn, names

    def checksum_round(self):
        """Run one vote. Returns ``("ok", None)``, or ``("mismatch",
        device_id)`` with ``device_id=None`` when the dissenter cannot
        be localized. The ``integrity.checksum`` fault site runs FIRST:
        an injected fault there is the vote infrastructure itself
        failing, and it propagates — a broken vote must never read as a
        clean one."""
        faults.fault_point(SITE_CHECKSUM)
        _count("checksum_rounds")
        import jax
        tr = self.trainer
        fn, names = self._checksum_fn()
        leaves = [leaf for n in names
                  for leaf in jax.tree_util.tree_leaves(tr.params[n])]
        from ..parallel.mesh import mesh_scope
        with mesh_scope(tr._mesh):
            grid = np.asarray(fn(*leaves))      # uint32, shape mesh.shape
        mesh = tr._mesh
        axes = list(mesh.axis_names)
        plan = tr._plan
        data_axis = plan.data_axis if plan is not None else "data"
        didx = axes.index(data_axis) if data_axis in axes else 0
        nrep = grid.shape[didx]
        sums = np.moveaxis(grid, didx, 0).reshape(nrep, -1)
        devs = np.moveaxis(np.asarray(mesh.devices), didx, 0).reshape(
            nrep, -1)
        bad_ids = set()
        localizable = True
        for col in range(sums.shape[1]):
            # one column = the replicas sharing every non-data mesh
            # coordinate: they hold the same logical parameter shard,
            # so their checksums must agree bit-for-bit
            _count("votes")
            vals = sums[:, col]
            uniq, counts = np.unique(vals, return_counts=True)
            if len(uniq) == 1:
                continue
            if nrep < 3 or counts.max() < (nrep // 2 + 1):
                # two replicas disagreeing (or no majority) proves
                # corruption but cannot name the liar
                localizable = False
                continue
            majority = uniq[counts.argmax()]
            for r in range(nrep):
                if vals[r] != majority:
                    bad_ids.add(int(devs[r, col].id))
        if not bad_ids and localizable:
            return ("ok", None)
        if localizable and len(bad_ids) == 1:
            return ("mismatch", bad_ids.pop())
        return ("mismatch", None)

    # -- rollback + replay classification ------------------------------------

    def _prune_contaminated(self):
        """Delete every ``step_<N>`` checkpoint newer than the last
        validated update: a divergence detected N steps late has been
        checkpointing corrupt state the whole window — those saves must
        not be resume candidates. The ``MXTPU_CKPT_KEEP`` retention
        window exists precisely so something older survives this."""
        if not self.checkpoint_dir:
            return
        base = os.path.abspath(self.checkpoint_dir)
        if not os.path.isdir(base):
            return
        removed = []
        for name in os.listdir(base):
            m = re.match(r"step_(\d+)$", name)
            if m and int(m.group(1)) > self._last_good_update:
                shutil.rmtree(os.path.join(base, name), ignore_errors=True)
                try:
                    os.remove(os.path.join(base, name + ".inprogress"))
                except OSError:
                    pass
                removed.append(name)
        if removed:
            logging.warning(
                "integrity: pruned %d contaminated checkpoint(s) newer "
                "than validated update %d: %s", len(removed),
                self._last_good_update, sorted(removed))

    def recover(self, train_data, err: DivergenceDetected):
        """Rollback-and-replay for a sentinel breach (``fit``'s recovery
        loop). First breach at a position: prune contaminated saves,
        restore the newest surviving checkpoint, rewind the iterator and
        replay — a transient upset will not repeat. A SECOND breach at
        the same (epoch, batch) is a poison batch: quarantine it under
        the data-guard budget, then roll back once more and resume past
        it. Returns ``(begin_epoch, begin_batch)``."""
        if not self.checkpoint_dir:
            raise IntegrityAbort(
                "divergence detected but fit() has no checkpoint_dir to "
                "roll back to — aborting rather than training on "
                f"diverged state ({err})") from err
        key = (err.epoch, err.nbatch)
        n = self._replays.get(key, 0) + 1
        self._replays[key] = n
        if n > 1:
            # deterministic replay reproduced the divergence at the same
            # position: the batch is poison, not the hardware
            self._quarantine_batch(key)
        self._prune_contaminated()
        tr = self.trainer
        restored = tr.restore_latest(self.checkpoint_dir)
        if restored is None:
            raise IntegrityAbort(
                f"divergence at update ~{err.breach_step} but "
                f"{self.checkpoint_dir!r} holds no validated checkpoint "
                "to roll back to") from err
        _count("replays")
        _count("rollbacks")
        begin_epoch = max(getattr(tr, "_restored_epoch", 0), 0)
        begin_batch = 0
        iter_state = getattr(tr, "_restored_iter_state", None)
        if iter_state is not None:
            from .data import apply_resume_state
            begin_epoch, begin_batch = apply_resume_state(
                train_data, iter_state)
        self.on_recovered()
        logging.warning(
            "integrity: rolled back to step_%s after divergence "
            "(replay %d at epoch %d batch %d), resuming at epoch %d "
            "batch %d", restored, n, err.epoch, err.nbatch, begin_epoch,
            begin_batch)
        return begin_epoch, begin_batch

    def _quarantine_batch(self, key):
        self._quarantined.add(key)
        _count("quarantines")
        batch = getattr(self.trainer, "_global_batch", None) or 1
        skipped = len(self._quarantined) * batch
        if skipped > self.policy.max_skipped_records:
            from .data import DataBudgetExceeded
            raise DataBudgetExceeded(
                f"integrity replay quarantined {len(self._quarantined)} "
                f"poison batch(es) (~{skipped} records), exceeding the "
                f"max_skipped_records={self.policy.max_skipped_records} "
                "budget — refusing to silently drop more data")
        logging.warning(
            "integrity: batch (epoch %d, nbatch %d) diverged again on "
            "deterministic replay — quarantined as poison (%d/%d record "
            "budget used)", key[0], key[1], skipped,
            self.policy.max_skipped_records)

    def is_quarantined(self, epoch: int, nbatch: int) -> bool:
        """True when replay classification condemned this batch."""
        return (epoch, nbatch) in self._quarantined

    def on_recovered(self):
        """Reset the breach latch after ANY successful recovery (our own
        rollback, or the elastic controller's re-mesh + restore): fresh
        sentinel statistics, reopened commit gate, and the restored
        update counter becomes the new validated baseline."""
        self.breached = False
        self._since = 0
        tr = self.trainer
        if hasattr(tr, "_reset_integrity_state"):
            tr._reset_integrity_state()
        self._last_good_update = min(self._last_good_update,
                                     tr._num_update)
