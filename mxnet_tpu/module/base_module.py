"""BaseModule: the high-level train/score/predict interface.

Reference: python/mxnet/module/base_module.py — BaseModule.fit:376 (epoch
loop :476-492), forward_backward:189, score, predict, iter_predict,
init_params/set_params plumbing. Same API; the loops here are structured
around a lookahead batch generator (so ``prepare`` sees the upcoming
batch while the current one is in flight — the async-prefetch contract)
and ``predict`` is just a fold over ``iter_predict``.
"""
from __future__ import annotations

import logging
import time
from typing import List, Optional

from .. import metric as metric_mod
from .. import ndarray as nd
from .. import profiler as _profiler
from ..base import MXNetError
from ..callback import BatchEndParam
from ..initializer import Uniform

__all__ = ["BaseModule"]

_END = object()


def _as_list(obj):
    if obj is None:
        return []
    if isinstance(obj, list):
        return obj
    return [obj]


def _fire(callbacks, param):
    """Invoke one callback or a list of them with the same param."""
    for cb in _as_list(callbacks):
        cb(param)


def _lookahead(iterable, snapshot=None, want=None):
    """Yield (batch, upcoming, state) triples; ``upcoming`` is None on
    the last.

    The training loop hands ``upcoming`` to ``prepare`` so bucketing /
    prefetch modules can stage the next executor while the current step
    is still in flight (reference: the next_data_batch dance in
    base_module.py fit).

    ``snapshot`` (the iterator's ``state_dict`` when mid-epoch
    checkpointing is armed) is called after fetching each batch and
    *before* fetching the next — so ``state`` is the exact
    about-to-fetch-the-next-batch resume point, uncontaminated by the
    lookahead prefetch. ``want(k)`` (k = 0-based position in this
    epoch's stream) gates the snapshot to the batches that will
    actually checkpoint — state_dict() cost is source-defined
    (arbitrary iterators may pay O(dataset)), so it must not run every
    batch."""
    it = iter(iterable)
    with _profiler.span("fit.fetch", batch=0):
        here = next(it, _END)
    k = 0
    while here is not _END:
        state = None
        if snapshot is not None and (want is None or want(k)):
            state = snapshot()
        # batch k+1 is fetched before step k runs: each fetch carries the
        # ordinal of the batch it fetches, the loop's body that of batch k
        with _profiler.span("fit.fetch", batch=k + 1):
            nxt = next(it, _END)
        _profiler.set_batch(k)
        yield here, (None if nxt is _END else nxt), state
        here = nxt
        k += 1
    _profiler.set_batch(None)


def _resolve_metric(m):
    return m if isinstance(m, metric_mod.EvalMetric) else metric_mod.create(m)


def _check_input_names(symbol, names, typename, throw):
    known = set(symbol.list_arguments())
    bad = [n for n in names if n not in known]
    if not bad:
        return
    param_suffixes = ("_weight", "_bias", "_gamma", "_beta")
    data_like = [a for a in symbol.list_arguments()
                 if not a.endswith(param_suffixes)]
    msg = (f"You created Module with Module(..., {typename}_names={names}) "
           f"but input with name '{bad[0]}' is not found in "
           f"symbol.list_arguments(). Did you mean one of: \n"
           + "\n".join(data_like))
    if throw:
        raise ValueError(msg)
    logging.warning(msg)


class BaseModule:
    def __init__(self, logger=logging):
        self.logger = logger
        self.binded = False
        self.for_training = False
        self.inputs_need_grad = False
        self.params_initialized = False
        self.optimizer_initialized = False
        self._symbol = None

    # -- properties subclasses provide ---------------------------------------
    @property
    def data_names(self):
        raise NotImplementedError(
            f"{type(self).__name__} must implement data_names")

    @property
    def output_names(self):
        raise NotImplementedError(
            f"{type(self).__name__} must implement output_names")

    @property
    def data_shapes(self):
        raise NotImplementedError(
            f"{type(self).__name__} must implement data_shapes")

    @property
    def label_shapes(self):
        raise NotImplementedError(
            f"{type(self).__name__} must implement label_shapes")

    @property
    def output_shapes(self):
        raise NotImplementedError(
            f"{type(self).__name__} must implement output_shapes")

    @property
    def symbol(self):
        return self._symbol

    # -- abstract ops --------------------------------------------------------
    def bind(self, *args, **kwargs):
        raise NotImplementedError(
            f"{type(self).__name__} must implement bind")

    def init_params(self, *args, **kwargs):
        raise NotImplementedError(
            f"{type(self).__name__} must implement init_params")

    def init_optimizer(self, *args, **kwargs):
        raise NotImplementedError(
            f"{type(self).__name__} must implement init_optimizer")

    def forward(self, data_batch, is_train=None):
        raise NotImplementedError(
            f"{type(self).__name__} must implement forward")

    def backward(self, out_grads=None):
        raise NotImplementedError(
            f"{type(self).__name__} must implement backward")

    def update(self):
        raise NotImplementedError(
            f"{type(self).__name__} must implement update")

    def get_outputs(self, merge_multi_context=True):
        raise NotImplementedError(
            f"{type(self).__name__} must implement get_outputs")

    def get_input_grads(self, merge_multi_context=True):
        raise NotImplementedError(
            f"{type(self).__name__} must implement get_input_grads")

    def get_params(self):
        raise NotImplementedError(
            f"{type(self).__name__} must implement get_params")

    def update_metric(self, eval_metric, labels):
        raise NotImplementedError(
            f"{type(self).__name__} must implement update_metric")

    # -- composite ops -------------------------------------------------------
    def forward_backward(self, data_batch):
        """reference: base_module.py:189"""
        self.forward(data_batch, is_train=True)
        self.backward()

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True, allow_extra=False):
        self.init_params(initializer=None, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init, allow_extra=allow_extra)

    def save_params(self, fname):
        arg_params, aux_params = self.get_params()
        tagged = {f"arg:{k}": v for k, v in arg_params.items()}
        tagged.update((f"aux:{k}", v) for k, v in aux_params.items())
        nd.save(fname, tagged)

    def load_params(self, fname):
        groups = {"arg": {}, "aux": {}}
        for tagged_name, value in nd.load(fname).items():
            tag, _, name = tagged_name.partition(":")
            if tag not in groups or not name:
                raise ValueError(f"Invalid param file {fname}")
            groups[tag][name] = value
        self.set_params(groups["arg"], groups["aux"])

    # -- scoring / prediction ------------------------------------------------
    def _trimmed_outputs(self, batch):
        """Forward outputs with the batch's pad rows dropped."""
        keep = None if not batch.pad else -batch.pad
        return [out[:keep] if keep else out for out in self.get_outputs()]

    def score(self, eval_data, eval_metric, num_batch=None,
              batch_end_callback=None, score_end_callback=None, reset=True,
              epoch=0):
        """reference: base_module.py score"""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        eval_metric = _resolve_metric(eval_metric)
        eval_metric.reset()
        seen = 0
        for eval_batch in eval_data:
            if num_batch is not None and seen == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            self.update_metric(eval_metric, eval_batch.label)
            _fire(batch_end_callback,
                  BatchEndParam(epoch=epoch, nbatch=seen,
                                eval_metric=eval_metric, locals=locals()))
            seen += 1
        _fire(score_end_callback,
              BatchEndParam(epoch=epoch, nbatch=seen,
                            eval_metric=eval_metric, locals=locals()))
        return eval_metric.get_name_value()

    def as_serving_backend(self, input_name=None, quant=None,
                           calib_data=None, quant_config=None,
                           stats_path=None):
        """Adapt this bound module for the serving runtime
        (:class:`mxnet_tpu.serving.InferenceServer`): forward-only, one
        host batch in, numpy outputs back (docs/how_to/serving.md).

        ``quant`` (default: the ``MXTPU_QUANT`` knob) turns on int8
        post-training quantization (docs/how_to/quantization.md):
        per-tensor scales calibrated from ``calib_data`` (any DataIter /
        iterable of batches; snapshot to the manifest-covered
        ``stats_path`` sidecar so a reloaded server never
        re-calibrates), weights stored int8, and a measured accuracy
        gate that falls back to this fp32 backend — with a typed
        :class:`~mxnet_tpu.quant.QuantAccuracyWarning` — rather than
        ship a model beyond ``quant_config.max_accuracy_delta``."""
        from ..base import getenv
        from ..serving.backends import ModuleBackend
        if quant is None:
            quant = bool(getenv("MXTPU_QUANT", 0, int))
        if not quant:
            return ModuleBackend(self, input_name=input_name)
        if calib_data is None:
            from ..base import MXNetError
            raise MXNetError(
                "as_serving_backend(quant=True) needs calib_data — "
                "post-training quantization calibrates activation "
                "scales (and measures the accuracy gate) on a handful "
                "of representative batches")
        from ..quant import quantize_backend
        return quantize_backend(self, calib_data, config=quant_config,
                                stats_path=stats_path,
                                input_name=input_name)

    def as_decode_backend(self, state_names):
        """Adapt this bound module as one *stateful decode step* for the
        in-flight batcher (:class:`mxnet_tpu.serving.InflightBatcher`):
        ``state_names`` are the data inputs carrying per-slot recurrent
        state, and the symbol's last ``len(state_names)`` outputs are
        the next states in the same order (docs/how_to/serving.md)."""
        from ..serving.slots import ModuleStepBackend
        return ModuleStepBackend(self, state_names)

    def iter_predict(self, eval_data, num_batch=None, reset=True):
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            yield (self._trimmed_outputs(eval_batch), nbatch, eval_batch)

    def predict(self, eval_data, num_batch=None, merge_batches=True,
                reset=True, always_output_list=False):
        """reference: base_module.py predict — here a fold over
        iter_predict."""
        per_batch = [[o.copy() for o in outs] for outs, _, _ in
                     self.iter_predict(eval_data, num_batch=num_batch,
                                       reset=reset)]
        if not per_batch:
            return per_batch
        if not merge_batches:
            return per_batch
        widths = {len(outs) for outs in per_batch}
        assert len(widths) == 1, \
            "Cannot merge batches, as num of outputs is not the same " \
            "in mini-batches. Maybe bucketing is used?"
        merged = [nd.concatenate(column) for column in zip(*per_batch)]
        if len(merged) == 1 and not always_output_list:
            return merged[0]
        return merged

    # -- the main training loop ----------------------------------------------
    def fit(self, train_data, eval_data=None, eval_metric="acc",
            epoch_end_callback=None, batch_end_callback=None, kvstore="local",
            optimizer="sgd", optimizer_params=(("learning_rate", 0.01),),
            eval_end_callback=None, eval_batch_end_callback=None,
            initializer=Uniform(0.01), arg_params=None, aux_params=None,
            allow_missing=False, force_rebind=False, force_init=False,
            begin_epoch=0, num_epoch=None, validation_metric=None,
            monitor=None, checkpoint_prefix=None, checkpoint_period=1,
            checkpoint_batch_period=None, resume=None,
            save_optimizer_states=True, supervisor=None,
            async_checkpoint=None):
        """reference: base_module.py:376 — the canonical Module training
        loop: bind → init params/optimizer → per-epoch train pass with
        lookahead prepare, then the optional validation pass.

        Fault tolerance (docs/how_to/fault_tolerance.md,
        docs/how_to/data_resilience.md): with ``checkpoint_prefix`` set,
        a manifest-covered checkpoint (params + optimizer state) is
        written atomically every ``checkpoint_period`` epochs — plus,
        with ``checkpoint_batch_period=N``, every N batches *within* an
        epoch, including the data iterator's ``state_dict()`` (position
        + shuffle RNG). ``resume='auto'`` discovers the newest *valid*
        checkpoint at that prefix and continues from its epoch — and,
        when the checkpoint carries iterator state and ``train_data``
        supports ``load_state_dict``, from its exact batch position, so
        the resumed run replays a bitwise-identical batch sequence; with
        no valid checkpoint it starts fresh. ``resume=<int>`` demands
        that specific epoch.

        Preemption awareness (docs/how_to/preemption.md): ``supervisor``
        (True, a :class:`~mxnet_tpu.resilience.TrainingSupervisor`, or
        armed process-wide via ``MXTPU_SUPERVISOR=1``) makes the loop
        survive what doesn't raise — SIGTERM finishes the in-flight
        step, checkpoints with iterator state, writes a clean-exit
        marker and raises :class:`~mxnet_tpu.resilience.Preempted`
        (typed exit code); a stalled step walks the retry → rebind →
        abort escalation ladder; repeated crashes at one (epoch, batch)
        back off exponentially and eventually quarantine that batch.

        ``async_checkpoint`` (default: the ``MXTPU_ASYNC_CKPT`` knob)
        moves every fit checkpoint onto the background writer
        (:class:`~mxnet_tpu.resilience.AsyncCheckpointer`,
        docs/how_to/fault_tolerance.md): the loop pays only a host
        snapshot; rolls and sweeps of superseded stems run post-commit
        on the writer so the newest committed checkpoint is never
        deleted ahead of its successor; a preemption *flushes* the
        pending snapshot before the clean-exit marker; a failed
        background write surfaces as a typed
        :class:`~mxnet_tpu.resilience.AsyncCheckpointError` on the next
        checkpoint."""
        assert num_epoch is not None, "please specify number of epochs"

        from ..resilience import supervisor as _sup_mod
        sup = _sup_mod.resolve(supervisor)

        resume_states = None
        resume_iter_state = None
        begin_batch = 0
        resumed = False
        resumed_label = None
        if resume is True:   # fit(resume=True) means 'auto', not epoch 1
            resume = "auto"
        if resume is not None and resume is not False:
            assert checkpoint_prefix, "resume requires checkpoint_prefix"
            from ..resilience import CheckpointCorrupt
            from ..resilience.checkpoint import (AUTO, epoch_of_label,
                                                 load_checkpoint_ex,
                                                 load_iter_state)
            try:
                # resume=<int> demands that exact epoch (no fallback to a
                # different one); only 'auto' may walk back to an older
                # valid checkpoint
                (ck_epoch, _, ck_arg, ck_aux,
                 resume_states) = load_checkpoint_ex(
                    checkpoint_prefix,
                    AUTO if resume == "auto" else resume,
                    allow_fallback=(resume == "auto"))
                arg_params, aux_params = ck_arg, ck_aux
                force_init = True
                if isinstance(ck_epoch, int):
                    # a mid-epoch label maps back to its in-progress
                    # epoch; the iterator state below refines the batch
                    begin_epoch = epoch_of_label(ck_epoch)
                else:
                    self.logger.warning(
                        "resumed epoch-less checkpoint %s carries no "
                        "epoch number; fit restarts at epoch 0 on the "
                        "restored params", checkpoint_prefix)
                try:
                    resume_iter_state = load_iter_state(checkpoint_prefix,
                                                        ck_epoch)
                except CheckpointCorrupt as err:
                    # the params/states already loaded and verified; a
                    # bad iterator-state file must degrade to an
                    # epoch-start resume, not throw that work away
                    self.logger.warning(
                        "checkpoint %s: iterator state unreadable (%s); "
                        "resuming at the start of epoch %s instead of "
                        "mid-epoch", checkpoint_prefix, err, ck_epoch)
                self.logger.info("fit: resuming from checkpoint %s epoch=%s",
                                 checkpoint_prefix, ck_epoch)
                resumed = True
                resumed_label = ck_epoch
                # an abnormal exit strands superseded mid-epoch stems
                # (killed between a mid save and its roll, or before the
                # epoch-end sweep); GC them now, bounded by the stem we
                # actually loaded so a fallback never deletes newer
                # evidence (docs/how_to/preemption.md)
                from ..resilience.checkpoint import sweep_stale_checkpoints
                sweep_stale_checkpoints(checkpoint_prefix, used=ck_epoch)
            except (FileNotFoundError, CheckpointCorrupt):
                # only "nothing to resume" starts fresh; an unreachable
                # checkpoint directory (dead mount, permissions) raises —
                # silently retraining from scratch would bury the prior
                # lineage under newer checkpoints at the same prefix
                if resume != "auto":
                    raise
                self.logger.info("fit(resume='auto'): no valid checkpoint "
                                 "at %s, starting fresh", checkpoint_prefix)

        from ..resilience.data import (apply_resume_state,
                                       supports_state as _supports_state)
        if resume_iter_state is not None:
            begin_epoch, begin_batch = apply_resume_state(
                train_data, resume_iter_state, logger=self.logger)

        crash_guard = None
        if sup is not None and checkpoint_prefix:
            if resumed:
                # the clean-exit marker served its purpose: this resume
                # consumed the preemption checkpoint
                _sup_mod.clear_preempt_marker(checkpoint_prefix)
                # crash-loop protection: repeated resumes at the same
                # (epoch, batch) back off exponentially; past the limit
                # that batch is presumed poison and quarantined under
                # the DataGuardPolicy budget (resilience/supervisor.py)
                crash_guard = sup.crash_guard(checkpoint_prefix)
                crash_guard.on_resume(begin_epoch, begin_batch)
                begin_batch = _sup_mod.skip_quarantined_batches(
                    train_data, crash_guard, begin_epoch, begin_batch,
                    logger=self.logger)
            else:
                # a fresh run at this prefix starts a new lineage: a
                # stale clean-exit marker must not claim it was preempted
                _sup_mod.clear_preempt_marker(checkpoint_prefix)

        # warm-start accounting for resumed runs: the persistent
        # compilation cache (mxnet_tpu/compiler) serves this process's
        # step programs if an earlier run compiled them — report what
        # the resume actually skipped once the first epoch materialized
        # every program (docs/how_to/compiler.md)
        resume_compiler_base = None
        if resume is not None and resume is not False:
            from .. import compiler as _compiler
            resume_compiler_base = _compiler.stats()

        self.bind(data_shapes=train_data.provide_data,
                  label_shapes=train_data.provide_label,
                  for_training=True, force_rebind=force_rebind)
        if monitor is not None:
            self.install_monitor(monitor)
        self.init_params(initializer=initializer, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init)
        self.init_optimizer(kvstore=kvstore, optimizer=optimizer,
                            optimizer_params=optimizer_params)
        if resume_states is not None and hasattr(self,
                                                 "load_optimizer_states"):
            self.load_optimizer_states(resume_states)

        train_metric = _resolve_metric(eval_metric)
        validation_metric = validation_metric or train_metric

        can_snapshot = _supports_state(train_data)
        if can_snapshot and checkpoint_prefix \
                and (checkpoint_batch_period or sup is not None) \
                and hasattr(train_data, "enable_state_snapshots"):
            # PrefetchingIter-style sources capture per-prefetch
            # snapshots only once armed — they cost O(dataset) each, so
            # arming is tied to batch-period checkpointing (or an armed
            # supervisor, whose preemption checkpoint can land on any
            # batch); the epoch-end-only snapshot degrades gracefully
            train_data.enable_state_snapshots()
        batch_ckpt = None
        mid_saver = None
        if checkpoint_prefix and (checkpoint_batch_period
                                  or sup is not None):
            from ..resilience.checkpoint import (mid_epoch_label,
                                                 remove_checkpoint)
            prev_mid = [None]

            def _save_mid_epoch(ep, nbatch, iter_snapshot):
                # a FRESH stem per save (mid_epoch_label): never
                # overwrite the previous good checkpoint in place —
                # a torn multi-file replace would destroy it. The
                # superseded mid-epoch stem is rolled afterwards so
                # a long epoch holds at most one on disk.
                label = mid_epoch_label(ep, nbatch)
                if prev_mid[0] == label:
                    # this batch's period save already captured exactly
                    # this state (a preempt/abort landing on a
                    # checkpoint batch): re-writing would delete-then-
                    # rewrite the newest good checkpoint, and the roll
                    # below would then remove the stem it just wrote
                    return label
                prev = prev_mid[0]
                # the roll of the superseded stem rides as post_commit:
                # it runs only after the new manifest is on disk (sync
                # or on the async writer), so the newest committed
                # checkpoint is never deleted before its successor
                # commits. An async-superseded snapshot skips its
                # post_commit entirely — its predecessor then outlives
                # one extra roll (GC'd by the epoch-end sweep or the
                # resume-time sweep_stale_checkpoints), which is the
                # safe direction.
                self._write_fit_checkpoint(
                    checkpoint_prefix, label, save_optimizer_states,
                    iter_state=({"epoch": ep, "nbatch": nbatch + 1,
                                 "iterator": iter_snapshot}
                                if iter_snapshot is not None else None),
                    post_commit=((lambda: remove_checkpoint(
                        checkpoint_prefix, prev))
                        if prev is not None else None))
                prev_mid[0] = label
                return label

            mid_saver = _save_mid_epoch
            if checkpoint_batch_period and can_snapshot:
                batch_ckpt = (max(1, int(checkpoint_batch_period)),
                              _save_mid_epoch)
        if checkpoint_batch_period and not can_snapshot:
            self.logger.warning(
                "checkpoint_batch_period=%s ignored: train_data (%s) "
                "has no state_dict()", checkpoint_batch_period,
                type(train_data).__name__)

        if async_checkpoint is None:
            from .. import config as _config
            async_checkpoint = bool(_config.get("MXTPU_ASYNC_CKPT"))
        actx = None
        if async_checkpoint and checkpoint_prefix:
            from ..resilience import AsyncCheckpointer
            actx = AsyncCheckpointer(name="fit-ckpt-writer")
            self._fit_async_ckpt = actx

        def _finish_async():
            # runs on every exit (success, Preempted, abort): surface a
            # stored writer failure and stop the thread. The preempt /
            # abort paths flushed already, so this is a no-op there and
            # cannot mask their typed exception.
            self._fit_async_ckpt = None
            actx.close(flush=True)

        from contextlib import ExitStack
        with ExitStack() as _sup_stack:
            if actx is not None:
                _sup_stack.callback(_finish_async)
            if sup is not None:
                _sup_stack.enter_context(sup.attach())
            self._fit_epochs(
                train_data, eval_data, begin_epoch, begin_batch, num_epoch,
                train_metric, validation_metric, batch_end_callback,
                epoch_end_callback, eval_end_callback,
                eval_batch_end_callback, monitor, checkpoint_prefix,
                checkpoint_period, save_optimizer_states, can_snapshot,
                batch_ckpt, resume_compiler_base, sup, mid_saver,
                crash_guard, resumed_label)

    def _fit_epochs(self, train_data, eval_data, begin_epoch, begin_batch,
                    num_epoch, train_metric, validation_metric,
                    batch_end_callback, epoch_end_callback,
                    eval_end_callback, eval_batch_end_callback, monitor,
                    checkpoint_prefix, checkpoint_period,
                    save_optimizer_states, can_snapshot, batch_ckpt,
                    resume_compiler_base, sup, mid_saver, crash_guard,
                    resumed_label=None):
        """The epoch loop of :meth:`fit` (extracted so the supervisor
        context wraps exactly the supervised region)."""
        for epoch in range(begin_epoch, num_epoch):
            started = time.time()
            nseen = self._train_one_epoch(
                train_data, epoch, train_metric, batch_end_callback,
                monitor, begin_batch=begin_batch, batch_ckpt=batch_ckpt,
                sup=sup,
                snapshot_fn=(train_data.state_dict if can_snapshot
                             else None),
                mid_saver=mid_saver, crash_guard=crash_guard,
                marker_target=checkpoint_prefix,
                resumed_label=resumed_label)
            # a mid-epoch resume whose checkpoint landed on the epoch's
            # last batch replays an empty tail: the epoch's end-of-epoch
            # callbacks and eval (almost certainly) already ran before
            # the crash — firing them again would double their side
            # effects. This is deliberately at-most-once: a crash in the
            # narrow window between that final checkpoint and the
            # callbacks skips them for that epoch (exactly-once through
            # kills would need transactional callback markers)
            replayed_empty_tail = begin_batch > 0 and nseen == 0
            begin_batch = 0
            if resume_compiler_base is not None:
                from .. import compiler as _compiler
                now = _compiler.stats()
                self.logger.info(
                    "fit(resume): compiler served %d cached program(s), "
                    "compiled %d fresh",
                    now["programs"]["loaded"]
                    - resume_compiler_base["programs"]["loaded"],
                    now["programs"]["compiled"]
                    - resume_compiler_base["programs"]["compiled"])
                resume_compiler_base = None
            for name, val in train_metric.get_name_value():
                self.logger.info("Epoch[%d] Train-%s=%f", epoch, name, val)
            self.logger.info("Epoch[%d] Time cost=%.3f", epoch,
                             time.time() - started)

            # sync the param snapshot back into the module so callbacks
            # (checkpointing) and the next epoch agree on one copy
            snapshot = self.get_params()
            self.set_params(*snapshot)
            if not replayed_empty_tail:
                for cb in _as_list(epoch_end_callback):
                    cb(epoch, self.symbol, *snapshot)
            # reset BEFORE the epoch-end checkpoint: the persisted
            # iterator state is then the fresh next-epoch position
            # (post-reshuffle), so a resumed shuffled run replays the
            # next epoch's batch sequence bitwise. When eval shares the
            # train iterator, eval must consume it first — keep the
            # legacy order (checkpoint → eval → reset, no iter state).
            shared_iter = eval_data is train_data
            if not shared_iter:
                train_data.reset()
            if checkpoint_prefix and (epoch + 1) % max(
                    1, int(checkpoint_period)) == 0:
                # checkpoint labeled epoch+1 == "epochs completed", matching
                # the do_checkpoint callback convention; resume picks it up
                # as begin_epoch
                iter_state = None
                if can_snapshot and not shared_iter:
                    try:
                        iter_state = {"epoch": epoch + 1, "nbatch": 0,
                                      "iterator": train_data.state_dict()}
                    except MXNetError as err:
                        # e.g. a PrefetchingIter whose per-prefetch
                        # snapshots are disarmed (no batch-period
                        # checkpointing): epoch-granularity resume
                        # without iterator state, as before this PR
                        self.logger.debug(
                            "epoch-end iterator snapshot unavailable "
                            "(%s); checkpoint carries no iterator state",
                            err)
                # the mid-epoch sweep rides as post_commit: the stems
                # it deletes are superseded only once THIS checkpoint's
                # manifest is on disk (ordering holds on the async
                # writer too)
                from ..resilience.checkpoint import \
                    clear_mid_epoch_checkpoints
                self._write_fit_checkpoint(
                    checkpoint_prefix, epoch + 1, save_optimizer_states,
                    iter_state=iter_state,
                    post_commit=(lambda _e=epoch + 1:
                                 clear_mid_epoch_checkpoints(
                                     checkpoint_prefix, _e)))

            if eval_data and not replayed_empty_tail:
                for name, val in self.score(
                        eval_data, validation_metric,
                        score_end_callback=eval_end_callback,
                        batch_end_callback=eval_batch_end_callback,
                        epoch=epoch):
                    self.logger.info("Epoch[%d] Validation-%s=%f", epoch,
                                     name, val)
            if shared_iter:
                train_data.reset()

    def _write_fit_checkpoint(self, prefix, epoch, save_optimizer_states,
                              iter_state=None, post_commit=None):
        """One checkpoint write for fit(): the module's own
        save_checkpoint when it has one (params + optimizer state +
        iterator state, all manifest-covered), else the params-only
        model.save_checkpoint fallback.

        ``post_commit`` runs strictly after the checkpoint's manifest is
        on disk (the roll of a superseded stem, the mid-epoch sweep) —
        synchronously here, or on the writer thread when fit armed the
        AsyncCheckpointer. That ordering is the safety invariant: the
        previous good checkpoint is never deleted before its successor
        is fully committed."""
        actx = getattr(self, "_fit_async_ckpt", None)
        if actx is not None:
            self._submit_fit_checkpoint(
                actx, prefix, epoch, save_optimizer_states,
                iter_state=iter_state, post_commit=post_commit)
            return
        if hasattr(self, "save_checkpoint"):
            self.save_checkpoint(prefix, epoch,
                                 save_optimizer_states=save_optimizer_states,
                                 iter_state=iter_state)
        else:
            if save_optimizer_states:
                self.logger.warning(
                    "%s has no save_checkpoint; checkpointing "
                    "params only (optimizer state will be "
                    "reinitialized on resume)", type(self).__name__)
            from ..model import save_checkpoint as _save_ckpt
            _save_ckpt(prefix, epoch, self.symbol, *self.get_params(),
                       iter_state=iter_state)
        if post_commit is not None:
            post_commit()

    def _submit_fit_checkpoint(self, actx, prefix, epoch,
                               save_optimizer_states, iter_state=None,
                               post_commit=None):
        """Async variant of :meth:`_write_fit_checkpoint`: the caller's
        thread pays only the host snapshot (params, optimizer bytes —
        the ``checkpoint.snapshot`` fault site) plus an ``.inprogress``
        marker, then hands serialization + the atomic commit to the
        background writer. Until the writer lands the manifest the
        marker keeps discovery/sweeps away from the stem; a superseded
        snapshot (depth-1 back-pressure) never wrote files, so its
        cleanup is just clearing that marker."""
        from ..resilience import faults
        from ..resilience.checkpoint import (clear_inprogress,
                                             mark_inprogress)
        faults.fault_point("checkpoint.snapshot")
        states = None
        if hasattr(self, "save_checkpoint"):
            # mirror Module.save_checkpoint's host sync, then snapshot
            self._sync_params_from_devices()
            if save_optimizer_states:
                states = self._optimizer_state_bytes()
        elif save_optimizer_states:
            self.logger.warning(
                "%s has no save_checkpoint; checkpointing params only "
                "(optimizer state will be reinitialized on resume)",
                type(self).__name__)
        from .. import ndarray as _nd
        from ..resilience.async_checkpoint import _copy_tree
        # get_params() hands back NDArrays whose device buffers the next
        # fused (donating) step may invalidate — deep-copy to host NOW;
        # the writer serializes only this decoupled snapshot
        raw_args, raw_auxs = self.get_params()
        args = {k: _nd.array(v) for k, v in _copy_tree(raw_args).items()}
        auxs = {k: _nd.array(v) for k, v in _copy_tree(raw_auxs).items()}
        symbol = self.symbol
        mark_inprogress(prefix, epoch)

        def _commit():
            from ..model import save_checkpoint as _save_ckpt
            _save_ckpt(prefix, epoch, symbol, args, auxs,
                       states=states, iter_state=iter_state)
            if post_commit is not None:
                post_commit()

        actx.submit(epoch, _commit,
                    on_supersede=lambda: clear_inprogress(prefix, epoch))

    def _train_one_epoch(self, train_data, epoch, train_metric,
                         batch_end_callback, monitor, begin_batch=0,
                         batch_ckpt=None, sup=None, snapshot_fn=None,
                         mid_saver=None, crash_guard=None,
                         marker_target=None, resumed_label=None):
        """Returns the number of batches trained this epoch."""
        train_metric.reset()
        snapshot = want = None
        if sup is not None and snapshot_fn is not None:
            # preemption/stall checkpoints can land on ANY batch, and a
            # checkpoint's params must pair with the EXACT iterator
            # position (a stale snapshot would double-train the gap on
            # resume) — so the supervised loop deliberately snapshots
            # every batch, overriding the want() cost gate below. Cheap
            # for the standard iterators (position + rng); a source
            # whose state_dict pays O(dataset) should amortize it like
            # PrefetchingIter's armed per-prefetch snapshots, or report
            # supports_state False and accept epoch-granularity preempt
            snapshot = snapshot_fn
        elif batch_ckpt is not None:
            snapshot = snapshot_fn or train_data.state_dict
            period = batch_ckpt[0]
            # snapshot only the batches that will actually checkpoint
            want = lambda k: (begin_batch + k + 1) % period == 0  # noqa: E731
        # fused whole-step path (perf/step_runtime.py): forward, backward
        # and the optimizer update in ONE donated XLA program. Modules
        # that cannot take it (monitor installed, kvstore, sparse grads,
        # exotic optimizer, ...) return None and keep the imperative pair
        fused_step = None
        rebind = None
        if monitor is None:
            getter = getattr(self, "_fused_train_step", None)
            if getter is not None:
                fused_step = getter()
            if fused_step is not None:
                # stall-ladder rung 2: rebuild the donated whole-step
                # program (FusedStep.rebind via the module's stepper)
                rebind = getattr(self, "_rebind_fused_step", None)
        nseen = 0
        prev_state = None       # last *trained* position (abort rewind)
        progressed = False
        for k, (batch, upcoming, state) in enumerate(
                _lookahead(train_data, snapshot, want)):
            nbatch = begin_batch + k
            nseen = k + 1
            if monitor is not None:
                monitor.tic()
            if sup is None:
                if fused_step is not None:
                    fused_step(batch)
                else:
                    self.forward_backward(batch)
                    self.update()
            else:
                def _one_step(_b=batch):
                    if fused_step is not None:
                        fused_step(_b)
                    else:
                        self.forward_backward(_b)
                        self.update()

                def _abort_ckpt(err, _nb=nbatch, _ps=prev_state):
                    # ladder exhausted: persist the last consistent,
                    # fully-trained position (the stalled batch itself
                    # replays on resume)
                    if mid_saver is None:
                        return
                    from ..resilience.checkpoint import mid_epoch_label
                    target = mid_epoch_label(epoch, max(_nb - 1, 0))
                    if target == resumed_label:
                        # zero successful steps since resume: the stem
                        # this run resumed from IS this exact state —
                        # rewriting it in place (with the job already
                        # dying) risks tearing the only good checkpoint
                        return
                    mid_saver(epoch, max(_nb - 1, 0),
                              _ps if _nb > 0 else None)
                    _actx = getattr(self, "_fit_async_ckpt", None)
                    if _actx is not None:
                        # the job is dying: the abort checkpoint must be
                        # durable before the typed abort propagates
                        _actx.flush()

                sup.run_step(_one_step, rebind=rebind,
                             on_abort=_abort_ckpt,
                             label=f"step epoch {epoch} batch {nbatch}")
            if crash_guard is not None and not progressed:
                # first successful step past the resume point: the
                # crash-loop attempt counter starts over
                crash_guard.note_progress()
                progressed = True
            if upcoming is not None:
                self.prepare(upcoming)
            with _profiler.span("fit.metric"):
                self.update_metric(train_metric, batch.label)
            if monitor is not None:
                monitor.toc_print()
            if batch_end_callback is not None:
                with _profiler.span("fit.callbacks"):
                    _fire(batch_end_callback,
                          BatchEndParam(epoch=epoch, nbatch=nbatch,
                                        eval_metric=train_metric,
                                        locals=locals()))
            if batch_ckpt is not None and (nbatch + 1) % batch_ckpt[0] == 0:
                batch_ckpt[1](epoch, nbatch, state)
            if sup is not None and sup.check_preempt():
                # graceful preemption: the in-flight step above finished;
                # checkpoint exactly this position (+ iterator state when
                # snapshots are available), drop the clean-exit marker,
                # exit typed. resume='auto' continues bitwise.
                label = None
                if mid_saver is not None:
                    label = mid_saver(epoch, nbatch, state)
                _actx = getattr(self, "_fit_async_ckpt", None)
                sup.preempt_exit(marker_target, label=label, epoch=epoch,
                                 nbatch=nbatch,
                                 flush=(_actx.flush if _actx is not None
                                        else None))
            if state is not None:
                prev_state = state
        return nseen

    def prepare(self, data_batch):
        pass

    def install_monitor(self, mon):
        raise NotImplementedError(
            f"{type(self).__name__} must implement install_monitor")

    def getstate(self):
        return self.__dict__
