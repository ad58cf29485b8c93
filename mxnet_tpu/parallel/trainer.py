"""SPMDTrainer: one jit-compiled, mesh-sharded training step.

Reference analogue: the whole update path of stack §3.1 —
``ExecutorGroup.forward/backward`` per device + kvstore push/pull +
``Updater`` (module.py:556-615, model.py:105-132, comm.h reduce) — fused
into a single XLA program: forward, backward (vjp), cross-device gradient
reduction (psum inserted by the SPMD partitioner), and the optimizer
update, with parameter/optimizer-state buffers donated in place. That
program is ``perf.step_runtime.FusedStep``'s, built under this trainer's
``ShardingPlan``: the trainer is its mesh front end (shapes, the plan,
placement of parameters and state, the fit loop, checkpoints) and holds no
step body of its own.

BatchNorm note: batch statistics are computed over the *global* sharded
batch (XLA lowers the mean/var to cross-replica collectives), i.e.
sync-BN — stronger than the reference's per-device statistics.
"""
from __future__ import annotations

import itertools
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import initializer as _init_mod, optimizer as _opt_mod
from .. import profiler as _profiler
from ..analysis.annotations import hot_path
from ..base import MXNetError
from ..ndarray import NDArray
from ..perf.step_runtime import CompileGuard, FusedStep
from .mesh import make_mesh
from .sharding import ShardingPlan, batch_pspec, divisibility_error

__all__ = ["SPMDTrainer"]


def _fetching(iterable):
    """``enumerate(iterable)`` for the fit loop: every ``next()`` is a
    ``fit.fetch`` span, and the loop's body runs under the fetched batch's
    ordinal (the k-th batch since the loop, which resets its iterator
    first, began). ``base_module._lookahead`` is Module.fit's."""
    it = iter(iterable)
    for k in itertools.count():
        try:
            with _profiler.span("fit.fetch", batch=k):
                batch = next(it)
        except StopIteration:
            _profiler.set_batch(None)
            return
        _profiler.set_batch(k)
        yield k, batch


class SPMDTrainer:
    """Train a symbol SPMD over a named mesh (dp via ``data`` axis, tp via
    ``model`` axis; further axes compose through custom param rules).

    The mesh front end of the one training step
    (:class:`~mxnet_tpu.perf.step_runtime.FusedStep`; ``Module.fit`` is
    the other). ``bind`` infers shapes, builds the ``ShardingPlan``,
    builds the step under it (graph passes, HBM budget gate, evaluator,
    riders' state, program key, jit: all the step's own), then
    initialises and places parameters, aux and optimizer state;
    ``params[n]`` is one array and ``states[n]`` the optimizer rule's own
    state, as checkpoints hold them. ``step`` places the batch and calls
    the step; ``fit`` is the loop with its checkpoints and recovery."""

    def __init__(self, symbol, optimizer="sgd", optimizer_params=None,
                 mesh=None, data_names: Sequence[str] = ("data",),
                 label_names: Sequence[str] = ("softmax_label",),
                 param_rules=None, dtype="float32", compute_dtype=None,
                 shard_optimizer_state=None, donate_buffers=True,
                 loss_scale=None, integrity=None):
        self._symbol = symbol
        self._mesh = mesh if mesh is not None else make_mesh()
        self._data_names = list(data_names)
        self._label_names = list(label_names)
        # param_rules: a legacy callable (name, shape, mesh) -> spec, an
        # ordered [(regex, PartitionSpec)] rule list, or None (the
        # MXTPU_PARTITION_RULES env rules, else the default tensor-
        # parallel rule) — resolved by the ShardingPlan built at bind
        self._param_rules = param_rules
        self._dtype = dtype
        # ZeRO-style update_on_kvstore analog (reference: the dist server
        # runs the optimizer on its 1/num_servers key shard,
        # kvstore_dist_server.h:175-186; SURVEY §5.8 psum_scatter):
        # optimizer state is additionally sharded over the *data* axis, so
        # each data-parallel device holds and updates only a 1/N slice.
        # Under GSPMD this turns the gradient allreduce into a
        # reduce_scatter feeding the sharded update, followed by an
        # all_gather of the updated params — halving comm exactly like the
        # reference's server-side update, and shrinking per-device
        # optimizer-state memory ~N x. None defers to the MXTPU_ZERO knob.
        self._shard_opt_req = shard_optimizer_state
        self._shard_opt = bool(shard_optimizer_state)
        self._plan: Optional[ShardingPlan] = None
        # mixed precision: master weights stay fp32, 2D+ weights are cast to
        # compute_dtype inside the step (reference analogue: mp_sgd_update's
        # fp32 master weights, optimizer_op.cc:114 — here the cast is traced
        # so XLA feeds the MXU bf16 operands directly). None defers to the
        # MXTPU_PRECISION mode (docs/how_to/quantization.md), which also
        # arms the dynamic loss-scale guard; ``loss_scale`` overrides
        # (True / LossScaleConfig / False).
        self._compute_dtype = compute_dtype
        self._loss_scale_req = loss_scale
        # silent-failure integrity guard (resilience/integrity.py): the
        # divergence sentinel rides the donated step like the loss-scale
        # state; None defers to MXTPU_INTEGRITY_PERIOD (0 = off,
        # bitwise-identical program), True/False/IntegrityConfig override
        self._integrity_req = integrity
        self._ig_cfg = None
        if isinstance(optimizer, str):
            optimizer = _opt_mod.create(optimizer, **(optimizer_params or {}))
        self._optimizer = optimizer
        # the step program (perf/step_runtime.py), built in bind(), where
        # input shapes are known so the remat-policy activation estimate
        # can engage; the trainer keeps the ORIGINAL symbol for naming/
        # shape surfaces, the FusedStep traces the optimized one, and
        # _opt_res is its record of the passes (mxnet_tpu/compiler)
        self._fused: Optional[FusedStep] = None
        self._opt_res = None
        self.params: Dict[str, jax.Array] = {}
        self.states: Dict[str, object] = {}
        self.aux: Dict[str, jax.Array] = {}
        self._num_update = 0
        self._rng = jax.random.PRNGKey(0)
        # donation is the default (in-place param/state update); tests
        # toggle it off to prove bitwise equivalence of the two modes
        self._donate = bool(donate_buffers)
        # retrace detector: steps after the first compile must hit the
        # trace cache. The bound step's own guard from bind() on.
        self.retrace_guard = CompileGuard("spmd-step")

    # -- initialization ----------------------------------------------------

    def bind(self, data_shapes, label_shapes=None,
             initializer=None, arg_params=None, aux_params=None):
        """Infer shapes, initialize + shard parameters, compile the step."""
        with _profiler.span("bind", args={"front": "spmd"}):
            with _profiler.span("bind.plan"):
                plan, fused, known, shapes, aux_shapes = self._bind_plan(
                    data_shapes, label_shapes)
            with _profiler.span("bind.params", args={}) as placed:
                params, aux = self._bind_params(
                    plan, fused._param_names, shapes, aux_shapes,
                    initializer or _init_mod.Xavier(magnitude=2.0),
                    arg_params, aux_params)
                leaves = list(params.values()) + list(aux.values())
                placed.args.update(bytes=sum(x.nbytes for x in leaves),
                                   leaves=len(leaves))
            _profiler.count("bind.param_bytes", placed.args["bytes"])
            with _profiler.span("bind.state", args={}) as made:
                states = self._bind_states(plan, fused, params, shapes)
                made.args["leaves"] = len(jax.tree_util.tree_leaves(states))
            self.params, self.states, self.aux = params, states, aux
            self._fused = fused
            self._opt_res = fused._opt_res
            self._ig_cfg = fused._ig_cfg
            self.retrace_guard = fused.guard    # a fresh program's, count 0
            self._in_shardings = self._input_shardings(known)
        return self

    def _bind_plan(self, data_shapes, label_shapes):
        """The sharding plan, the inferred shapes and the step program
        (``bind.plan``): everything that can refuse a bind, before any
        state of a previous one is replaced."""
        known = dict(data_shapes)
        known.update(label_shapes or {})
        # remembered for elastic re-binds: remesh() re-runs bind with the
        # same global shapes on a different mesh (resilience/elastic.py)
        self._bound_data_shapes = dict(data_shapes)
        self._bound_label_shapes = dict(label_shapes or {})
        self._global_batch = (int(known[self._data_names[0]][0])
                              if self._data_names
                              and self._data_names[0] in known else None)
        # the partition-rule engine resolved for THIS mesh: params,
        # grads, per-slot optimizer state, batch inputs. Rebuilt on
        # every (re)bind — an elastic re-mesh re-derives every spec
        # (ZeRO included) for the surviving topology.
        zero_req = self._shard_opt_req
        if zero_req is None and self._shard_opt:
            # back-compat toggle: tr._shard_opt = True before bind()
            zero_req = True
        # remember the resolved request so an elastic re-mesh through a
        # ZeRO-degenerate topology (data axis of 1) re-arms ZeRO when
        # the mesh grows back, instead of losing the mode
        self._shard_opt_req = zero_req
        plan = ShardingPlan(self._mesh, rules=self._param_rules,
                            zero=zero_req)
        if plan.zero_requested and "data" not in self._mesh.axis_names:
            raise MXNetError(
                "shard_optimizer_state (ZeRO) shards the weight update "
                "over the mesh 'data' axis, but this mesh has axes "
                f"{self._mesh.axis_names} — add a 'data' axis or disable "
                "ZeRO")
        self._plan = plan
        self._shard_opt = plan.zero
        # validate up front, BEFORE any state is replaced: failing after
        # params or the step were rebuilt would leave a torn half-bound
        # trainer behind the error. This is the first wall an elastic
        # re-mesh hits when it picks an incompatible device count, so
        # it must be the framework's own error (raised while the
        # trainer is still intact), not a jax shape blowup at step one.
        if "data" in self._mesh.axis_names:
            dsize = self._mesh.shape["data"]
            for n in list(self._data_names) + list(self._label_names):
                shp = known.get(n)
                if shp and shp[0] % dsize:
                    # with ZeRO on, the data-axis size IS the ZeRO
                    # shard degree (zero_degree), so one check covers
                    # both contracts — the message names both roles
                    raise divisibility_error(
                        shp[0], n, "data", dsize,
                        what="mesh (= ZeRO shard degree)" if plan.zero
                        else "mesh")
        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**known)
        arg_names = self._symbol.list_arguments()
        aux_names = self._symbol.list_auxiliary_states()
        io_names = set(self._data_names) | set(self._label_names)
        param_names = [n for n in arg_names if n not in io_names]
        shapes = dict(zip(arg_names, arg_shapes))

        # static per-param wd (lr multipliers fold into the dynamic lr
        # input); recompute multipliers now that idx2name is known so
        # biases/BN params get wd_mult=0 (reference: optimizer.py
        # set_wd_mult). The step reads them when it is built.
        self._optimizer.idx2name = dict(enumerate(param_names))
        self._optimizer.set_wd_mult(dict(self._optimizer.wd_mult))
        self._optimizer.set_lr_mult(dict(self._optimizer.lr_mult))
        # the step program: graph passes with the now-known bind shapes
        # (remat budget can price the activations), the HBM budget gate
        # (MXTPU_HBM_BUDGET_MB: the typed MemoryBudgetError), the
        # evaluator, the riders' state, the program key and the jit —
        # all FusedStep's, rebuilt on every (re)bind. Built HERE, before
        # any param/state of a previous bind is replaced, so an
        # over-budget re-mesh fails while the trainer is still intact
        # (same contract as the divisibility wall above).
        all_shapes = dict(shapes)
        all_shapes.update(dict(zip(aux_names, aux_shapes)))
        fused = FusedStep(
            self._symbol, self._optimizer, param_names,
            compute_dtype=self._compute_dtype, donate=self._donate,
            name="spmd-step", input_shapes=all_shapes,
            input_dtypes={n: str(self._dtype) for n in all_shapes},
            sharding=plan, loss_scale=self._loss_scale_req,
            integrity=self._integrity_req, kind="spmd-step")
        return plan, fused, known, shapes, dict(zip(aux_names, aux_shapes))

    def _bind_params(self, plan, param_names, shapes, aux_shapes,
                     initializer, arg_params, aux_params):
        """Every parameter and auxiliary state taken to the device under
        its spec (``bind.params``): the given value, else the
        initializer's."""
        mesh = self._mesh
        layouts = self._symbol._arg_layouts()
        params = {}
        for name in param_names:
            if arg_params and name in arg_params:
                host = np.asarray(arg_params[name].asnumpy()
                                  if isinstance(arg_params[name], NDArray)
                                  else arg_params[name])
            else:
                arr = NDArray(np.zeros(shapes[name], dtype=self._dtype))
                attrs = ({"__layout__": layouts[name]}
                         if name in layouts else None)
                initializer(_init_mod.InitDesc(name, attrs), arr)
                host = arr.asnumpy()
            spec = plan.param_spec(name, host.shape)
            params[name] = jax.device_put(host, NamedSharding(mesh, spec))
        aux = {}
        for name, shp in aux_shapes.items():
            if aux_params and name in aux_params:
                host = np.asarray(aux_params[name].asnumpy()
                                  if isinstance(aux_params[name], NDArray)
                                  else aux_params[name])
            else:
                arr = NDArray(np.zeros(shp, dtype=self._dtype))
                initializer(_init_mod.InitDesc(name), arr)
                host = arr.asnumpy()
            aux[name] = jax.device_put(host, NamedSharding(mesh, P()))
        return params, aux

    def _bind_states(self, plan, fused, params, shapes):
        """The optimizer state made on the device, a parameter at a time
        (``bind.state``)."""
        # optimizer-state sharding from the plan: param spec, plus (in
        # ZeRO mode) the first mesh-divisible unsharded dim split over
        # the data axis (sharding.zero_shard_spec)
        if plan.zero:
            # ZeRO contract check: a param whose every dim is either
            # already sharded or data-indivisible keeps replicated state —
            # report it instead of silently degrading (VERDICT r2 #7)
            unsharded = plan.zero_unsharded({n: shapes[n] for n in params})
            if unsharded:
                import logging
                logging.warning(
                    "shard_optimizer_state: %d param(s) have no dim "
                    "divisible by the data axis (%d) and keep REPLICATED "
                    "optimizer state: %s", len(unsharded),
                    self._mesh.shape["data"], unsharded[:8])
        states = {}
        for n, w in params.items():
            state_sh = plan.state_sharding(n, shapes[n])
            states[n] = jax.tree_util.tree_map(
                lambda x, _sh=state_sh: jax.device_put(x, _sh),
                fused._init_state(w))
        return states

    def _input_shardings(self, known):
        """name -> sharding of every bound input, by the batch rule."""
        mesh = self._mesh
        # sequence parallelism: shard the sequence dim (dim 1) of token
        # inputs over the axis the graph's attention ops actually name —
        # not a hardcoded literal — so inputs arrive pre-sharded for the
        # shard_map and non-sequence models never get a spurious split
        seq_axis = None
        for node in self._symbol._topo_nodes():
            if node.is_variable or node.op.name != "MultiHeadAttention":
                continue
            ax = node.attrs.get("seq_axis")
            if ax and ax in mesh.axis_names and mesh.shape[ax] > 1:
                seq_axis = ax
                break
        in_shardings = {}
        for n in list(self._data_names) + list(self._label_names):
            if n not in known:
                continue
            shp = tuple(known[n])
            spec = list(batch_pspec(mesh, len(shp)))
            spec += [None] * (len(shp) - len(spec))
            if (seq_axis is not None and len(shp) >= 2 and spec[1] is None
                    and shp[1] % mesh.shape[seq_axis] == 0):
                spec[1] = seq_axis
            in_shardings[n] = NamedSharding(mesh, P(*spec))
        return in_shardings

    def rebind_step(self):
        """Rebuild the donated step program on the SAME mesh and live
        state — stall-escalation rung 2 (resilience/supervisor.py): a
        wedged executable/dispatch is abandoned for a fresh jit. The
        retrace guard treats this as a new program lifetime, and the
        abstract-args snapshot survives (shapes/shardings unchanged)."""
        if self._fused is None:
            raise MXNetError("call bind() before rebind_step()")
        self._fused.rebind()
        return self

    # -- stepping ----------------------------------------------------------

    @hot_path("the per-step training path (ISSUE: SPMDTrainer.step)")
    def step(self, batch: Dict[str, np.ndarray]):
        """Run one optimizer step on a global batch; returns outputs."""
        if self._fused is None:
            raise MXNetError("call bind() before step()")
        with _profiler.span("fit.step"):
            # fault site only, no retry: the step donates its param/state
            # buffers, so re-running a half-executed step is never safe —
            # recovery from a failed step is restore_latest()+resume
            from ..resilience import fault_point
            from ..resilience.elastic import check_collective
            fault_point("trainer.step")
            # mesh.collective: a participant dying mid-collective surfaces
            # as DeviceLost; fit(elastic=True) recovers via checkpoint
            # restore onto the surviving devices (resilience/elastic.py)
            check_collective()
            inputs = {}
            with _profiler.span("step.place"):
                for n, v in batch.items():
                    if isinstance(v, NDArray):
                        # hand the underlying device array straight to
                        # device_put: an asnumpy() here would be a full
                        # device->host readback per batch
                        v = v._data
                    elif not isinstance(v, jax.Array):
                        # host-side input prep: device arrays took the
                        # _data path above, so this never reads back from
                        # the accelerator
                        v = np.asarray(v)  # tpu-lint: disable=host-sync-under-trace
                    # no-op when v already lives there with this sharding
                    inputs[n] = jax.device_put(v, self._in_shardings[n])
            self._num_update += 1
            _profiler.count("step.count")
            self._rng, sub = jax.random.split(self._rng)
            opt = self._optimizer
            lr = jnp.float32(opt.lr if opt.lr_scheduler is None
                             else opt.lr_scheduler(self._num_update))
            t = jnp.float32(self._num_update)
            # the call runs under the mesh (FusedStep.__call__): mesh-aware
            # ops consult it while the step traces (first call compiles)
            with _profiler.span("step.dispatch"):
                self.params, self.states, self.aux, outs = self._fused(
                    self.params, self.states, self.aux, inputs, sub, lr, t)
            # the lying-chip fault site (resilience/integrity.py): an
            # armed mesh.silent_corrupt plan lands a seeded single-device
            # bitflip HERE, after the updated params exist — and nothing
            # raises; disarmed this is one active_plan()-is-None check
            from ..resilience.integrity import corruption_point
            corruption_point(self)
            return outs

    def compiled_step_hlo(self) -> str:
        """Optimized HLO text of the compiled training step.

        Lets tests/tools assert the communication pattern the sharding
        was designed to produce — e.g. that ZeRO optimizer-state sharding
        turned the gradient all-reduce into reduce-scatter + all-gather
        (trainer docstring; reference analogue: the dist server's
        key-sharded update, kvstore_dist_server.h:175-186)."""
        if self._fused is None:
            raise MXNetError("run at least one step() first")
        return self._fused.compiled_hlo()

    def loss_scale_stats(self):
        """Host snapshot of the loss-scale guard state (None unless the
        MXTPU_PRECISION mode / ``loss_scale=`` armed it) — a boundary
        read for callbacks and tests, never on the step path."""
        return None if self._fused is None \
            else self._fused.loss_scale_stats()

    def aux_counters(self):
        """Host snapshot of the counters the graph's ops keep on the device:
        ``{node name: {counter name: value}}`` for every node whose op
        declares ``aux_counters`` (a vector of an auxiliary state, one
        element a counter, added to in the donated step since bind). A
        boundary read (one small transfer per node), never on the step
        path; {} for a graph with no such op."""
        out = {}
        for node in self._symbol._topo_nodes():
            if node.is_variable or not node.op.aux_counters:
                continue
            found = {}
            for idx, names in node.op.aux_counters.items():
                if idx >= len(node.inputs):     # an input the attrs left out
                    continue
                values = np.asarray(self.aux[node.inputs[idx][0].name])
                found.update(zip(names, map(float, values)))
            if found:
                out[node.name] = found
        return out

    def integrity_stats(self):
        """Host snapshot of the in-trace divergence-sentinel state (None
        unless MXTPU_INTEGRITY_PERIOD / ``integrity=`` armed the guard)
        — a boundary read for the IntegrityGuard and tests, never on
        the step path (resilience/integrity.py)."""
        return None if self._fused is None \
            else self._fused.integrity_stats()

    @property
    def _ig_state(self):
        """The sentinel's state, which the step carries (the integrity
        guard and the fault injectors of its tests read and set it)."""
        return None if self._fused is None else self._fused._ig_state

    @_ig_state.setter
    def _ig_state(self, state):
        self._fused._ig_state = state

    def _reset_integrity_state(self):
        """Fresh sentinel statistics (same shapes/shardings/dtypes, so
        no retrace): called after any rollback/recovery — the restored
        params' gradient distribution starts a new regime."""
        if self._fused is not None:
            self._fused.reset_integrity_state()

    def get_params(self):
        """Gather (host) copies, reference Module.get_params."""
        arg = {n: NDArray(np.asarray(v)) for n, v in self.params.items()}
        aux = {n: NDArray(np.asarray(v)) for n, v in self.aux.items()}
        return arg, aux

    # -- checkpoint / resume ------------------------------------------------
    # Reference: Module.save_checkpoint + .states (SURVEY.md §5.4) — here
    # the distributed analog: orbax writes each shard from its owning
    # process/device, so multi-host sharded training checkpoints without
    # gathering to one host; resume is exact (params + optimizer state +
    # aux + update counter + rng).

    def _ckpt_state(self):
        return {"params": self.params, "states": self.states,
                "aux": self.aux}

    def save_checkpoint(self, directory, step=0, epoch=None,
                        iter_state=None):
        """Write a sharded checkpoint to <directory>/step_<step>, then a
        ``manifest.json`` with SHA-256 digests of every file in it (the
        validity marker restore_latest trusts). Orbax itself writes to a
        tmp dir and renames, so a crash mid-save never corrupts an
        existing checkpoint; the save runs under the default retry
        policy behind the ``checkpoint.write`` fault site.
        ``iter_state`` (a JSON-serializable data-iterator snapshot)
        lands in ``iter_state.json`` inside the checkpoint dir,
        manifest-covered, for deterministic mid-epoch resume."""
        import json
        import os

        import orbax.checkpoint as ocp

        from ..resilience import guarded_call

        if self._fused is None:
            raise MXNetError("bind() before save_checkpoint()")
        path = os.path.join(os.path.abspath(directory), f"step_{step}")
        state = self._ckpt_state()
        state["meta"] = {"num_update": np.asarray(self._num_update, np.int64),
                         "epoch": np.asarray(-1 if epoch is None else epoch,
                                             np.int64),
                         "rng": np.asarray(self._rng)}

        def _save():
            with ocp.StandardCheckpointer() as ck:
                ck.save(path, state, force=True)

        guarded_call("checkpoint.write", _save)
        from ..resilience import checkpoint as _ckpt
        if iter_state is not None:
            _ckpt.atomic_write_bytes(
                os.path.join(path, "iter_state.json"),
                json.dumps(iter_state, sort_keys=True).encode("utf-8"))
        _ckpt.write_dir_manifest(path)
        return path

    def _save_checkpoint_async(self, ckpt, directory, step=0, epoch=None,
                               iter_state=None, post_commit=None,
                               precious=False, supersede=None):
        """Async variant of :meth:`save_checkpoint`: the step loop pays
        only the device→host snapshot (``checkpoint.snapshot`` fault
        site) plus a ``step_<N>.inprogress`` marker beside the target
        dir; the orbax write + ``manifest.json`` commit run on ``ckpt``
        (an :class:`~mxnet_tpu.resilience.AsyncCheckpointer`) behind it.
        ``restore_latest`` skips marked-but-manifestless dirs, so a kill
        anywhere before the commit is invisible to discovery.
        ``post_commit`` (the roll of the superseded mid-epoch dir) runs
        on the writer strictly after the manifest lands. A superseded
        snapshot never wrote the dir — its cleanup is the marker alone.
        Returns the target path (commit pending until flush)."""
        import json
        import os

        from ..resilience import faults, guarded_call
        from ..resilience import checkpoint as _ckpt

        if self._fused is None:
            raise MXNetError("bind() before save_checkpoint()")
        base = os.path.abspath(directory)
        path = os.path.join(base, f"step_{step}")
        faults.fault_point("checkpoint.snapshot")
        # host snapshot, decoupled from the donated training buffers:
        # the next step may overwrite device memory freely
        state = jax.device_get(self._ckpt_state())
        state["meta"] = {"num_update": np.asarray(self._num_update, np.int64),
                         "epoch": np.asarray(-1 if epoch is None else epoch,
                                             np.int64),
                         "rng": np.asarray(self._rng)}
        os.makedirs(base, exist_ok=True)
        marker = path + ".inprogress"
        with open(marker, "w", encoding="utf-8") as f:
            f.write('{"pid": %d}\n' % os.getpid())

        def _commit():
            import orbax.checkpoint as ocp

            def _save():
                with ocp.StandardCheckpointer() as ck:
                    ck.save(path, state, force=True)

            guarded_call("checkpoint.write", _save)
            if iter_state is not None:
                _ckpt.atomic_write_bytes(
                    os.path.join(path, "iter_state.json"),
                    json.dumps(iter_state, sort_keys=True).encode("utf-8"))
            _ckpt.write_dir_manifest(path)
            try:
                os.remove(marker)
            except OSError:
                pass
            if post_commit is not None:
                post_commit()

        def _superseded():
            try:
                os.remove(marker)
            except OSError:
                pass

        ckpt.submit(step, _commit, on_supersede=_superseded,
                    precious=precious, supersede=supersede)
        return path

    def restore_checkpoint(self, directory, step=0):
        """Exact resume from save_checkpoint; call bind() first (the
        checkpoint restores onto the bound shardings). Verifies the
        checkpoint's manifest before reading it."""
        import os

        import orbax.checkpoint as ocp

        from ..resilience import guarded_call

        if self._fused is None:
            raise MXNetError("bind() before restore_checkpoint()")
        path = os.path.join(os.path.abspath(directory), f"step_{step}")
        from ..resilience import checkpoint as _ckpt
        _ckpt.verify_dir_manifest(path)
        abstract = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding),
            self._ckpt_state())
        abstract["meta"] = {
            "num_update": np.zeros((), np.int64),
            "epoch": np.zeros((), np.int64),
            "rng": np.zeros(np.asarray(self._rng).shape,
                            np.asarray(self._rng).dtype)}

        def _restore():
            with ocp.StandardCheckpointer() as ck:
                return ck.restore(path, abstract)

        try:
            state = guarded_call("checkpoint.read", _restore)
        except (ValueError, KeyError) as err:
            # checkpoints written before the epoch field existed have
            # meta={num_update, rng}; retry with the legacy tree shape —
            # but only when the mismatch is actually about that field,
            # so a genuine shape/sharding mismatch keeps its real error
            # and does not pay a second full restore
            if "epoch" not in str(err):
                raise
            del abstract["meta"]["epoch"]
            state = guarded_call("checkpoint.read", _restore)
            state["meta"]["epoch"] = np.int64(-1)
        self.params = state["params"]
        self.states = state["states"]
        self.aux = state["aux"]
        self._num_update = int(state["meta"]["num_update"])
        self._restored_epoch = int(state["meta"]["epoch"])
        self._rng = jnp.asarray(state["meta"]["rng"])
        import json
        ipath = os.path.join(path, "iter_state.json")
        self._restored_iter_state = None
        if os.path.exists(ipath):
            # digest-verified above by verify_dir_manifest
            with open(ipath, "r", encoding="utf-8") as f:
                self._restored_iter_state = json.load(f)
        return self

    def restore_latest(self, directory):
        """Resume from the newest *valid* ``step_<N>`` checkpoint under
        ``directory``: candidates are tried newest-first, and one that
        fails manifest verification (torn write, flipped byte) is skipped
        with a warning. Returns the restored step, or None if the
        directory holds no usable checkpoint."""
        import logging
        import os

        from ..resilience import CheckpointCorrupt, RetryExhausted

        base = os.path.abspath(directory)
        steps = []
        if os.path.isdir(base):
            for name in os.listdir(base):
                if name.startswith("step_") and name[5:].isdigit():
                    step_dir = os.path.join(base, name)
                    if os.path.exists(step_dir + ".inprogress") \
                            and not os.path.exists(os.path.join(
                                step_dir, "manifest.json")):
                        # an async writer was (or died) mid-commit here:
                        # the dir is not a checkpoint yet, don't even
                        # pay the failed-verification warning for it
                        continue
                    steps.append(int(name[5:]))
        for step in sorted(steps, reverse=True):
            try:
                self.restore_checkpoint(directory, step=step)
                if step != max(steps):
                    logging.warning(
                        "restore_latest: fell back to step_%d (newer "
                        "checkpoints failed verification)", step)
                return step
            except (CheckpointCorrupt, OSError, ValueError, KeyError,
                    RetryExhausted) as err:
                logging.warning("restore_latest: skipping step_%d: %s",
                                step, err)
        return None

    # -- elastic re-mesh ----------------------------------------------------

    def remesh(self, mesh, carry_state=True):
        """Re-bind this trainer onto ``mesh`` (an elastic topology
        change: devices lost or added — resilience/elastic.py). The
        partition rules re-derive every sharding for the new topology
        (the ZeRO state specs included, so the cross-replica update
        layout survives the change) and the step program recompiles
        exactly once — the CompileGuard treats a rebind as a new
        program lifetime, not a retrace.

        With ``carry_state`` (the between-steps path: state is
        consistent) params / optimizer state / aux move bitwise:
        re-gathered to host, then re-sharded under the new mesh's
        rules. With ``carry_state=False`` (the failed-step path) the
        trainer re-initializes and the caller restores a checkpoint —
        after a mid-step device loss the donated buffers are untrusted
        and the dead device's shards are gone."""
        if self._fused is None:
            raise MXNetError("call bind() before remesh()")
        old_params, old_states, old_aux = self.params, self.states, self.aux
        self._mesh = mesh
        if not carry_state:
            self.bind(self._bound_data_shapes, self._bound_label_shapes)
            return self
        self.bind(self._bound_data_shapes, self._bound_label_shapes,
                  arg_params={n: np.asarray(v)
                              for n, v in old_params.items()},
                  aux_params={n: np.asarray(v) for n, v in old_aux.items()})
        # bind() built zero optimizer state on the new shardings;
        # overwrite with the surviving state, re-gathered and re-sharded
        # the same way (bitwise: pure data movement, no arithmetic)
        self.states = jax.tree_util.tree_map(
            lambda new, old: jax.device_put(np.asarray(old), new.sharding),
            self.states, old_states)
        return self

    # -- training loop ------------------------------------------------------

    def fit(self, train_data, num_epoch, checkpoint_dir=None,
            checkpoint_period=1, checkpoint_batch_period=None, resume=None,
            batch_end_callback=None, epoch_end_callback=None,
            elastic=False, elastic_config=None, supervisor=None,
            async_checkpoint=None):
        """Minimal epoch loop over a DataIter (call bind() first):
        each batch becomes one fused SPMD step. With ``checkpoint_dir``,
        a sharded checkpoint is written every ``checkpoint_period``
        epochs — plus, with ``checkpoint_batch_period=N``, every N
        batches within an epoch including the iterator's
        ``state_dict()``; ``resume='auto'`` continues from the newest
        valid one (params, optimizer state, update counter, rng, and —
        when the checkpoint carries iterator state and ``train_data``
        supports ``load_state_dict`` — the exact mid-epoch batch
        position: bitwise the trajectory the uninterrupted run takes),
        ``resume=<int>`` demands that exact ``step_<N>`` checkpoint.

        ``elastic=True`` (requires ``checkpoint_dir``) arms the elastic
        controller (resilience/elastic.py): the device set is probed
        every batch, and a device lost or added mid-run triggers
        checkpoint → re-mesh onto a compatible surviving topology →
        re-shard → resume, with the bitwise-identical batch stream.
        Pass a pre-built :class:`~mxnet_tpu.resilience.elastic.
        ElasticController` as ``elastic`` to inject a custom probe/
        health monitor; ``elastic_config`` takes an
        :class:`~mxnet_tpu.resilience.elastic.ElasticConfig`.

        ``supervisor`` (True, a :class:`~mxnet_tpu.resilience.
        TrainingSupervisor`, or ``MXTPU_SUPERVISOR=1``) arms preemption
        awareness (docs/how_to/preemption.md): SIGTERM finishes the
        in-flight step, checkpoints (iterator state included) with a
        clean-exit marker and exits typed; a stalled step walks the
        retry → ``rebind_step()`` → elastic re-mesh → abort ladder;
        crash loops at one (epoch, batch) back off and quarantine.

        ``async_checkpoint`` (default: the ``MXTPU_ASYNC_CKPT`` knob)
        moves every fit checkpoint onto a background writer: the step
        loop pays only a device→host snapshot, and the orbax write +
        manifest commit happen behind it with depth-1 back-pressure
        (a newer mid-epoch snapshot supersedes an unstarted one).
        Preemption, stall-abort, and epoch-boundary checkpoints flush
        so they are durable before the run exits; a background write
        failure surfaces as a typed ``AsyncCheckpointError`` on the
        next checkpoint call (docs/how_to/fault_tolerance.md)."""
        if self._fused is None:
            raise MXNetError("call bind() before fit()")
        from ..resilience import supervisor as _sup_mod
        sup = _sup_mod.resolve(supervisor)
        begin_epoch = 0
        begin_batch = 0
        resume_iter = None
        restored = None
        if resume is True:   # fit(resume=True) means 'auto', not step 1
            resume = "auto"
        if resume is not None and resume is not False:
            if not checkpoint_dir:
                raise MXNetError("fit(resume=...) requires checkpoint_dir")
            if resume == "auto":
                restored = self.restore_latest(checkpoint_dir)
            else:
                self.restore_checkpoint(checkpoint_dir, step=int(resume))
                restored = int(resume)
            if restored is not None:
                saved_epoch = getattr(self, "_restored_epoch", -1)
                if saved_epoch < 0:
                    import logging
                    logging.warning(
                        "resumed checkpoint step_%s carries no epoch "
                        "metadata (saved via save_checkpoint without "
                        "epoch=); fit restarts at epoch 0 on the restored "
                        "params", restored)
                begin_epoch = saved_epoch if saved_epoch >= 0 else 0
                resume_iter = getattr(self, "_restored_iter_state", None)
        from ..resilience.data import (apply_resume_state,
                                       supports_state as _supports_state)
        if resume_iter is not None:
            begin_epoch, begin_batch = apply_resume_state(train_data,
                                                          resume_iter)
        crash_guard = None
        if sup is not None and checkpoint_dir:
            if restored is not None:
                # the clean-exit marker served its purpose: this resume
                # consumed the preemption checkpoint
                _sup_mod.clear_preempt_marker(checkpoint_dir)
                # crash-loop protection (resilience/supervisor.py):
                # repeated resumes at one (epoch, batch) back off
                # exponentially; past the limit the batch is quarantined
                # under the DataGuardPolicy budget and skipped
                import os as _os
                _os.makedirs(_os.path.abspath(checkpoint_dir),
                             exist_ok=True)
                crash_guard = sup.crash_guard(checkpoint_dir)
                crash_guard.on_resume(begin_epoch, begin_batch)
                begin_batch = _sup_mod.skip_quarantined_batches(
                    train_data, crash_guard, begin_epoch, begin_batch)
            else:
                # fresh lineage: a stale clean-exit marker must not
                # claim this run was preempted
                _sup_mod.clear_preempt_marker(checkpoint_dir)
        cbs = (batch_end_callback if isinstance(batch_end_callback, list)
               else [batch_end_callback]) if batch_end_callback is not None \
            else []
        can_snapshot = _supports_state(train_data)
        if can_snapshot and checkpoint_dir \
                and (checkpoint_batch_period or sup is not None) \
                and hasattr(train_data, "enable_state_snapshots"):
            # PrefetchingIter-style sources capture per-prefetch
            # snapshots only once armed — they cost O(dataset) each, so
            # arming is tied to batch-period checkpointing (or an armed
            # supervisor, whose preemption checkpoint can land on any
            # batch); the epoch-end-only snapshot degrades gracefully
            train_data.enable_state_snapshots()
        bperiod = max(1, int(checkpoint_batch_period)) \
            if checkpoint_batch_period else None
        controller = None
        if elastic:
            from ..resilience.elastic import ElasticController
            if isinstance(elastic, ElasticController):
                controller = elastic      # caller-built: injectable probe
                if elastic_config is not None:
                    raise MXNetError(
                        "fit(): pass elastic_config when elastic=True, "
                        "or build the ElasticController with its config "
                        "— not both (the controller's own config would "
                        "silently win)")
                if controller.trainer is not self:
                    raise MXNetError(
                        "fit(): the ElasticController was built for a "
                        "different trainer — its recovery would re-mesh "
                        "and restore that trainer while this one keeps "
                        "the broken mesh")
            else:
                if not checkpoint_dir:
                    raise MXNetError("fit(elastic=True) requires "
                                     "checkpoint_dir")
                controller = ElasticController(self, checkpoint_dir,
                                               config=elastic_config)
        if sup is not None:
            # rung 3 of the stall ladder needs an elastic controller;
            # without one the ladder is retry → rebind → abort
            sup.can_remesh = controller is not None
        iguard = None
        if self._ig_cfg is not None:
            # silent-failure integrity guard (MXTPU_INTEGRITY_PERIOD /
            # integrity=; resilience/integrity.py): periodic sentinel
            # reads + cross-replica checksum votes. It shares the
            # elastic controller's MeshHealth so a vote-localized bad
            # chip is excluded through the same path a probed loss is.
            from ..resilience.integrity import IntegrityGuard
            iguard = IntegrityGuard(
                self, self._ig_cfg,
                health=(controller.health if controller is not None
                        else None),
                checkpoint_dir=checkpoint_dir)
        if async_checkpoint is None:
            from .. import config as _config
            async_checkpoint = bool(_config.get("MXTPU_ASYNC_CKPT"))
        actx = None
        if async_checkpoint and checkpoint_dir:
            from ..resilience import AsyncCheckpointer
            # the guard gates commits: a breached (diverged) state must
            # never reach disk, even from an already-queued snapshot
            actx = AsyncCheckpointer(
                name="spmd-ckpt-writer",
                gate=iguard.gate if iguard is not None else None)
        from contextlib import ExitStack
        with ExitStack() as _sup_stack:
            if actx is not None:
                # every exit (success, Preempted, abort) surfaces a
                # stored writer failure and stops the thread
                _sup_stack.callback(actx.close, flush=True)
            if sup is not None:
                _sup_stack.enter_context(sup.attach())
            if controller is None and iguard is None:
                self._run_epochs(train_data, num_epoch, begin_epoch,
                                 begin_batch, checkpoint_dir,
                                 checkpoint_period, bperiod, can_snapshot,
                                 cbs, epoch_end_callback, None, sup,
                                 crash_guard, actx)
                return self
            from ..resilience.elastic import DeviceLost
            from ..resilience.integrity import DivergenceDetected
            while True:
                try:
                    self._run_epochs(train_data, num_epoch, begin_epoch,
                                     begin_batch, checkpoint_dir,
                                     checkpoint_period, bperiod,
                                     can_snapshot, cbs, epoch_end_callback,
                                     controller, sup, crash_guard, actx,
                                     iguard)
                    return self
                except DivergenceDetected as err:
                    # sentinel breach: the mesh is healthy but the state
                    # diverged — prune the contaminated saves, roll back
                    # to the last validated checkpoint, rewind, replay
                    # (a second breach at the same position quarantines
                    # the batch as poison). The commit gate already kept
                    # the breach out of any in-flight async save.
                    begin_epoch, begin_batch = iguard.recover(
                        train_data, err)
                except DeviceLost as err:
                    if controller is None:
                        # a ChecksumMismatch localized a lying chip but
                        # without elastic there is no re-mesh path —
                        # surface it (the checkpoint dir was pruned of
                        # contamination; a relaunch resumes clean)
                        raise
                    # a collective participant died mid-step (or a step
                    # stalled through retry+rebind — the ladder's rung 3
                    # surfaces as DeviceLost too): the donated buffers
                    # are untrusted — re-mesh onto the survivors,
                    # restore the newest checkpoint, rewind the iterator
                    if actx is not None:
                        from ..resilience import AsyncCheckpointError
                        try:
                            # a pending snapshot predates the device
                            # loss — commit it so recovery restores the
                            # newest state instead of replaying to it
                            actx.flush()
                        except AsyncCheckpointError as werr:
                            import logging
                            logging.warning(
                                "async checkpoint flush failed during "
                                "device-loss recovery (%s); recovering "
                                "from the last committed checkpoint",
                                werr)
                    begin_epoch, begin_batch = controller.recover(
                        train_data, err)
                    if iguard is not None:
                        # re-mesh + restore IS a successful integrity
                        # recovery: reopen the commit gate, reset the
                        # sentinel statistics for the new topology
                        iguard.on_recovered()

    def _run_epochs(self, train_data, num_epoch, begin_epoch, begin_batch,
                    checkpoint_dir, checkpoint_period, bperiod,
                    can_snapshot, cbs, epoch_end_callback, controller,
                    sup=None, crash_guard=None, actx=None, iguard=None):
        from ..callback import BatchEndParam
        # NOTE: this mid-epoch checkpoint orchestration deliberately
        # parallels BaseModule.fit (module/base_module.py) — the trainer
        # rolls whole step_<N> dirs where Module rolls labeled stems,
        # and skips the epoch-end write after an empty-tail replay
        # because its dir would collide with the promoted mid save.
        # A semantics change here must be mirrored there.
        import os
        import shutil

        from .. import config as _config
        last_mid_step = None
        # superseded mid-epoch dirs, oldest first: the MXTPU_CKPT_KEEP
        # rollback window (default 1 = the classic single-survivor roll).
        # The integrity guard's rollback needs checkpoints OLDER than the
        # newest to survive — a divergence detected N steps late prunes
        # every save in the contaminated window and restores past it
        # (resilience/integrity.py, docs/how_to/integrity.md).
        keep_mid = max(1, int(_config.get("MXTPU_CKPT_KEEP")))
        mid_paths = []

        def _mid_window_push(path):
            """Record ``path`` as the newest mid-epoch save; return the
            dirs that just fell out of the rollback window (for the
            caller to delete — post-commit, on the async path)."""
            if path in mid_paths:
                mid_paths.remove(path)
            mid_paths.append(path)
            drop = []
            while len(mid_paths) > keep_mid:
                drop.append(mid_paths.pop(0))
            return drop

        prev_state = None       # last *trained* position (stall rewinds)
        progressed = False
        remesh_exc = None
        if sup is not None and controller is not None:
            from ..resilience.elastic import DeviceLost

            def remesh_exc(err):
                # rung 3: a step that stalls through retry + rebind is
                # treated as a sick participant — the outer fit loop's
                # DeviceLost recovery restores onto survivors (PR 6)
                lost = DeviceLost(
                    f"step stalled through retry and rebind ({err}); "
                    "escalating to elastic re-mesh: restore the newest "
                    "checkpoint onto the surviving devices")
                if getattr(err, "slow", False):
                    # a StepSlow escalation: the recovery path must
                    # quarantine the topology as DEGRADED (gray
                    # failure), not mark a device lost
                    lost.slow = True
                return lost
        for epoch in range(begin_epoch, num_epoch):
            if begin_batch == 0:
                train_data.reset()
            # else: mid-epoch resume — the restored iterator already
            # sits at begin_batch; a reset would replay the epoch head
            nseen = 0
            for k, batch in _fetching(train_data):
                nbatch = begin_batch + k
                nseen = k + 1
                if iguard is not None \
                        and iguard.is_quarantined(epoch, nbatch):
                    # replay classification condemned this batch as
                    # poison (it diverged twice deterministically): the
                    # fetch above consumed it, so the iterator position
                    # stays consistent — it is simply never trained on
                    continue
                inputs = self._batch_dict(batch)
                if sup is None:
                    step_outs = self.step(inputs)  # noqa: F841 in locals()
                else:
                    def _abort_ckpt(err, _ep=epoch, _ps=prev_state):
                        # ladder exhausted: persist the last consistent,
                        # fully-trained position before aborting (the
                        # stalled batch itself replays on resume)
                        if not checkpoint_dir:
                            return
                        if actx is not None:
                            # drain the writer first: the manifest check
                            # below is only meaningful once pending
                            # snapshots committed, and the job is dying
                            # — the abort checkpoint must be durable
                            actx.flush()
                        import os
                        step_dir = os.path.join(
                            os.path.abspath(checkpoint_dir),
                            f"step_{self._num_update}")
                        if os.path.exists(os.path.join(
                                step_dir, "manifest.json")):
                            # this update count is already on disk —
                            # e.g. the very checkpoint this run resumed
                            # from, stalling before the first update
                            # committed. Orbax force=True would delete
                            # it before rewriting; with the job already
                            # dying, a kill mid-save would destroy the
                            # only good copy.
                            return
                        self.save_checkpoint(
                            checkpoint_dir, step=self._num_update,
                            epoch=_ep, iter_state=_ps)

                    step_outs = sup.run_step(  # noqa: F841 — in locals()
                        lambda _b=inputs: self.step(_b),
                        rebind=self.rebind_step, remesh_exc=remesh_exc,
                        on_abort=_abort_ckpt,
                        label=f"SPMD step epoch {epoch} batch {nbatch}")
                    if crash_guard is not None and not progressed:
                        crash_guard.note_progress()
                        progressed = True
                if iguard is not None:
                    # the amortized integrity boundary, deliberately
                    # BEFORE this batch's checkpoint block: a breach
                    # raises here, so diverged state is structurally
                    # unable to reach the save path below (the async
                    # gate is the second, belt-and-braces wall)
                    iguard.after_step(epoch, nbatch)
                if cbs:
                    with _profiler.span("fit.callbacks"):
                        for cb in cbs:
                            cb(BatchEndParam(epoch=epoch, nbatch=nbatch,
                                             eval_metric=None,
                                             locals=locals()))
                if checkpoint_dir and bperiod and can_snapshot \
                        and (nbatch + 1) % bperiod == 0:
                    # state_dict() here is "about to fetch nbatch+1" —
                    # the exact resume point for this mid-epoch save
                    mid_iter = {"epoch": epoch, "nbatch": nbatch + 1,
                                "iterator": train_data.state_dict()}
                    if actx is not None:
                        # the roll rides as post_commit on the writer:
                        # dirs that fell out of the rollback window are
                        # deleted only once this save's manifest is on
                        # disk, so the newest committed checkpoint (and
                        # the MXTPU_CKPT_KEEP retained stems) always
                        # survive a kill
                        target = os.path.join(
                            os.path.abspath(checkpoint_dir),
                            f"step_{self._num_update}")
                        drop = _mid_window_push(target)
                        path = self._save_checkpoint_async(
                            actx, checkpoint_dir, step=self._num_update,
                            epoch=epoch, iter_state=mid_iter,
                            post_commit=(
                                (lambda _ps=tuple(drop):
                                 [shutil.rmtree(p, ignore_errors=True)
                                  for p in _ps])
                                if drop else None))
                    else:
                        path = self.save_checkpoint(
                            checkpoint_dir, step=self._num_update,
                            epoch=epoch, iter_state=mid_iter)
                        # roll the superseded mid-epoch dirs: a long
                        # epoch holds at most MXTPU_CKPT_KEEP mid-epoch
                        # checkpoints on disk (the rollback window)
                        for p in _mid_window_push(path):
                            shutil.rmtree(p, ignore_errors=True)
                    last_mid_step = self._num_update
                if controller is not None:
                    # between steps the state is consistent: a detected
                    # topology change checkpoints, re-meshes and
                    # re-shards in place — the stream continues at the
                    # very next batch, no rewind
                    if controller.check(train_data, epoch=epoch,
                                        nbatch=nbatch):
                        # the controller checkpointed this exact state
                        # (or reused this batch's mid-epoch save):
                        # promote it like a mid save so an epoch-end
                        # write at the same update count skips instead
                        # of delete-then-rewriting the step_<N> dir —
                        # and roll the superseded mid dir so the
                        # one-mid-checkpoint-on-disk invariant holds
                        last_mid_step = self._num_update
                        cpath = controller.last_checkpoint_path
                        if cpath:
                            drop = _mid_window_push(cpath)
                            if drop:
                                if actx is not None:
                                    # a dropped dir may still be an
                                    # uncommitted async submit — never
                                    # rmtree a dir the writer may be
                                    # mid-write in
                                    actx.flush()
                                for p in drop:
                                    shutil.rmtree(p, ignore_errors=True)
                if sup is not None:
                    if can_snapshot:
                        try:
                            # "about to fetch nbatch+1": the exact resume
                            # point after the step that just completed —
                            # kept one batch behind for stall rewinds,
                            # used directly by a preemption checkpoint.
                            # Per-batch on purpose: checkpoint params
                            # must pair with the exact position (a stale
                            # snapshot double-trains the gap on resume);
                            # O(dataset)-snapshot sources should report
                            # supports_state False instead
                            prev_state = {
                                "epoch": epoch, "nbatch": nbatch + 1,
                                "iterator": train_data.state_dict()}
                        except MXNetError:
                            prev_state = None
                    if sup.check_preempt():
                        # graceful preemption: the in-flight step is
                        # done; checkpoint this exact position, drop the
                        # clean-exit marker, exit typed (resume='auto'
                        # continues bitwise)
                        if checkpoint_dir:
                            import os
                            if actx is not None:
                                # drain first: a pending async submit
                                # for this very step commits, making
                                # the manifest check below truthful —
                                # and the preemption checkpoint must be
                                # durable before the typed exit anyway
                                actx.flush()
                            step_dir = os.path.join(
                                os.path.abspath(checkpoint_dir),
                                f"step_{self._num_update}")
                            if not os.path.exists(os.path.join(
                                    step_dir, "manifest.json")):
                                # a bperiod save this very batch already
                                # captured this exact state; re-saving
                                # would delete-then-rewrite the newest
                                # good checkpoint
                                step_dir = self.save_checkpoint(
                                    checkpoint_dir, step=self._num_update,
                                    epoch=epoch, iter_state=prev_state)
                            last_mid_step = self._num_update
                            for p in _mid_window_push(step_dir):
                                shutil.rmtree(p, ignore_errors=True)
                        sup.preempt_exit(
                            checkpoint_dir, label=self._num_update,
                            epoch=epoch, nbatch=nbatch,
                            flush=(actx.flush if actx is not None
                                   else None))
            # a mid-epoch resume whose checkpoint landed on the epoch's
            # last batch replays an empty tail: this epoch's end-of-epoch
            # callback and checkpoint already happened before the crash
            replayed_empty_tail = begin_batch > 0 and nseen == 0
            begin_batch = 0
            if epoch_end_callback is not None and not replayed_empty_tail:
                epoch_end_callback(epoch, self)
            if checkpoint_dir and not replayed_empty_tail \
                    and (epoch + 1) % max(
                        1, int(checkpoint_period)) == 0:
                if self._num_update == last_mid_step:
                    # the final batch's mid-epoch save already captured
                    # this exact state (same num_update/params/rng, and
                    # its exhausted iterator position resumes into
                    # epoch+1 identically); rewriting the same step_<N>
                    # dir would delete-then-rewrite the newest good
                    # checkpoint — the torn window this design avoids.
                    # Promote that dir to epoch-checkpoint status: it
                    # must survive the next epoch's mid-epoch roll so
                    # per-epoch retention (rollback/model selection)
                    # keeps one checkpoint per epoch boundary.
                    if actx is not None:
                        # the promoted save may still be pending on the
                        # writer, where epoch+1's first submit would
                        # supersede (= never write) it — commit it now
                        actx.flush()
                    # the promoted dir is an epoch checkpoint now: pull
                    # it out of the mid-epoch rollback window so the
                    # next epoch's rolls can never delete it (the rest
                    # of the window keeps its retention)
                    promoted = os.path.join(
                        os.path.abspath(checkpoint_dir),
                        f"step_{self._num_update}")
                    if promoted in mid_paths:
                        mid_paths.remove(promoted)
                    continue
                iter_state = None
                if can_snapshot:
                    try:
                        # exhausted end-of-epoch state: the resumed loop
                        # reset()s into epoch+1 drawing from the restored
                        # shuffle RNG, so the next epoch replays bitwise
                        iter_state = {"epoch": epoch + 1, "nbatch": 0,
                                      "iterator": train_data.state_dict()}
                    except MXNetError:
                        # a disarmed PrefetchingIter (no batch-period
                        # checkpointing): epoch-granularity resume
                        # without iterator state, as before this PR
                        pass
                if actx is not None:
                    # epoch-boundary checkpoints are retention points:
                    # precious (a later mid-epoch submit must never
                    # supersede one away) and non-superseding (a still-
                    # pending mid save commits first, so its post_commit
                    # roll keeps its ordering guarantee)
                    self._save_checkpoint_async(
                        actx, checkpoint_dir, step=self._num_update,
                        epoch=epoch + 1, iter_state=iter_state,
                        precious=True, supersede=False)
                else:
                    self.save_checkpoint(checkpoint_dir,
                                         step=self._num_update,
                                         epoch=epoch + 1,
                                         iter_state=iter_state)

    def _batch_dict(self, batch) -> Dict[str, np.ndarray]:
        """Map a DataBatch onto this trainer's data/label names."""
        if isinstance(batch, dict):
            return batch
        inputs = {}
        data = batch.data if isinstance(batch.data, (list, tuple)) \
            else [batch.data]
        for name, arr in zip(self._data_names, data):
            inputs[name] = arr
        if batch.label is not None:
            label = batch.label if isinstance(batch.label, (list, tuple)) \
                else [batch.label]
            for name, arr in zip(self._label_names, label):
                inputs[name] = arr
        return inputs
