"""Module: executor-backed trainer over one Symbol.

Reference: python/mxnet/module/module.py — bind:351 (builds a
DataParallelExecutorGroup), init_optimizer:460 with kvstore wiring
:486-531, forward:556 / backward:598 / update:615. TPU-native shape: the
executor-group-of-one-executor-per-device collapses into a single
XLA-compiled executor; multi-device data parallelism is a sharded training
step over the mesh (parallel/), not N executors (SURVEY.md §7.1 KVStore row).
"""
from __future__ import annotations

import logging
from typing import Dict, List, Optional

from .. import ndarray as nd
from .. import optimizer as opt
from .. import profiler as _profiler
from ..base import MXNetError
from ..initializer import InitDesc, Uniform
from ..io import DataDesc
from ..model import (_create_kvstore, _initialize_kvstore, _update_params,
                     _update_params_on_kvstore, save_checkpoint)
from ..ndarray.ndarray import _as_jax
from .base_module import BaseModule, _check_input_names

__all__ = ["Module"]


def _namelist(value):
    return list(value) if value is not None else []


class Module(BaseModule):
    def __init__(self, symbol, data_names=("data",),
                 label_names=("softmax_label",), logger=logging, context=None,
                 work_load_list=None, fixed_param_names=None,
                 state_names=None, group2ctxs=None):
        super().__init__(logger=logger)
        from ..context import current_context
        if context is None:
            context = current_context()
        self._context = (list(context) if isinstance(context, (list, tuple))
                         else [context])
        self._symbol = symbol
        # ctx_group -> Context placement map (reference Module group2ctxs;
        # a list of per-device dicts there — one mesh-wide dict here)
        if isinstance(group2ctxs, (list, tuple)):
            group2ctxs = group2ctxs[0] if group2ctxs else None
        self._group2ctxs = group2ctxs

        roles = {"data": (_namelist(data_names), True),
                 "label": (_namelist(label_names), False),
                 "state": (_namelist(state_names), True),
                 "fixed_param": (_namelist(fixed_param_names), True)}
        for role, (names, strict) in roles.items():
            _check_input_names(symbol, names, role, strict)
        self._data_names, self._label_names, self._state_names, \
            self._fixed_param_names = (roles[r][0] for r in
                                       ("data", "label", "state",
                                        "fixed_param"))
        non_param = set(self._data_names) | set(self._label_names) \
            | set(self._state_names)
        self._param_names = [n for n in symbol.list_arguments()
                             if n not in non_param]
        self._aux_names = symbol.list_auxiliary_states()
        self._output_names = symbol.list_outputs()

        # training state, populated by init_params/init_optimizer/bind
        self._arg_params = self._aux_params = None
        self._params_dirty = False
        self._optimizer = self._kvstore = self._updater = None
        self._update_on_kvstore = self._preload_opt_states = None
        self._exec = self._monitor = None
        self._data_shapes = self._label_shapes = None
        self._dp_mesh = None  # multi-ctx bind: 1-axis data-parallel mesh
        # fused whole-step runtime (perf/): None = not built yet,
        # False = this module is ineligible, else the live ModuleStepper
        self._fused_stepper = None

    @staticmethod
    def load(prefix, epoch=None, load_optimizer_states=False, **kwargs):
        """reference: module.py Module.load — manifest-verified; a corrupt
        checkpoint falls back to the last good one, and the optimizer
        states file is taken from the checkpoint actually loaded."""
        from ..model import _load_checkpoint_ex
        _, sym, args, auxs, states = _load_checkpoint_ex(prefix, epoch)
        mod = Module(symbol=sym, **kwargs)
        mod._arg_params = args
        mod._aux_params = auxs
        mod.params_initialized = True
        if load_optimizer_states:
            if states is None:
                raise MXNetError(
                    f"checkpoint at {prefix!r} has no optimizer states "
                    "(.states) file")
            mod._preload_opt_states = states
        return mod

    def save_checkpoint(self, prefix, epoch=None, save_optimizer_states=False,
                        iter_state=None):
        """reference: module.py:152 — adds .states with updater state.
        Atomic (tmp+fsync+rename) with a digest manifest covering params
        and states; ``epoch=None`` uses the epoch-less ``prefix.params``
        naming scheme. ``iter_state`` optionally persists a data-iterator
        snapshot (``<stem>.iter.json``, manifest-covered) so
        ``fit(resume='auto')`` can resume mid-epoch."""
        self._sync_params_from_devices()
        states = (self._optimizer_state_bytes()
                  if save_optimizer_states else None)
        save_checkpoint(prefix, epoch, self.symbol, *self.get_params(),
                        states=states, iter_state=iter_state)

    def save(self, prefix, save_optimizer_states=False):
        """Epoch-less checkpoint (``prefix.params`` + manifest) —
        discoverable by ``fit(resume='auto')`` like numbered ones."""
        self.save_checkpoint(prefix, None,
                             save_optimizer_states=save_optimizer_states)

    # -- shapes --------------------------------------------------------------
    @property
    def data_names(self):
        """Names of the data inputs this module consumes."""
        return self._data_names

    @property
    def label_names(self):
        """Names of the label inputs this module consumes."""
        return self._label_names

    @property
    def output_names(self):
        """Names of the symbol's outputs."""
        return self._output_names

    @property
    def data_shapes(self):
        """Bound data descriptors (valid after bind)."""
        assert self.binded
        return self._data_shapes

    @property
    def label_shapes(self):
        """Bound label descriptors (valid after bind)."""
        assert self.binded
        return self._label_shapes

    @property
    def output_shapes(self):
        assert self.binded
        # shape inference, not execution: valid immediately after bind
        # (reference reads the executor's inferred output shapes)
        from ..io import DataDesc
        shape_kwargs = {d.name: d.shape
                        for d in self._data_shapes + self._label_shapes}
        _, out_shapes, _ = self._symbol.infer_shape(**shape_kwargs)
        return [DataDesc(n, tuple(s))
                for n, s in zip(self._output_names, out_shapes)]

    # -- params --------------------------------------------------------------
    def get_params(self):
        assert self.binded and self.params_initialized
        self._sync_params_from_devices()
        return (self._arg_params, self._aux_params)

    def _sync_params_from_devices(self):
        if self._exec is None:
            return
        self._sync_fused()
        self._arg_params = {n: self._exec.arg_dict[n].copy()
                            for n in self._param_names}
        self._aux_params = {n: self._exec.aux_dict[n].copy()
                            for n in self._aux_names}
        self._params_dirty = False

    # -- fused whole-step runtime (perf/step_runtime.py) ----------------------
    def _fused_train_step(self):
        """The fit loop's fused step callable, or None to run the
        imperative forward_backward+update pair. Built lazily; survives
        across epochs (state refresh, not recompilation)."""
        if self._monitor is not None or self._fused_stepper is False:
            return None
        if self._fused_stepper is None:
            from ..perf import module_stepper
            stepper = module_stepper(self)
            self._fused_stepper = stepper if stepper is not None else False
            if stepper is None:
                return None
        return self._fused_stepper.step

    def _rebind_fused_step(self):
        """Stall-escalation rung 2 (resilience/supervisor.py): rebuild
        the fused step's compiled program, keeping its device state."""
        if self._fused_stepper not in (None, False):
            self._fused_stepper.rebind()

    def _sync_fused(self):
        """Flush the fused stepper's device state back into the executor
        and updater (no-op when absent or already synced)."""
        stepper = self._fused_stepper
        if stepper not in (None, False):
            stepper.sync_to_module()

    def _invalidate_fused(self, drop=False):
        """External write to params/optimizer state: the stepper must
        re-pull before its next step (``drop`` discards it entirely —
        symbol/shape/optimizer changed)."""
        if drop:
            self._fused_stepper = None
        elif self._fused_stepper not in (None, False):
            self._fused_stepper.invalidate()

    def init_params(self, initializer=Uniform(0.01), arg_params=None,
                    aux_params=None, allow_missing=False, force_init=False,
                    allow_extra=False):
        """reference: module.py:246"""
        if self.params_initialized and not force_init:
            logging.warning("Parameters already initialized and force_init=False. "
                            "init_params call ignored.")
            return
        assert self.binded, "call bind before initializing the parameters"
        with _profiler.span("bind", args={"front": "module",
                                          "stage": "init_params"}):
            self._init_params(initializer, arg_params, aux_params,
                              allow_missing)

    def _init_params(self, initializer, arg_params, aux_params,
                     allow_missing):
        self._sync_fused()      # make the executor arrays live targets
        attrs = self._symbol.attr_dict()
        for pname, layout in self._symbol._arg_layouts().items():
            attrs.setdefault(pname, {})["__layout__"] = layout

        def fill(name, arr, supplied):
            given = supplied.get(name) if supplied else None
            if given is not None:
                if given is not arr:
                    given.copyto(arr)
                return
            if initializer is None and not allow_missing:
                raise RuntimeError(f"init failed: no initializer and "
                                   f"param {name} missing")
            if initializer is not None:
                initializer(InitDesc(name, attrs.get(name)), arr)

        with _profiler.span("bind.params", args={}) as placed:
            for name in self._param_names:
                fill(name, self._exec.arg_dict[name], arg_params)
            for name in self._aux_names:
                fill(name, self._exec.aux_dict[name], aux_params)
            leaves = [self._exec.arg_dict[n] for n in self._param_names] \
                + [self._exec.aux_dict[n] for n in self._aux_names]
            placed.args.update(
                bytes=sum(a.size * a.dtype.itemsize for a in leaves),
                leaves=len(leaves))
        _profiler.count("bind.param_bytes", placed.args["bytes"])

        self.params_initialized = True
        self._params_dirty = False
        self._dp_replicate_params()
        self._invalidate_fused()

    # -- bind ----------------------------------------------------------------
    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        """reference: module.py:351"""
        if force_rebind:
            if self._exec is not None and self._params_dirty:
                # trained weights live only in the executor: snapshot
                # them before teardown or the rebind would resurrect the
                # stale host copies
                self._sync_params_from_devices()
            self._exec = None
            self.binded = False
            self._invalidate_fused(drop=True)
        if self.binded:
            self.logger.warning("Already bound, ignoring bind()")
            return

        with _profiler.span("bind", args={"front": "module",
                                          "stage": "bind"}):
            self._bind(data_shapes, label_shapes, for_training,
                       inputs_need_grad, shared_module, grad_req)

    def _bind(self, data_shapes, label_shapes, for_training,
              inputs_need_grad, shared_module, grad_req):
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad

        data_shapes = [x if isinstance(x, DataDesc) else DataDesc(*x)
                       for x in data_shapes]
        if label_shapes is not None:
            label_shapes = [x if isinstance(x, DataDesc) else DataDesc(*x)
                            for x in label_shapes]
        else:
            label_shapes = []
        self._data_shapes = data_shapes
        self._label_shapes = label_shapes

        self._dp_mesh = self._build_dp_mesh(data_shapes, label_shapes)

        shape_kwargs = {d.name: d.shape for d in data_shapes + label_shapes}
        req = {}
        for name in self._symbol.list_arguments():
            if name in self._data_names:
                req[name] = "write" if inputs_need_grad else "null"
            elif name in self._label_names or name in self._state_names:
                req[name] = "null"
            elif name in self._fixed_param_names or not for_training:
                req[name] = "null"
            else:
                req[name] = grad_req
        shared_exec = shared_module._exec if shared_module is not None else None
        with _profiler.span("bind.plan"):   # shapes inferred, arrays made
            self._exec = self._symbol.simple_bind(
                ctx=self._context[0], grad_req=req,
                shared_exec=shared_exec, group2ctx=self._group2ctxs,
                **shape_kwargs)
        self.binded = True

        if shared_module is not None and shared_module.params_initialized:
            self.set_params(*shared_module.get_params())
        elif self.params_initialized:
            # params preloaded before bind (Module.load checkpoint resume,
            # or a force_rebind of a trained module): the fresh executor
            # starts zeroed — copy them in and re-pin the multi-context
            # placement (reference: module.py bind's set_params)
            self._exec.copy_params_from(self._arg_params, self._aux_params,
                                        allow_extra_params=True)
            self._dp_replicate_params()

    # -- multi-context data parallelism ---------------------------------------
    def _build_dp_mesh(self, data_shapes, label_shapes):
        """ctx=[...] with several devices: the reference sliced the batch
        across per-device executors (executor_group.py:233-262); here the
        SAME executor program runs SPMD over a 1-axis mesh — inputs are
        batch-sharded, params replicated, and XLA's partitioner inserts
        the gradient all-reduce. A ctx list that cannot span distinct
        devices fails loudly instead of silently training on one chip."""
        if len(self._context) <= 1:
            return None
        if self._group2ctxs:
            raise MXNetError(
                "Module(ctx=[...]) data parallelism cannot be combined "
                "with group2ctxs model parallelism in one bind")
        devs = [c.jax_device for c in self._context]
        if len(set(devs)) != len(devs):
            raise MXNetError(
                f"Module was given {len(self._context)} contexts but they "
                f"map to only {len(set(devs))} distinct device(s) — "
                "multi-context training would silently run at 1/"
                f"{len(self._context)} of the implied throughput. Pass "
                "one context, or as many contexts as physical devices.")
        n = len(devs)
        for d in list(data_shapes) + list(label_shapes):
            if d.shape and d.shape[0] % n:
                raise MXNetError(
                    f"batch dimension of {d.name} {d.shape} is not "
                    f"divisible by the {n} bound contexts")
        import numpy as _np_mod
        from jax.sharding import Mesh
        return Mesh(_np_mod.asarray(devs), ("data",))

    def _dp_place_inputs(self, inputs):
        """Batch-shard input arrays over the data axis (dim 0)."""
        if self._dp_mesh is None:
            return inputs
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        placed = {}
        for name, val in inputs.items():
            arr = _as_jax(val, dtype=self._exec.arg_dict[name].dtype)
            spec = P("data") if arr.ndim else P()
            placed[name] = nd.NDArray(
                jax.device_put(arr, NamedSharding(self._dp_mesh, spec)))
        return placed

    def _dp_replicate_params(self):
        """Pin params/aux fully-replicated on the mesh (no-op off-mesh)."""
        if self._dp_mesh is None:
            return
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        everywhere = NamedSharding(self._dp_mesh, P())
        input_names = set(self._data_names) | set(self._label_names)
        for pool in (self._exec.arg_dict, self._exec.aux_dict):
            for name, arr in pool.items():
                if name in input_names:
                    continue
                if getattr(arr, "stype", "default") != "default":
                    continue  # sparse grads stay host-assembled
                arr._set_data(jax.device_put(arr._data, everywhere))

    # -- optimizer ------------------------------------------------------------
    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        """reference: module.py:460 (kvstore wiring :486-531)"""
        assert self.binded and self.params_initialized
        if self.optimizer_initialized and not force_init:
            self.logger.warning("optimizer already initialized, ignoring...")
            return
        with _profiler.span("bind", args={"front": "module",
                                          "stage": "init_optimizer"}):
            self._init_optimizer(kvstore, optimizer, optimizer_params)

    def _init_optimizer(self, kvstore, optimizer, optimizer_params):
        # flush the stepper's donated device state BEFORE dropping it —
        # dropping first would orphan the trained params in dead buffers
        self._sync_fused()
        self._invalidate_fused(drop=True)   # optimizer is changing
        if self._params_dirty:
            self._sync_params_from_devices()

        arg_dict = {n: self._exec.arg_dict[n] for n in self._param_names}
        (kvstore, update_on_kvstore) = _create_kvstore(
            kvstore, len(self._context), arg_dict)
        batch_size = self._data_shapes[0].shape[0]
        if kvstore and "dist" in kvstore.type and "_sync" in kvstore.type:
            batch_size *= kvstore.num_workers
        rescale_grad = 1.0 / batch_size

        if isinstance(optimizer, str):
            idx2name = {i: n for i, n in enumerate(self._param_names)}
            optimizer_params = dict(optimizer_params)
            if "rescale_grad" not in optimizer_params:
                optimizer_params["rescale_grad"] = rescale_grad
            optimizer = opt.create(optimizer, sym=self.symbol,
                                   param_idx2name=idx2name, **optimizer_params)
        else:
            assert isinstance(optimizer, opt.Optimizer)
            if optimizer.rescale_grad != rescale_grad:
                self.logger.warning(
                    "Optimizer created manually outside Module but rescale_grad "
                    f"is not normalized to 1.0/batch_size/num_workers "
                    f"({optimizer.rescale_grad} vs. {rescale_grad}). Is this "
                    "intended?")

        self._optimizer = optimizer
        self._kvstore = kvstore
        self._update_on_kvstore = update_on_kvstore
        self._updater = None

        # what holds the optimizer's state from here on: the kvstore's copy
        # of the weights, the updater, a state loaded before bind. The fused
        # step makes its own on the device at its first step (stage
        # ``fused_step``, perf/step_runtime.py module_stepper).
        with _profiler.span("bind.state", args={}) as made:
            if kvstore:
                # copy initialized weights into the store
                param_arrays = [self._exec.arg_dict[n]
                                for n in self._param_names]
                _initialize_kvstore(kvstore=kvstore,
                                    param_arrays=param_arrays,
                                    arg_params=self._arg_params or
                                    {n: self._exec.arg_dict[n]
                                     for n in self._param_names},
                                    param_names=self._param_names,
                                    update_on_kvstore=update_on_kvstore)
            if update_on_kvstore:
                kvstore.set_optimizer(self._optimizer)
            else:
                self._updater = opt.get_updater(optimizer)

            self.optimizer_initialized = True
            if self._preload_opt_states is not None:
                self.load_optimizer_states(self._preload_opt_states)
                self._preload_opt_states = None
            made.args["leaves"] = (len(self._updater.states)
                                   if self._updater is not None else 0)

    def borrow_optimizer(self, shared_module):
        """Share another Module's optimizer/updater/kvstore (reference
        module.py:borrow_optimizer — used by BucketingModule so all buckets
        update through one optimizer state)."""
        assert shared_module.optimizer_initialized
        # a cached fused step traced the OLD optimizer's update math:
        # flush its state and rebuild against the borrowed one
        self._sync_fused()
        self._invalidate_fused(drop=True)
        self._optimizer = shared_module._optimizer
        self._kvstore = shared_module._kvstore
        self._update_on_kvstore = shared_module._update_on_kvstore
        self._updater = shared_module._updater
        self.optimizer_initialized = True

    # -- compute -------------------------------------------------------------
    def _input_dict(self, data_batch):
        inputs = {}
        data = data_batch.data
        if not isinstance(data, (list, tuple)):
            data = [data]
        for name, arr in zip(self._data_names, data):
            inputs[name] = arr
        label = data_batch.label
        if label is not None and self._label_names:
            if not isinstance(label, (list, tuple)):
                label = [label]
            for name, arr in zip(self._label_names, label):
                inputs[name] = arr
        return inputs

    def forward(self, data_batch, is_train=None):
        """reference: module.py:556"""
        assert self.binded and self.params_initialized
        self._sync_fused()
        if is_train is None:
            is_train = self.for_training
        self._exec.forward(is_train=is_train,
                           **self._dp_place_inputs(
                               self._input_dict(data_batch)))

    def backward(self, out_grads=None):
        """reference: module.py:598"""
        assert self.binded and self.params_initialized
        self._exec.backward(out_grads=out_grads)

    def forward_backward(self, data_batch):
        """Fused path: one XLA program for fwd+bwd (avoids the recompute the
        separate backward() entry pays)."""
        assert self.binded and self.params_initialized
        self._sync_fused()
        self._exec.forward_backward(
            **self._dp_place_inputs(self._input_dict(data_batch)))

    def update(self):
        """reference: module.py:615"""
        assert self.binded and self.params_initialized \
            and self.optimizer_initialized
        self._sync_fused()
        self._params_dirty = True
        param_arrays = [self._exec.arg_dict[n] for n in self._param_names]
        grad_arrays = [self._exec.grad_dict.get(n) for n in self._param_names]
        if self._update_on_kvstore:
            _update_params_on_kvstore(param_arrays, grad_arrays, self._kvstore,
                                      self._param_names)
        else:
            _update_params(param_arrays, grad_arrays, updater=self._updater,
                           num_device=len(self._context),
                           kvstore=self._kvstore,
                           param_names=self._param_names)
        # keep params mesh-replicated for the next SPMD step (no-op when
        # the updater preserved placement or there is no mesh)
        self._dp_replicate_params()
        # the executor arrays changed under the stepper: it must re-pull
        # before its next step or this imperative update would be lost
        self._invalidate_fused()

    def get_outputs(self, merge_multi_context=True):
        assert self.binded
        return self._exec.outputs

    def get_input_grads(self, merge_multi_context=True):
        assert self.binded and self.params_initialized and self.inputs_need_grad
        return [self._exec.grad_dict.get(n) for n in self._data_names]

    def get_states(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        return [self._exec.arg_dict[n] for n in self._state_names]

    def set_states(self, states=None, value=None):
        assert self.binded and self.params_initialized
        if states is not None:
            for name, s in zip(self._state_names, states):
                self._exec.arg_dict[name]._set_data(
                    _as_jax(s, dtype=self._exec.arg_dict[name].dtype))
        else:
            for name in self._state_names:
                self._exec.arg_dict[name][:] = value

    def update_metric(self, eval_metric, labels):
        """reference: base_module.py:895 — metric consumes outputs lazily."""
        if labels is None:
            labels = []
        eval_metric.update(labels, self.get_outputs())

    def install_monitor(self, mon):
        assert self.binded
        self._sync_fused()
        self._invalidate_fused(drop=True)   # monitor needs the imperative path
        self._monitor = mon
        mon.install(self._exec)

    def _optimizer_state_bytes(self):
        """Serialized optimizer state. dump_optimizer=True also persists
        per-index update counts (Adam/rmsprop bias correction), so resumed
        training follows the uninterrupted trajectory — the reference
        loses these (its .states holds only the state arrays)."""
        assert self.optimizer_initialized
        self._sync_fused()
        if self._update_on_kvstore:
            return self._kvstore.get_optimizer_states(dump_optimizer=True)
        return self._updater.get_states(dump_optimizer=True)

    def save_optimizer_states(self, fname):
        from ..resilience import checkpoint as _ckpt
        _ckpt.write_bytes_guarded(fname, self._optimizer_state_bytes())

    def load_optimizer_states(self, fname):
        assert self.optimizer_initialized
        if self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
        else:
            from ..resilience import checkpoint as _ckpt
            self._updater.set_states(_ckpt.read_bytes_guarded(fname))
        self._invalidate_fused()

    def reshape(self, data_shapes, label_shapes=None):
        assert self.binded
        self._sync_fused()
        self._invalidate_fused(drop=True)
        data_shapes = [x if isinstance(x, DataDesc) else DataDesc(*x)
                       for x in data_shapes]
        if label_shapes is not None:
            label_shapes = [x if isinstance(x, DataDesc) else DataDesc(*x)
                            for x in label_shapes]
        else:
            label_shapes = []
        if self._dp_mesh is not None:
            # same loud divisibility contract as bind
            n = self._dp_mesh.shape["data"]
            for d in data_shapes + label_shapes:
                if d.shape and d.shape[0] % n:
                    raise MXNetError(
                        f"batch dimension of {d.name} {d.shape} is not "
                        f"divisible by the {n} bound contexts")
        self._data_shapes = data_shapes
        self._label_shapes = label_shapes
        shape_kwargs = {d.name: d.shape for d in data_shapes + label_shapes}
        self._exec = self._exec.reshape(**shape_kwargs)
