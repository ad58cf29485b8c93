"""PersistentJit: ``jax.jit`` with an ahead-of-time, on-disk program
store — plus the in-process program registry the executor shares traced
programs through.

A ``PersistentJit`` behaves exactly like the ``jax.jit`` it wraps; the
difference is WHERE the executable comes from on the first call of each
call signature:

1. in-memory table (this object already materialized the program);
2. the persistent :class:`~.cache.CompilationCache` — the executable is
   deserialized (``jax.experimental.serialize_executable``), skipping
   trace AND XLA compile entirely (the warm start);
3. a real ``lower().compile()`` — traced once, compiled once, then
   serialized into the cache for every later process.

The store can only ever change latency, never numerics, but it does
not fail quietly: a cached executable that will not load is logged at
WARNING, invalidated, counted (``invalid_load``) and recompiled; a
``lower().compile()`` that raises is logged at WARNING, counted
(``bypassed``) and that function goes through plain ``jax.jit`` from
then on; an executable that cannot be serialized (host callbacks) is
counted (``unserializable``) and stays in-process.

``on_materialize(kind)`` (kind in ``{"compiled", "loaded"}``) fires once
per new executable so retrace guards can count a cache load as the one
expected program materialization instead of reporting a missed compile.

Beside each executable it compiles, the store keeps that program's **op
map**: ``{HLO instruction name: op_name path}`` parsed once from
``compiled.as_text()`` (:func:`parse_op_map`). The path is what
``jax.named_scope`` left in the instruction's metadata, so the names a
device trace gives its operations (``fusion.2089``) read back as the graph
op that emitted them (``jvp(Convolution/stage1_unit1_conv1)``). A warm load
pays nothing for it; :func:`op_map` reads it back on demand.

Every materialization is one ``compile.materialize`` span of the program's
profiler (``mxnet_tpu/profiler.py``) whose ``args`` say which program
(``kind``, ``key``, ``sig``), what served it (``source``) and why it was
needed (``cause``), over one child span a phase: ``compile.store_get``,
``compile.load``, or ``compile.lower``, ``compile.backend``,
``compile.op_map``, ``compile.store_put``. What JAX itself reports of a
trace, a lowering or a backend compile (``jax.monitoring``) is recorded as
``jax.trace``, ``jax.lower``, ``jax.backend_compile`` under whichever span
is open, so the compiles that pass through no ``PersistentJit`` are seen
too.
"""
from __future__ import annotations

import contextlib
import json
import logging
import pickle
import re
import threading
import time
from collections import OrderedDict
from typing import Callable, Dict, Optional, Sequence

import jax
from jax.experimental import serialize_executable as _se

from .. import profiler as _profiler
from ..base import getenv
from . import cache as _cache
from .fingerprint import aval_signature, program_key

__all__ = ["PersistentJit", "ProgramRegistry", "program_stats",
           "reset_program_stats", "op_map", "parse_op_map"]

_lock = threading.Lock()
_prog_counters: Dict[str, int] = {}


def _count(key: str, n: int = 1):
    with _lock:
        _prog_counters[key] = _prog_counters.get(key, 0) + n


def program_stats() -> Dict[str, int]:
    """compiled/loaded/bypassed/shared program counters."""
    with _lock:
        base = {"compiled": 0, "loaded": 0, "bypassed": 0, "shared": 0,
                "invalid_load": 0, "unserializable": 0,
                "jax_cache_served": 0}
        base.update(_prog_counters)
        return base


def reset_program_stats():
    with _lock:
        _prog_counters.clear()


# JAX's own persistent cache can answer the store's compile (same HLO
# under another store key, or a store entry lost). The executable it
# hands back was deserialized. On XLA:CPU it must not be serialized again:
# XLA:CPU writes such an executable out without its kernels, and the
# entry then dies at run time with "Function <fusion> not found". A TPU
# executable carries its code and is written to the store like a compiled
# one: left to JAX's cache, every later process would trace and lower the
# program again before JAX's cache answered (the step of a decoder cell:
# 5.5-14.5 s each time). JAX records the hit on the compiling thread, so a
# thread-local count taken around one compile tells whose executable came
# back.
_jax_thread = threading.local()     # .cache_hits, .phases_open, .hits_before


def _rewritable(compiled) -> bool:
    """Whether an executable JAX's cache handed back may be serialized
    again: everywhere but on XLA:CPU (see above)."""
    return compiled.runtime_executable().local_devices()[0].platform != "cpu"


def _on_jax_event(event, **_):
    if event == "/jax/compilation_cache/cache_hits":
        _jax_thread.cache_hits = getattr(_jax_thread, "cache_hits", 0) + 1


# What JAX times of its own work, by the name its span takes. JAX reports a
# phase's start (a scalar, the wall clock) and at its end the duration; a
# traced body that calls jitted functions reports a trace for each of them
# inside its own, thousands in a model's step, so only the outermost phase
# open on a thread is recorded: what it covers is its own to tell.
_JAX_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "jax.backend_compile"}


def _on_jax_phase_start(event, _value, **_):
    if event in _JAX_PHASES:
        opened = getattr(_jax_thread, "phases_open", 0)
        if opened == 0:
            _jax_thread.hits_before = getattr(_jax_thread, "cache_hits", 0)
        _jax_thread.phases_open = opened + 1


def _on_jax_phase_end(event, seconds, fun_name=None, **_):
    name = _JAX_PHASES.get(event)
    if name is None:
        return
    still_open = getattr(_jax_thread, "phases_open", 1) - 1
    _jax_thread.phases_open = max(still_open, 0)
    if still_open > 0:
        return
    args = {"fun": str(fun_name)} if fun_name else {}
    if getattr(_jax_thread, "cache_hits", 0) != \
            getattr(_jax_thread, "hits_before", 0):
        args["jax_cache_hit"] = True    # JAX's own cache answered
    _jax_thread.hits_before = getattr(_jax_thread, "cache_hits", 0)
    end = time.perf_counter_ns()
    _profiler.record(name, end - int(seconds * 1e9), end, args=args or None)


jax.monitoring.register_event_listener(_on_jax_event)
jax.monitoring.register_scalar_listener(_on_jax_phase_start)
jax.monitoring.register_event_duration_secs_listener(_on_jax_phase_end)


# -- the op map --------------------------------------------------------------

_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'\bmetadata=\{[^}\n]*?\bop_name="([^"\n]*)"')

# kind -> (store key, parsed map or None) of the program of that kind this
# process materialized last; None until :func:`op_map` has read it back
_materialized: Dict[str, tuple] = {}


def parse_op_map(hlo_text: str):
    """``({instruction name: op_name}, instructions)`` from the text of a
    compiled HLO module: every instruction of every computation that
    carries an ``op_name`` (parameters left out), under its name without
    the ``%``; ``instructions`` counts the non-parameter instructions,
    named or not."""
    ops, total = {}, 0
    for line in hlo_text.splitlines():
        hit = _INSTRUCTION.match(line)
        if hit is None or " parameter(" in line:
            continue
        total += 1
        name = _OP_NAME.search(line, hit.end())
        if name is not None:
            ops[hit.group(1)] = name.group(1)
    return ops, total


def _op_map_key(key: str) -> str:
    return key + "-ops"


def _keep_op_map(store, kind: str, key: str, compiled):
    """Parse and store the op map of a program just compiled."""
    with _profiler.span("compile.op_map", args={}) as phase:
        try:
            ops, total = parse_op_map(compiled.as_text())
        except Exception as err:    # noqa: BLE001 — names only; never the run
            logging.info("PersistentJit[%s]: no op map (%s: %s)", kind,
                         type(err).__name__, err)
            return
        logging.info("PersistentJit[%s]: op map names %d of %d "
                     "instructions", kind, len(ops), total)
        phase.args.update(instructions=total, named=len(ops))
        store.put(_op_map_key(key), json.dumps(ops).encode(),
                  meta={"kind": kind, "op_map_of": key}, counter="op_maps")
        _materialized[kind] = (key, ops)


_METADATA_IN_KEY = "jax_compilation_cache_include_metadata_in_key"
_metadata_in_key = [0, False]   # compiles under way; the setting they found


@contextlib.contextmanager
def _metadata_in_jax_key():
    """While a PersistentJit compiles, JAX's own persistent cache keys on
    the module *with* its locations. By default it leaves them out, and a
    named scope is a location: a program would be served the executable of
    its twin compiled before a scope was added (by the checkout before,
    on the same cache directory), whose op_names name nothing, and the op
    map read from it would be empty. Only these compiles pay: the
    executable store answers for them first, under a key that holds no
    line of anybody's script, so their warm start does not depend on
    JAX's cache; every other ``jax.jit`` keeps JAX's default key."""
    with _lock:
        if _metadata_in_key[0] == 0:
            _metadata_in_key[1] = getattr(jax.config, _METADATA_IN_KEY)
            jax.config.update(_METADATA_IN_KEY, True)
        _metadata_in_key[0] += 1
    try:
        yield
    finally:
        with _lock:
            _metadata_in_key[0] -= 1
            if _metadata_in_key[0] == 0:
                jax.config.update(_METADATA_IN_KEY, _metadata_in_key[1])


def op_map(kind: str) -> Dict[str, str]:
    """The op map of the program of ``kind`` this process compiled or
    loaded last; empty where there is none (store off, program compiled
    before op maps were kept, entry evicted)."""
    key, ops = _materialized.get(kind, (None, None))
    if key is not None and ops is None:
        data = _cache.default_cache().get(_op_map_key(key))
        ops = json.loads(data) if data is not None else {}
        _materialized[kind] = (key, ops)
    return ops or {}


class PersistentJit:
    """Drop-in ``jax.jit`` wrapper with AOT load/store per call signature.

    ``key_parts`` are the stable identity strings of the *function
    being compiled* (graph fingerprint, optimizer signature, transform
    signature, ...); the concrete call signature (avals, shardings,
    statics) is appended per materialization. ``kind`` names the call
    site in the persisted key and the logs."""

    def __init__(self, fn: Callable, *, kind: str,
                 key_parts: Sequence[str] = (),
                 static_argnums: Sequence[int] = (),
                 donate_argnums: Sequence[int] = (),
                 on_materialize: Optional[Callable[[str], None]] = None):
        self._fn = fn
        self.kind = kind
        self._key_parts = tuple(str(p) for p in key_parts)
        self._static = tuple(static_argnums)
        self._static_set = frozenset(static_argnums)
        self._donate = tuple(donate_argnums)
        self._on_materialize = on_materialize
        self._jit = jax.jit(fn, static_argnums=self._static or None,
                            donate_argnums=self._donate or None)
        # instances are shared process-wide (executor ProgramRegistry)
        # and called from serving worker threads: materialization is
        # serialized so one signature never deserializes/compiles twice
        self._mat_lock = threading.Lock()
        self._programs: Dict[object, Callable] = {}
        # once lower()/compile() has rejected this function, every later
        # call goes straight to the plain jit — the per-call signature
        # walk must not outlive its purpose
        self._disabled = False
        # steady-state fast path, keyed by the static-arg values: each
        # statics combination keeps a short candidate list of
        # materialized programs, tried in order — the compiled
        # executable validates its own dynamic avals, raising on
        # mismatch (cheap) so the next candidate is tried. This keeps
        # multi-bucket serving (several dynamic shapes under identical
        # statics) off the per-leaf signature walk; only a signature
        # explosion (> _FAST_CANDIDATES) falls back to full dispatch.
        self._fast: Dict[object, list] = {}

    _FAST_CANDIDATES = 4

    # expose the underlying jit for callers that need .lower() etc.
    @property
    def jit(self):
        return self._jit

    def __call__(self, *args):
        if self._disabled or not _cache.cache_enabled():
            return self._jit(*args)
        try:
            statics_key = tuple(args[i] for i in self._static)
            fast = self._fast.get(statics_key)
        except (TypeError, IndexError):     # unhashable static: full path
            statics_key = None
            fast = None
        if fast:
            for cand in fast:
                try:
                    return cand(*args)
                except (TypeError, ValueError):
                    continue        # aval mismatch: try the next bucket
        try:
            sig, canon = aval_signature(args, self._static)
        except Exception:   # noqa: BLE001 — exotic leaves: plain jit path
            _count("bypassed")
            return self._jit(*args)
        prog = self._programs.get(sig)
        if prog is None:
            with self._mat_lock:
                prog = self._programs.get(sig)   # double-checked
                if prog is None:
                    prog = self._materialize(canon, args)
                    self._programs[sig] = prog
                    if statics_key is not None and prog is not self._jit:
                        cands = self._fast.setdefault(statics_key, [])
                        if len(cands) < self._FAST_CANDIDATES:
                            cands.append(prog)
        return prog(*args)

    # -- materialization -----------------------------------------------------

    def _wrap_compiled(self, compiled) -> Callable:
        static_set = self._static_set

        def run(*args):
            # no try/except here: the executable validates its input
            # avals itself, and a signature-matched call that still
            # fails is a real error the caller must see. (The fast path
            # in __call__ catches the validation error for the one
            # legitimate case — aval drift — and re-dispatches.)
            dyn = tuple(a for i, a in enumerate(args) if i not in static_set)
            return compiled(*dyn)

        return run

    def _notify(self, kind: str):
        _count(kind)
        if self._on_materialize is not None:
            self._on_materialize(kind)

    def _materialize(self, canon: str, args) -> Callable:
        """One new executable, under one ``compile.materialize`` span that
        says which program, what served it and why it was needed."""
        note = {"kind": self.kind,
                # a program this object already has means a drifted shape,
                # dtype or static: the case CompileGuard warns of
                "cause": "new_signature" if self._programs else "first",
                "sig": canon[:120]}
        with _profiler.span("compile.materialize", args=note):
            prog = self._materialize_noted(canon, args, note)
        if note["source"] != "bypassed":
            _profiler.count("compile.materialized")
            _profiler.count("compile." + note["source"])
        return prog

    def _materialize_noted(self, canon: str, args, note: dict) -> Callable:
        key = program_key(self.kind, "+".join(self._key_parts), canon,
                          donation=self._donate)
        note["key"] = key[:12]
        store = _cache.default_cache()
        with _profiler.span("compile.store_get", args={}) as phase:
            data = store.get(key)
            phase.args["bytes"] = len(data) if data is not None else 0
        if data is not None:
            try:
                with _profiler.span("compile.load"):
                    payload, in_tree, out_tree, device_ids = \
                        pickle.loads(data)
                    compiled = _se.deserialize_and_load(
                        payload, in_tree, out_tree,
                        execution_devices=_devices_by_id(device_ids))
                self._notify("loaded")
                _materialized[self.kind] = (key, None)
                note["source"] = "loaded"
                return self._wrap_compiled(compiled)
            except Exception as err:    # noqa: BLE001 — entry unusable here
                logging.warning("PersistentJit[%s]: cached executable "
                                "%s failed to load (%s: %s); recompiling",
                                self.kind, key[:12], type(err).__name__, err)
                # a digest-valid entry that cannot deserialize is as
                # invalid as a corrupt one — one shared invalidation
                # definition lives on the cache
                store.invalidate(key)
                _count("invalid_load")
                note["invalid_load"] = True
        jax_hits = getattr(_jax_thread, "cache_hits", 0)
        try:
            with _metadata_in_jax_key():
                # the python step body traced and lowered, then XLA and
                # Mosaic: two spans, so the two can be told apart
                with _profiler.span("compile.lower"):
                    lowered = self._jit.lower(*args)
                with _profiler.span("compile.backend"):
                    compiled = lowered.compile()
        except Exception as err:        # noqa: BLE001 — AOT-unfriendly call
            # loud, counted, and the same call then goes through the
            # plain jit, which raises the real error if there is one
            logging.warning("PersistentJit[%s]: lower/compile failed "
                            "(%s: %s); this program runs through plain "
                            "jax.jit, outside the executable store",
                            self.kind, type(err).__name__, err)
            _count("bypassed")
            self._disabled = True       # don't re-pay the sig walk per call
            note["source"] = "bypassed"
            return self._jit
        self._notify("compiled")
        _keep_op_map(store, self.kind, key, compiled)
        if getattr(_jax_thread, "cache_hits", 0) != jax_hits:
            # JAX's cache served it; on the CPU it keeps serving it (above)
            _count("jax_cache_served")
            note["source"] = "jax_cache_served"
            if not _rewritable(compiled):
                return self._wrap_compiled(compiled)
        else:
            note["source"] = "compiled"
        with _profiler.span("compile.store_put", args={}) as phase:
            try:
                payload, in_tree, out_tree = _se.serialize(compiled)
                # the program's own device assignment, in order: a warm
                # load must hand deserialize_and_load exactly these, or it
                # spreads a one-device program over every local device
                device_ids = [d.id for d in
                              compiled.runtime_executable().local_devices()]
                data = pickle.dumps((payload, in_tree, out_tree, device_ids))
                phase.args["bytes"] = len(data)
                store.put(key, data,
                          meta={"kind": self.kind, "sig": canon[:512]})
            except Exception as err:        # noqa: BLE001 — unserializable
                logging.info("PersistentJit[%s]: executable not "
                             "serializable (%s: %s); in-process only",
                             self.kind, type(err).__name__, err)
                _count("unserializable")
        return self._wrap_compiled(compiled)


def _devices_by_id(device_ids):
    by_id = {d.id: d for d in jax.devices()}
    return [by_id[i] for i in device_ids]


class ProgramRegistry:
    """Fingerprint-keyed LRU of in-process program bundles.

    Replaces the executor's ``shared_exec._symbol is symbol`` staleness
    rule: two executors over structurally identical graphs (same
    fingerprint + same sparse-proxy signature) share ONE set of traced
    callables, so the second bind's first step hits the first's trace
    cache instead of silently retracing. Capped — eviction only costs
    sharing, never correctness."""

    def __init__(self, cap: Optional[int] = None):
        if cap is None:
            cap = getenv("MXTPU_PROGRAM_REGISTRY_CAP", 64, int)
        self.cap = int(cap)
        self._lock = threading.Lock()
        self._entries: "OrderedDict[object, object]" = OrderedDict()

    def get_or_build(self, key, builder: Callable[[], object]):
        with self._lock:
            hit = self._entries.get(key)
            if hit is not None:
                self._entries.move_to_end(key)
                _count("shared")
                return hit
        bundle = builder()
        with self._lock:
            # a racing builder may have landed first; last one wins is
            # fine (both bundles are equivalent programs)
            self._entries[key] = bundle
            self._entries.move_to_end(key)
            while len(self._entries) > self.cap:
                self._entries.popitem(last=False)
        return bundle

    def __len__(self):
        with self._lock:
            return len(self._entries)

    def clear(self):
        with self._lock:
            self._entries.clear()
