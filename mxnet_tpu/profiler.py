"""Profiler: the program's own spans and counters, on one host clock.

Reference surface: python/mxnet/profiler.py (profiler_set_config,
profiler_set_state, dump_profile) over src/engine/profiler.{h,cc}, which
stamps operator start/end in ThreadedEngine::ExecuteOprBlock and dumps
Chrome tracing JSON (profiler.h:106-124). Env controls
MXNET_PROFILER_AUTOSTART / MXNET_PROFILER_MODE (docs/how_to/env_var.md).

TPU-native rebuild. The host's work is recorded where it happens, as
*spans*: ``with profiler.span("fit.step"):`` stamps name, start and end
(``time.perf_counter_ns``), the thread, the enclosing span on that thread
(its cause) and a **batch ordinal**: the running number of the batch since
the iterator's last ``reset()``, which the input pipeline's producer thread
and the fit loop each count for themselves, so every span of one batch
carries the same number without a field on ``DataBatch``. A span may carry
a few named values of its own (``args``: what served a program, how many
bytes went to the device), and work whose start is only known at its end
is put in by its end points (:func:`record`).

* Always (state 'stop', the default): a span goes into a bounded in-memory
  ring and adds its duration to a per-name total. No lock beyond the GIL,
  no device sync, no file. :func:`spans`, :func:`totals`, :func:`counters`
  read them; ``perfbench/metrics/`` does.
* ``profiler_set_state('run')`` / ``MXTPU_PROFILER_AUTOSTART``, or between
  :func:`start_xla_trace` and :func:`stop_xla_trace`: each span is also a
  ``jax.profiler.TraceAnnotation(name, batch=k, **args)``, so in an XLA trace the
  spans lie on the profiler's own clock in ``/host:CPU`` beside the
  device's operations. :func:`dump_profile` writes the ring as Chrome JSON.

Nothing here waits for the device: a span around a dispatch times the
dispatch. Device time is the device trace's to give
(:func:`start_xla_trace`), and :func:`op_scopes` names its instructions by
the graph op that emitted them (``Convolution/stage1_unit1_conv1``).
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time
from collections import namedtuple
from typing import Dict, List, Optional

from .base import MXNetError, getenv

__all__ = ["profiler_set_config", "profiler_set_state", "dump_profile",
           "start_xla_trace", "stop_xla_trace", "is_running",
           "span", "record", "count", "spans", "totals", "self_totals",
           "counters", "set_batch", "op_scopes", "Span"]

_MODES = ("symbolic", "imperative", "all")

# One constant size. The fit thread records at most 10 spans a step and a
# producer 5 a batch (``input.fetch``, and ``input.slice`` + ``input.h2d``
# for the data and again for the label), so the ring holds the last ~4,000
# steps. The spans of set-up (``import.*``, ``bind*``, ``input.construct``,
# ``compile.*``, ``jax.*``) come once a process or once a program, some
# tens in all.
RING_SIZE = 1 << 16

Span = namedtuple("Span",
                  "seq name start_ns end_ns thread parent batch cat args",
                  defaults=(None,))
# a record built straight from its tuple, without the namedtuple's own
# python-level constructor: the one allocation a span costs
_new_span = tuple.__new__


class _ThreadState(threading.local):
    """Per thread: the open spans, the loop's batch ordinal, and this
    thread's share of the totals and counters (merged on read, so no two
    threads ever write one cell)."""

    def __init__(self):
        self.stack: List["span"] = []
        self.ident = threading.get_ident()
        self.batch: Optional[int] = None
        self.totals: Dict[str, list] = {}      # name -> [count, ns]
        self.counts: Dict[str, int] = {}
        _PROF.enrol(threading.current_thread(), self.totals, self.counts)


class _Profiler:
    def __init__(self):
        self.mode = "symbolic"
        self.filename = "profile.json"
        self.running = False
        self.xla_tracing = False
        self.annotation = None          # jax.profiler.TraceAnnotation
        self.ring: List[Optional[Span]] = [None] * RING_SIZE
        self.seq = itertools.count()    # next() is atomic under the GIL
        self.mark_ns = 0                # dump_profile writes spans since
        self.t0_ns = time.perf_counter_ns()
        # (thread, its totals, its counts); a thread that has ended is
        # folded into the first entry when the next one enrols
        self.threads: list = [(None, {}, {})]
        self.enrol_lock = threading.Lock()

    def enrol(self, thread, totals, counts):
        with self.enrol_lock:
            ended = [t for t in self.threads[1:] if not t[0].is_alive()]
            if ended:
                _fold(ended, self.threads[0][1], self.threads[0][2])
                self.threads = [t for t in self.threads if t not in ended]
            self.threads.append((thread, totals, counts))


def _fold(entries, totals, counts):
    for _thread, t, c in entries:
        for name, (n, ns) in list(t.items()):
            have = totals.setdefault(name, [0, 0])
            have[0] += n
            have[1] += ns
        for name, n in list(c.items()):
            counts[name] = counts.get(name, 0) + n


_PROF = _Profiler()
_TLS = _ThreadState()


def profiler_set_config(mode: str = "symbolic",
                        filename: str = "profile.json"):
    """Configure which per-call spans are recorded and where
    :func:`dump_profile` writes.

    mode: 'symbolic' (executor Forward/ForwardBackward), 'imperative'
    (one span per nd.* op call), 'all' (both; reference mode2int maps
    symbolic=0, all=1). The fit-loop and input-pipeline spans are
    recorded in every mode."""
    if mode not in _MODES:
        raise MXNetError(f"profiler mode must be one of {_MODES}")
    _PROF.mode = mode
    _PROF.filename = filename


def _load_annotation():
    if _PROF.annotation is None:
        import jax
        _PROF.annotation = jax.profiler.TraceAnnotation


def profiler_set_state(state: str = "stop"):
    """'run': spans are also written as ``TraceAnnotation``s and the
    per-call spans of the configured mode are recorded; 'stop' (the
    default): spans go to the in-memory ring only."""
    if state not in ("run", "stop"):
        raise MXNetError("profiler state must be 'run' or 'stop'")
    run = state == "run"
    if run and not _PROF.running:
        _PROF.mark_ns = time.perf_counter_ns()
        _load_annotation()
    _PROF.running = run


def is_running(kind: str = "symbolic") -> bool:
    """Should the per-call spans of this kind be recorded now?"""
    return _PROF.running and (_PROF.mode == "all" or _PROF.mode == kind)


class span:
    """Context manager recording one span of host work.

    ``batch``: the batch ordinal; left out, the span takes the enclosing
    span's, or else the ordinal its thread's fit loop set
    (:func:`set_batch`). ``kind`` ('symbolic' / 'imperative') marks a
    per-call span that is recorded only while the profiler runs in that
    mode; the named spans of the fit loops and the input pipeline pass
    none and are always recorded. ``args``: a small dict of named values
    that goes into the record as it is; the body may add to it until the
    span closes (``with span("x", args={}) as s: s.args["source"] = ...``).
    Values it holds when the span opens are also the ``TraceAnnotation``'s
    keywords while a trace runs. A span given none carries ``None``."""

    __slots__ = ("name", "batch", "cat", "seq", "parent", "start", "note",
                 "args")

    def __init__(self, name: str, batch: Optional[int] = None,
                 cat: str = "span", kind: Optional[str] = None,
                 args: Optional[dict] = None):
        self.name = name
        self.batch = batch
        self.cat = cat
        self.args = args
        self.seq = None if kind is not None and not is_running(kind) else -1

    def __enter__(self):
        if self.seq is None:
            return self
        tls = _TLS
        stack = tls.stack
        if stack:
            top = stack[-1]
            self.parent = top.seq
            if self.batch is None:
                self.batch = top.batch
        else:
            self.parent = -1
            if self.batch is None:
                self.batch = tls.batch
        stack.append(self)
        self.seq = next(_PROF.seq)
        self.note = None
        if _PROF.running or _PROF.xla_tracing:
            words = dict(self.args) if self.args else {}
            if self.batch is not None:
                words["batch"] = self.batch
            self.note = _PROF.annotation(self.name, **words)
            self.note.__enter__()
        self.start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        if self.seq is None:
            return False
        end = time.perf_counter_ns()
        if self.note is not None:
            self.note.__exit__(*exc)
        tls = _TLS
        tls.stack.pop()
        _PROF.ring[self.seq % RING_SIZE] = _new_span(Span, (
            self.seq, self.name, self.start, end, tls.ident,
            self.parent, self.batch, self.cat, self.args))
        cell = tls.totals.get(self.name)
        if cell is None:
            cell = tls.totals[self.name] = [0, 0]
        cell[0] += 1
        cell[1] += end - self.start
        return False


def record(name: str, start_ns: int, end_ns: int,
           args: Optional[dict] = None):
    """Put a finished span into the ring and the totals by its end points
    (``time.perf_counter_ns``): for work whose start is only known once it
    has ended, such as a duration a library reports. Its cause is the span
    open on this thread now, its batch ordinal that span's or the loop's."""
    tls = _TLS
    if tls.stack:
        top = tls.stack[-1]
        parent, batch = top.seq, top.batch
    else:
        parent, batch = -1, tls.batch
    seq = next(_PROF.seq)
    _PROF.ring[seq % RING_SIZE] = Span(
        seq, name, start_ns, end_ns, tls.ident, parent, batch, "span", args)
    cell = tls.totals.get(name)
    if cell is None:
        cell = tls.totals[name] = [0, 0]
    cell[0] += 1
    cell[1] += end_ns - start_ns


def count(name: str, n: int = 1):
    """Add ``n`` to the counter ``name``."""
    counts = _TLS.counts
    counts[name] = counts.get(name, 0) + n


def set_batch(k: Optional[int]):
    """The batch ordinal of this thread's loop: spans opened on it outside
    any other span, with no ordinal of their own, take this one."""
    _TLS.batch = k


def spans(since_ns: int = 0, until_ns: Optional[int] = None) -> List[Span]:
    """The recorded spans that overlap ``[since_ns, until_ns]`` on
    ``time.perf_counter_ns``, by start. The ring keeps the newest
    ``RING_SIZE``; older ones are gone."""
    out = [s for s in list(_PROF.ring)
           if s is not None and s.end_ns >= since_ns
           and (until_ns is None or s.start_ns <= until_ns)]
    out.sort(key=lambda s: (s.start_ns, s.seq))
    return out


def _merged():
    totals, counts = {}, {}
    with _PROF.enrol_lock:
        _fold(_PROF.threads, totals, counts)
    return totals, counts


def totals() -> Dict[str, tuple]:
    """name -> (count, summed duration in ns) over every span recorded
    since import, whether or not the ring still holds it."""
    return {name: tuple(v) for name, v in _merged()[0].items()}


def counters() -> Dict[str, int]:
    """name -> value of every :func:`count`er since import."""
    return _merged()[1]


def self_totals(since_ns: int = 0,
                until_ns: Optional[int] = None) -> Dict[str, int]:
    """name -> summed self time in ns of the spans in the interval: a
    span's duration less what its child spans (those it caused, on its
    thread) cover of it."""
    found = spans(since_ns, until_ns)
    own = {s.seq: s.end_ns - s.start_ns for s in found}
    for s in found:
        if s.parent in own:
            own[s.parent] -= s.end_ns - s.start_ns
    out: Dict[str, int] = {}
    for s in found:
        out[s.name] = out.get(s.name, 0) + max(own[s.seq], 0)
    return out


def dump_profile():
    """Write the spans recorded since the profiler was set to 'run' (or
    since the last dump) as Chrome trace JSON (chrome://tracing / perfetto
    format) and stop the profiler (reference MXDumpProfile semantics)."""
    profiler_set_state("stop")
    found = spans(_PROF.mark_ns)
    _PROF.mark_ns = time.perf_counter_ns()
    names = {s.seq: s.name for s in found}
    tids: Dict[int, int] = {}
    events = []
    for s in found:
        args = dict(s.args) if s.args else {}
        if s.batch is not None:
            args["batch"] = s.batch
        if s.parent in names:
            args["parent"] = names[s.parent]
        ev = {"name": s.name, "cat": s.cat, "ph": "X",
              "ts": (s.start_ns - _PROF.t0_ns) / 1e3,
              "dur": max((s.end_ns - s.start_ns) / 1e3, 0.01),
              "pid": 0, "tid": tids.setdefault(s.thread, len(tids))}
        if args:
            ev["args"] = args
        events.append(ev)
    with open(_PROF.filename, "w") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
    return _PROF.filename


def op_scopes(kind: str) -> Dict[str, str]:
    """``{HLO instruction name: op_name path}`` of the program of this
    ``kind`` ('spmd-step', 'fused-step', ...) that this process compiled
    or loaded last: the names a device trace gives its operations
    (``fusion.2089``), each with the scope of the graph op that emitted it
    (``jit(step)/jit(main)/jvp(Convolution/stage1_unit1_conv1)/...``).
    Empty where no such program exists or its map was never written."""
    from .compiler import aot
    return aot.op_map(kind)


# -- deep device traces (TPU-native extra) ---------------------------------

_XLA_TRACE_DIR = None


def start_xla_trace(logdir: str = "/tmp/mxtpu_xla_trace"):
    """Start a jax/XLA device trace (XPlane, viewable in TensorBoard or
    xprof). Until :func:`stop_xla_trace` every span is also a
    ``TraceAnnotation``, so the trace shows ``fit.fetch``, ``fit.step``,
    ``input.fetch``... on the host's lines beside the device's operations,
    on one clock. The host tracer runs at level 1, which keeps those and
    drops the runtime's level-2 events. It does not make a host-fed run's trace
    small: a ``device_put`` that changes the layout on the host writes one
    ``Transpose`` event per tile at level 1 (11.8 M events, 408 MB, in 2 s
    of a ResNet-50 run fed 154 MB a step), and slows the run it traces:
    trace a second or two of such a run, not a window."""
    global _XLA_TRACE_DIR
    import jax
    os.makedirs(logdir, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    _load_annotation()
    jax.profiler.start_trace(logdir, profiler_options=opts)
    _PROF.xla_tracing = True
    _XLA_TRACE_DIR = logdir
    return logdir


def stop_xla_trace():
    global _XLA_TRACE_DIR
    import jax
    _PROF.xla_tracing = False
    jax.profiler.stop_trace()
    d, _XLA_TRACE_DIR = _XLA_TRACE_DIR, None
    return d


# reference parity: env-var autostart (docs/how_to/env_var.md:101-108;
# the reference's MODE is 0/1 — accept both spellings)
if getenv("MXTPU_PROFILER_AUTOSTART", 0, int):
    _m = getenv("MXTPU_PROFILER_MODE", "all", str)
    if _m not in _MODES:
        _m = "symbolic" if _m == "0" else "all"
    profiler_set_config(_m)
    profiler_set_state("run")
    del _m
