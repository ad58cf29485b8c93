"""What the per-layer metrics share that read the program's own records:
its host spans (``mxnet_tpu.profiler.spans``) cut to the window, and the
device's operations charged to the graph op that emitted them
(``mxnet_tpu.profiler.op_scopes``).

The driver has closed the program and deleted the trace directory before a
reader runs, so readers use what survives in the process: ``ctx["trace"]``,
``ctx["feed"]["calls"]`` and ``ctx["window_s"]`` (the window on
``time.perf_counter``), and the program's profiler module. A program that
has no such records (the commit before they existed, a CPU rehearsal with no
device planes) makes every function here return ``None`` and no reader
raise.

Joins. The window is ``[calls[0][0], calls[0][0] + window_s]``, the first
``next()`` of the timed epoch to the ``block_until_ready`` after ``fit``;
spans are cut to it by their stamps. The j-th ``step.dispatch`` span in the
window dispatched the device's j-th step (``trace.steps()[j]``). The device
trace has a clock of its own: the offset between the two is taken at the
window's first step, which is dispatched onto a device that
``program.sync()`` has just drained, so it starts on the device when its
dispatch starts on the host, to within the dispatch's own length.
"""
import re

NS = 1e9

# the kinds of the two step programs (PersistentJit ``kind=``): a cell
# compiles one of them
STEP_KINDS = ("spmd-step", "fused-step")

_JIT = re.compile(r"^(?:p?jit\([^()]*\)/)+")
_WRAPPED = re.compile(r"^((?:[\w\-]+\()+)([^()]*)\)+(?:/|$)")


def program_profiler(ctx):
    """The program's profiler module, or None where it records no spans."""
    if "profiler" in ctx:
        return ctx["profiler"]
    try:
        from mxnet_tpu import profiler
    except Exception:       # noqa: BLE001 — no program, nothing to read
        return None
    return profiler if hasattr(profiler, "spans") else None


def window_ns(ctx):
    calls = ctx["feed"]["calls"]
    if not calls:
        return None
    lo = calls[0][0]
    return int(lo * NS), int((lo + ctx["window_s"]) * NS)


def window_spans(ctx, prefix=""):
    """``(name, start_ns, end_ns)`` of the program's spans whose name starts
    with ``prefix``, cut to the window, by start; None without spans."""
    profiler, window = program_profiler(ctx), window_ns(ctx)
    if profiler is None or window is None:
        return None
    lo, hi = window
    found = [(s.name, max(s.start_ns, lo), min(s.end_ns, hi))
             for s in profiler.spans(lo, hi) if s.name.startswith(prefix)]
    found = [s for s in found if s[2] > s[1]]
    return found or None


def span_share(ctx, name):
    """Summed duration of the spans called ``name`` inside the window over
    the window, in percent."""
    found = window_spans(ctx, name)
    if found is None:
        return None
    total = sum(e - s for n, s, e in found if n == name)
    return 100.0 * total / (ctx["window_s"] * NS) if total else None


def scope_of(op_name):
    """``(scope, backward)`` of an instruction's ``op_name`` path.

    ``jit(step)/jit(main)/transpose(jvp(Convolution/stage1_unit1_conv1))/
    conv_general_dilated`` -> ``("Convolution/stage1_unit1_conv1", True)``;
    ``jit(step)/optimizer_update/mul`` -> ``("optimizer_update", False)``.
    The last segment is the primitive; what stands before it is the stack
    of named scopes, the outermost wrapped in the transformations it was
    traced under. A path with no scope before its primitive, or whose
    wrapper is empty (``transpose(jvp())/broadcast_in_dim``: the step's own
    cotangent), has none: ``(None, False)``."""
    path = _JIT.sub("", op_name or "")
    wrapped = _WRAPPED.match(path)
    if wrapped is not None:
        scope = wrapped.group(2)
        return (scope or None), \
            bool(scope) and "transpose(" in wrapped.group(1)
    head, sep, _ = path.rpartition("/")
    if not sep:
        return None, False
    parts = head.split("/")
    # an interpreter scope is "<Op>/<node>"; a rider's is one word
    scope = "/".join(parts[:2]) if parts[0][:1].isupper() and len(parts) > 1 \
        else parts[0]
    return scope, False


def step_op_map(ctx):
    """The op map of the cell's step program, or None."""
    profiler = program_profiler(ctx)
    if profiler is None or not hasattr(profiler, "op_scopes"):
        return None
    for kind in STEP_KINDS:
        ops = profiler.op_scopes(kind)
        if ops:
            return ops
    return None


def instruction(event_name):
    """The instruction's own name in a device event's text
    (``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``)."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def scoped_seconds(ctx):
    """``({(scope, backward): seconds}, step_seconds, unscoped)`` on the
    busiest chip: the self time of every operation that ran inside a step
    program, summed under the scope its own ``op_name`` carries (a fusion is
    charged whole to the scope of the fusion instruction itself, which XLA
    takes from the fusion's root), ``(None, False)`` for those with none;
    the summed device time of the step programs; and ``{event name:
    seconds}`` of the operations with no scope. None without a trace, steps
    or map."""
    if "scoped_seconds" not in ctx:     # several readers, one reduction
        ctx["scoped_seconds"] = _scoped_seconds(ctx)
    return ctx["scoped_seconds"]


def _scoped_seconds(ctx):
    trace, ops = ctx["trace"], step_op_map(ctx)
    dev = trace.busiest()
    if dev is None or ops is None:
        return None
    steps = trace.steps(dev)
    if not steps:
        return None
    from perfbench.reduce import self_seconds
    inside, k = [], 0
    for name, start, end in sorted(trace.devices[dev], key=lambda o: o[1]):
        while k < len(steps) and steps[k][1] <= start:
            k += 1
        if k < len(steps) and steps[k][0] <= start:
            inside.append((name, start, end))
    out, unscoped = {}, {}
    for name, seconds in self_seconds(inside).items():
        key = scope_of(ops.get(instruction(name)))
        out[key] = out.get(key, 0.0) + seconds
        if key[0] is None:
            unscoped[name] = seconds
    return out, sum(e - s for s, e in steps), unscoped


def share_of_step(ctx, wanted):
    """Percent of the step programs' device time spent under the scopes
    ``wanted(scope, backward)`` accepts."""
    found = scoped_seconds(ctx)
    if found is None:
        return None
    by_scope, step_seconds, _ = found
    hit = sum(s for (scope, back), s in by_scope.items()
              if wanted(scope, back))
    return 100.0 * hit / step_seconds if step_seconds > 0 else None


def op_type(scope):
    return scope.split("/", 1)[0] if scope else None


def host_offset(ctx):
    """Seconds to add to a device stamp to get ``time.perf_counter``: the
    host start of the window's first ``step.dispatch`` less the device
    start of the window's first step. None without both."""
    steps = ctx["trace"].steps()
    dispatches = window_spans(ctx, "step.dispatch")
    if not steps or dispatches is None:
        return None
    return dispatches[0][1] / NS - steps[0][0]
