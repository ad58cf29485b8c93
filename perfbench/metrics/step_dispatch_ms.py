"""What one step costs the fit thread when nothing makes it wait, in
milliseconds: the least ``fit.step`` duration in the window (placing the
batch, ``step.place``; the step's own lines, a key split and two scalar
transfers; calling the step program, ``step.dispatch``).

Why the least and not the median. Where the device is the bottleneck the
host runs ahead until the runtime makes it wait for a free slot, inside
``fit.step`` (in ``step.dispatch`` under ``Module.fit``, in the step's own
lines before it under ``SPMDTrainer``), and from then on every ``fit.step``
lasts one device step: its median, and in a ResNet-50 cell its tenth
percentile too, read the kernels and not the host. A wait only ever
lengthens a span, so the least is a step that did not wait, and there is
one in every window: the window's first step is dispatched onto a device
that ``program.sync()`` has just drained. A slower dispatch moves it; a
faster kernel does not. Spans the window's edge cuts are left out.
"""
from perfbench import scopes


def read(ctx):
    profiler, window = scopes.program_profiler(ctx), scopes.window_ns(ctx)
    if profiler is None or window is None:
        return None
    lo, hi = window
    steps = [s.end_ns - s.start_ns for s in profiler.spans(lo, hi)
             if s.name == "fit.step" and s.start_ns >= lo and s.end_ns <= hi]
    return min(steps) / 1e6 if steps else None
