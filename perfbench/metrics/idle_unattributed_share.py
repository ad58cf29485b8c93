"""Share of the busiest chip's idle time in the window that lies inside no
``fit.*`` span of the program, in percent; lower is better.

The chip's idle gaps (between the first and the last operation of the
traced window) are moved onto the host's clock by ``scopes.host_offset``
and cut against the union of the fit loop's spans (``fit.fetch``,
``fit.step``, ``fit.metric``, ``fit.callbacks``). What a span covers is
attributed: the device idled while the host was in that call. What is left
is idle time the program cannot name: the loop's own lines between spans,
and the error of the offset (up to one dispatch's length).

Listed for the cells whose chip idles 1% of the window or more (the fed
cell, four chips). Where the device is busy 99.9% of the window the idle
time is some 10 ms in all, much of it one gap at the window's first step,
where the offset is taken: the share is then the offset's error and reads
nothing of the program (resident cell: 11.8% and 8.3% on two seeds)."""
from perfbench import scopes
from perfbench.reduce import gaps


def read(ctx):
    trace = ctx["trace"]
    dev = trace.busiest()
    offset = scopes.host_offset(ctx)
    fit = scopes.window_spans(ctx, "fit.")
    if dev is None or offset is None or fit is None:
        return None
    ops = [(s, e) for _, s, e in trace.devices[dev]]
    idle = gaps(ops, min(s for s, _ in ops), max(e for _, e in ops))
    total = sum(e - s for s, e in idle)
    if total <= 0:
        return None
    covers = [(s / scopes.NS, e / scopes.NS) for _, s, e in fit]
    # what the fit loop's spans leave of each idle gap, on the host's clock
    left = sum(e - s for lo, hi in idle
               for s, e in gaps(covers, lo + offset, hi + offset))
    return 100.0 * left / total
