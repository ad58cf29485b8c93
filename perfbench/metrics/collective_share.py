"""Time covered by all-reduce / reduce-scatter / all-gather / all-to-all /
collective-permute operations on one chip over the traced window, in
percent. Nothing to read on one chip."""
import re

COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute")


def read(ctx):
    if ctx["chips"] < 2:
        return None
    trace = ctx["trace"]
    dev = trace.busiest()
    if dev is None:
        return None
    # the instruction's own name, not the operands a consumer lists; async
    # collectives run on the line beside the synchronous operations
    ops = trace.devices[dev] + trace.async_ops.get(dev, [])
    spans = [(s, e) for name, s, e in ops
             if COLLECTIVE.search(name.split(" = ")[0])]
    if not spans:
        return None
    from perfbench.reduce import union_seconds
    return 100.0 * union_seconds(spans) / ctx["window_s"]
