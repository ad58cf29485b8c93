#!/usr/bin/env python3
"""Device time of a step by the model's own blocks, for programs whose graph
names them (``mx.AttrScope(__block__="layer3")``: every instruction's
``op_name`` then reads ``.../layer3/<Op>/<node>/<part>/<primitive>``).

``perfbench/scopes.py`` charges an instruction to the outermost scope alone;
the readers of a decoder's metrics need the op type and the part inside the
block, and whether the instruction ran in the first forward, in the forward
recomputed inside the backward (``jax.checkpoint``: ``rematted_computation``)
or in the backward itself. :func:`block_seconds` gives that, from the same
records (``ctx["trace"]``, the step program's op map) and with the same
charging rule (self time; a fusion whole to the scope its own ``op_name``
carries). A program without blocks or without an op map gives None.

    python3 perfbench/blocks.py --workload <cell> --seed <n> --seconds <s>

is ``tables.py`` for such a cell: the traced run, then one ``blocks {...}``
line (device seconds by block, by op and stage, by part, the five decoder
readers' numbers and the routed counters) that PERF.md section 5 is written
from.
"""
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import scopes  # noqa: E402

# the readers under perfbench/metrics/ that read these records; main() prints
# them beside the table (BENCHMARK.json entries since PR 37)
DECODER_METRICS = ("attention_share", "moe_share",
                   "window_attention_roofline", "expert_matmul_roofline",
                   "moe_load_max_over_mean")

_BLOCK = re.compile(r"(?:^|[/(])(layer\d+|loss_head)(?:\)+/|/)")


def parse(op_name):
    """``(block, op type, part, stage)`` of an instruction's ``op_name``, or
    None where it names no block. ``part`` is the named scope inside the op
    (``experts`` of ``MoEFFN/layer1_moe/experts/ragged_dot``), '' for none;
    ``stage`` is forward, recompute or backward."""
    found = _BLOCK.search(op_name or "")
    if found is None:
        return None
    before, rest = op_name[:found.start()], op_name[found.end():].split("/")
    stage = "recompute" if "rematted_computation" in before else \
        "backward" if "transpose(" in before else "forward"
    op = rest[0] if rest[0][:1].isupper() and len(rest) > 1 else ""
    part = rest[2] if op and len(rest) > 3 and rest[2].islower() \
        and "(" not in rest[2] else ""
    return found.group(1), op, part, stage


def grouped_matmul(event_name):
    """Is this device event one of XLA's grouped-matmul kernels? The
    compiler lowers ``jax.lax.ragged_dot`` to kernels of its own that carry
    ``op_name="ragged-dot-none"``: the program's scope does not reach them,
    and first forward, recomputed forward and backward cannot be told
    apart. Only ``MoEFFN``'s routed experts run them; their time is charged
    there under the stage ``kernel``. (The metadata kernel that precedes
    each group of them takes microseconds and is counted with them.)"""
    return scopes.instruction(event_name).startswith("ragged-dot")


def routed_window(ctx):
    """The routed layers' counters over the window (``moe.*`` of the
    program's own counters: the model file's ``Program.counters()`` adds
    what the device's grew by between the window's two ends); None for a
    program without them."""
    profiler = scopes.program_profiler(ctx)
    found = {k: v for k, v in profiler.counters().items()
             if k.startswith("moe.")} \
        if profiler is not None and hasattr(profiler, "counters") else {}
    return found if found.get("moe.assignments_held") else None


def block_seconds(ctx):
    """``({(block, op, part, stage): seconds}, step_seconds, other_seconds)``
    on the busiest chip over the traced window, ``other`` the step's
    operations that name no block (the embedding, the riders, the
    compiler's copies); None without a trace, steps, a map or a block."""
    if "block_seconds" not in ctx:
        ctx["block_seconds"] = _block_seconds(ctx)
    return ctx["block_seconds"]


def _block_seconds(ctx):
    from perfbench.reduce import self_seconds
    trace, ops = ctx["trace"], scopes.step_op_map(ctx)
    dev = trace.busiest()
    if dev is None or ops is None:
        return None
    steps = trace.steps(dev)
    if not steps:
        return None
    inside, k = [], 0
    for name, start, end in sorted(trace.devices[dev], key=lambda o: o[1]):
        while k < len(steps) and steps[k][1] <= start:
            k += 1
        if k < len(steps) and steps[k][0] <= start:
            inside.append((name, start, end))
    out, other = {}, 0.0
    for name, seconds in self_seconds(inside).items():
        key = parse(ops.get(scopes.instruction(name)))
        if key is None and grouped_matmul(name):
            key = ("", "MoEFFN", "experts", "kernel")
        if key is None:
            other += seconds
        else:
            out[key] = out.get(key, 0.0) + seconds
    return (out, sum(e - s for s, e in steps), other) if out else None


def share(ctx, wanted):
    """Percent of the step programs' device time under the keys
    ``wanted(block, op, part, stage)`` accepts; None where nothing is."""
    found = block_seconds(ctx)
    if found is None or found[1] <= 0:
        return None
    hit = sum(s for key, s in found[0].items() if wanted(*key))
    return 100.0 * hit / found[1] if hit else None


def seconds(ctx, wanted):
    found = block_seconds(ctx)
    if found is None:
        return None
    return sum(s for key, s in found[0].items() if wanted(*key)) or None


def table(ctx):
    found = block_seconds(ctx)
    if found is None:
        return None
    by_key, step_s, other = found
    by_block, by_op, by_part = {}, {}, {}
    for (block, op, part, stage), s in by_key.items():
        by_block[block] = by_block.get(block, 0.0) + s
        name = f"{op or 'glue'}.{stage}"
        by_op[name] = by_op.get(name, 0.0) + s
        if part:
            name = f"{op}/{part}.{stage}"
            by_part[name] = by_part.get(name, 0.0) + s
    order = lambda d: dict(sorted(d.items(), key=lambda kv: -kv[1]))  # noqa: E731
    return {"step_device_s": step_s, "in_blocks_s": sum(by_key.values()),
            "outside_blocks_s": other, "steps": len(ctx["trace"].steps()),
            "by_block_s": order(by_block), "by_op_s": order(by_op),
            "by_part_s": order(by_part)}


def main(argv=None):
    import types
    from perfbench import run as harness
    argv = list(sys.argv[1:] if argv is None else argv)
    seen, load_reader = {}, harness.load_reader

    def keeping_ctx(metric):
        reader = load_reader(metric)

        def read(ctx):
            seen["ctx"] = ctx
            return reader.read(ctx)
        return types.SimpleNamespace(read=read)

    harness.load_reader = keeping_ctx
    try:
        harness.main(argv + ["--trace", "1"])
    finally:
        harness.load_reader = load_reader
    found = table(seen["ctx"]) if "ctx" in seen else None
    if found is not None:
        ctx = seen["ctx"]
        found["metrics"] = {name: load_reader(name).read(ctx)
                            for name in DECODER_METRICS}
        found["routed"] = routed_window(ctx)
        print("blocks " + json.dumps(found), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
