"""The training driver: build -> checked first steps -> window through
``fit`` -> free -> reference -> comparison -> the result line.

One object (the model file's ``Program``: the compiled step with its state)
is built in set-up, driven from the seed through its first steps by the
window's own call and feed, and handed to the window. Set-up ends where the
window starts; the reference runs after the window has closed, the peak has
been read and the program's state is freed, and is not part of ``setup_s``.
"""
import gc
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.dirname(HERE) not in sys.path:
    sys.path.insert(0, os.path.dirname(HERE))

from perfbench import compare  # noqa: E402
from perfbench import feed as feed_mod  # noqa: E402
from perfbench import reduce as reduce_mod  # noqa: E402
from perfbench import run as harness  # noqa: E402


class CompileCounter:
    """Backend compilations JAX reports, counted since it was made."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, seconds, **_):
        if event == self.EVENT:
            self.count += 1


def checked_steps(program, window, batches, steps):
    """Drive the first batches through the window's own call and feed;
    return what the comparison reads of the program."""
    record = {"losses": [], "grad_norms": None, "delta_norms": None}

    def on_batch_end(param):
        k = param.nbatch
        if k < steps:
            record["losses"].append(program.step_loss(param, batches[k][1]))
        if k == 0:
            record["grad_norms"] = program.grad_norms()
        if k == steps - 1:
            record["delta_norms"] = program.delta_norms()

    # one more batch than is checked: the step after the last checked one
    # runs on donated state, as every step of the window does
    window.arm(batches=steps + 1)
    program.fit(window, on_batch_end)
    program.sync()
    return record


def device_record(devices, chips):
    """The device as JAX reports it. ``memory_peak_bytes`` is the fullest
    chip's peak of what the allocator held (``peak_bytes_in_use``) plus what
    the runtime reserved for the loaded programs' temporaries
    (``peak_bytes_reserved``), which the former does not count: read after
    the window and before the reference."""
    stats = [d.memory_stats() or {} for d in devices[:chips]]
    peak = max((s.get("peak_bytes_in_use") or 0)
               + (s.get("peak_bytes_reserved") or 0) for s in stats)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": chips, "memory_peak_bytes": int(peak),
            "memory_stats": stats[0]}


def run(cell, args, devices, t_process):
    import jax

    cfg, traffic = cell["cfg"], cell["traffic_params"]
    chips = cell["chips"]
    model = harness.load_module("models", cell["config"])
    end_to_end_values(cell, 0.0, 0.0)       # refuse a foreign metric early
    compiles = CompileCounter()

    marks = {"import_s": time.perf_counter() - t_process}
    program = model.Program(cfg, traffic, args.seed, devices)
    marks["build_s"] = time.perf_counter() - t_process
    batches = model.make_batches(cfg, traffic, args.seed)
    window = feed_mod.Window(feed_mod.inner_iterator(
        traffic, batches, program.input_shardings(), program.input_names))
    marks["feed_s"] = time.perf_counter() - t_process
    steps = traffic["check_steps"]
    record = checked_steps(program, window, batches, steps)
    marks["checked_steps_s"] = time.perf_counter() - t_process
    del batches
    gc.collect()

    # -- the window --------------------------------------------------------
    counters_before = dict(program.counters(), compiles=compiles.count)
    trace_dir = None
    clock = {}

    def start_trace():
        if args.trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        clock["t0"] = time.perf_counter()

    if args.trace:
        # under TMPDIR or the checkout's .cache, emptied before and after
        trace_dir = os.path.abspath(os.path.join(
            os.environ.get("TMPDIR") or os.path.join(HERE, os.pardir,
                                                     ".cache"),
            "perfbench-trace", cell["name"]))
        shutil.rmtree(trace_dir, ignore_errors=True)
    window.arm(seconds=args.seconds, on_first=start_trace)
    setup_s = time.perf_counter() - t_process
    program.fit(window)
    program.sync()
    t1 = time.perf_counter()
    if args.trace:
        jax.profiler.stop_trace()
    window_s = t1 - clock["t0"]
    items = window.count * model.items_per_batch(cfg, traffic)
    train_rate = items / window_s
    counters_after = dict(program.counters(), compiles=compiles.count)
    device = device_record(devices, chips)

    # -- free the program, then the reference --------------------------------
    feed_stats = {"wait_s": window.wait_s, "batches": window.count,
                  "window_s": window_s, "calls": window.calls}
    program.close()
    del program, window
    gc.collect()
    jax.clear_caches()
    t_ref = time.perf_counter()
    ref = model.reference(cfg, traffic, args.seed, devices=devices)
    numbers, where = compare.gaps(record, ref)
    ok, checks = compare.judge(numbers, cell["limits"])
    reference_s = time.perf_counter() - t_ref

    values = end_to_end_values(cell, setup_s, train_rate)
    line = {"correct": bool(ok), "attempted": feed_stats["batches"],
            "failed": 0}
    if args.trace:
        trace = reduce_mod.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        busy = trace.busy_seconds()
        device["busy_s"] = sum(busy.values()) / max(len(busy), 1)
        device["window_s"] = window_s
        ctx = {"trace": trace, "feed": feed_stats, "cell": cell,
               "cfg": cfg, "traffic": traffic, "model": model,
               "peaks": cell_peaks(device["kind"], args.rehearse),
               "chips": chips, "train_rate": train_rate,
               "window_s": window_s,
               # set-up's start for the setup_* readers: under run.py this
               # module's ``harness`` is a second import, stamped later
               "t_process": t_process,
               "counters": {k: counters_after[k] - counters_before[k]
                            for k in counters_after}}
        line["metrics"] = per_layer_metrics(cell, ctx)
        line["breakdown"] = breakdown(trace, feed_stats["calls"])
    else:
        line["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell["end_to_end"]}
    line["device"] = device
    line["reference_s"] = reference_s
    line["setup_marks"] = marks
    line["diagnostics"] = numbers
    line["where"] = where
    line["checks"] = checks
    return line


def end_to_end_values(cell, setup_s, train_rate):
    """What this driver measures: the set-up, and the window's rate under
    whichever items/s name BENCHMARK.json lists for the cell (train_rate;
    train_rate_hostfed where the host's feed bounds it)."""
    values = {}
    for m in cell["end_to_end"]:
        if m["name"] == "setup_s":
            values[m["name"]] = setup_s
        elif m["unit"] == "items/s":
            values[m["name"]] = train_rate
        else:
            raise SystemExit(f"{cell['name']}: the training driver does not "
                             f"measure {m['name']!r} ({m['unit']})")
    return values


def per_layer_metrics(cell, ctx):
    """Every per-layer metric of the cell whose reader finds something."""
    metrics = {}
    for m in cell["per_layer"]:
        value = harness.load_reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


def breakdown(trace, host_calls, top=10):
    """The device operations with most self time on the busiest chip, and
    its longest idle gaps by what the host was doing."""
    ops = sorted(trace.seconds_by_name(trace.busiest()).items(),
                 key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[reduce_mod.short_name(n), s] for n, s in ops],
            "idle_gaps": [[n, s]
                          for n, s in trace.idle_gaps(top, host_calls)]}


def cell_peaks(kind, rehearse=False):
    import json
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    if rehearse:        # nothing a rehearsal computes from it is printed
        return next(iter(peaks.values()))
    if kind not in peaks:
        raise SystemExit(f"no published peak for device kind {kind!r} in "
                         f"perfbench/peaks.json (known: {sorted(peaks)})")
    return peaks[kind]
