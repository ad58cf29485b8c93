"""From a profiler trace (``.xplane.pb``) to the few things metrics read.

``jax.profiler.ProfileData`` only: planes, lines, events with a start and a
duration in nanoseconds. A device plane is ``/device:TPU:<n>``; its
operations are the events of the line ``XLA Ops`` (named by the whole HLO
instruction), its programs the events of ``XLA Modules`` (``jit_step(...)``
once a step). The host tracer is off in the benchmark's traced runs (on a
fed cell its events, one per tile the transfer transposes, made a 1.2 GB
trace): what the host was doing comes from the iterator wrapper's own clock,
matched to the device's steps by their order (:func:`label_gaps`).

:func:`load` returns a :class:`Trace`; the arithmetic (interval union, sums
by name, gaps) is in plain functions over lists so a test can check it by
hand.
"""
import glob
import os

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"


def short_name(name, width=96):
    """An HLO instruction's text cut to its name, opcode and result type."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:width]
    result, _, call = rest.partition(") ") if rest.startswith("(") \
        else rest.partition(" ")
    opcode = call.split("(", 1)[0].strip()
    return f"{head} {opcode} {result.lstrip('(')}"[:width]


def union_seconds(intervals):
    """Length of the union of ``(start, end)`` intervals."""
    total, reach = 0.0, None
    for start, end in sorted(intervals):
        if reach is None or start > reach:
            total += end - start
            reach = end
        elif end > reach:
            total += end - reach
            reach = end
    return total


def gaps(intervals, lo, hi):
    """The idle gaps ``(start, end)`` that the union of ``intervals`` leaves
    inside ``[lo, hi]``, longest first."""
    out, reach = [], lo
    for start, end in sorted(intervals):
        if start > reach:
            out.append((reach, min(start, hi)))
        reach = max(reach, end)
        if reach >= hi:
            break
    if reach < hi:
        out.append((reach, hi))
    return sorted((g for g in out if g[1] > g[0]),
                  key=lambda g: g[0] - g[1])


def self_seconds(ops):
    """name -> summed self time of ``(name, start, end)`` operations on one
    line: an operation that encloses others (a loop around its body) is
    charged only what its children leave."""
    out, stack = {}, []      # stack of [name, end, child_time, start]

    def close(item):
        name, end, kids, start = item
        out[name] = out.get(name, 0.0) + max(end - start - kids, 0.0)
        if stack:
            stack[-1][2] += end - start

    for name, start, end in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= start:
            close(stack.pop())
        stack.append([name, end, 0.0, start])
    while stack:
        close(stack.pop())
    return out


class Trace:
    """Per device plane, lists of ``(name, start_s, end_s)``: ``devices`` the
    operations, ``modules`` the programs, ``async_ops`` the asynchronous
    operations (copies, collectives) that run beside them."""

    def __init__(self, devices, modules=None, async_ops=None):
        self.devices = devices
        self.modules = modules or {}
        self.async_ops = async_ops or {}

    def steps(self, device=None):
        """``(start, end)`` of every run of the program that took most time
        on one device plane: the training step, once a batch, in order."""
        dev = device or self.busiest()
        runs = self.modules.get(dev) or []
        total = {}
        for name, s, e in runs:
            total[name] = total.get(name, 0.0) + (e - s)
        if not total:
            return []
        step = max(total, key=total.get)
        return sorted((s, e) for name, s, e in runs if name == step)

    def window(self):
        """(first start, last end) over every device operation."""
        starts = [e[1] for ops in self.devices.values() for e in ops]
        ends = [e[2] for ops in self.devices.values() for e in ops]
        return (min(starts), max(ends)) if starts else (0.0, 0.0)

    def busy_seconds(self):
        """Union of operation intervals per device, plane name -> seconds."""
        return {d: union_seconds([(s, e) for _, s, e in ops])
                for d, ops in self.devices.items()}

    def seconds_by_name(self, device=None):
        """name -> summed self time on one device plane (default: the
        first), under the names the trace gives."""
        if not self.devices:
            return {}
        return self_seconds(self.devices[device or sorted(self.devices)[0]])

    def seconds_matching(self, match, device=None):
        """Summed duration and count of operations whose name ``match``
        accepts, on one device plane."""
        if not self.devices:
            return 0.0, 0
        ops = self.devices[device or sorted(self.devices)[0]]
        hit = [e - s for name, s, e in ops if match(name)]
        return sum(hit), len(hit)

    def busiest(self):
        busy = self.busy_seconds()
        return max(busy, key=busy.get) if busy else None

    def idle_gaps(self, top=10, host_calls=None):
        """Longest idle gaps of the busiest device inside its own window,
        each labelled by :func:`label_gaps`."""
        dev = self.busiest()
        if dev is None:
            return []
        ops = [(s, e) for _, s, e in self.devices[dev]]
        lo, hi = min(s for s, _ in ops), max(e for _, e in ops)
        return label_gaps(gaps(ops, lo, hi)[:top], self.steps(dev),
                          host_calls or [])


IN_NEXT = "between steps: fit loop in next() of the iterator (input pipeline)"
IN_BODY = "between steps: fit loop in its body (step call, callbacks)"
IN_STEP = "inside a step program (device waits on its own copies)"
UNKNOWN = "between steps: outside the window's record"


def label_gaps(found, steps, host_calls):
    """Label idle gaps ``(start, end)`` of a device. A gap inside a step's
    program is the device's own; a gap before step k is the host being late
    with it, and the wrapper's clock says where: ``host_calls[k]`` is
    ``(start, end)`` of the k-th ``next()``, and the body of ``fit`` ran
    between one call's end and the next one's start. The device's k-th step
    is the window's k-th batch, by order."""
    out = []
    for g0, g1 in found:
        label = UNKNOWN
        for k, (s, e) in enumerate(steps):
            if s <= g0 and g1 <= e:
                label = IN_STEP
                break
            if g1 <= s + 1e-9:          # the first step that starts after it
                if 0 < k < len(host_calls):
                    in_next = host_calls[k][1] - host_calls[k][0]
                    in_body = host_calls[k][0] - host_calls[k - 1][1]
                    label = IN_NEXT if in_next >= in_body else IN_BODY
                break
        out.append((label, g1 - g0))
    return out


def is_device_plane(name):
    return name.startswith("/device:TPU:") and "SparseCore" not in name


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path, device_plane=is_device_plane):
    """Read one ``.xplane.pb`` (or the newest under a trace directory)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    data = ProfileData.from_file(path)
    devices, modules, async_ops = {}, {}, {}
    lines = {OPS_LINE: devices, MODULES_LINE: modules, ASYNC_LINE: async_ops}
    for plane in data.planes:
        if device_plane(plane.name):
            for line in plane.lines:
                if line.name not in lines:
                    continue
                into = lines[line.name]
                into[plane.name] = [
                    (ev.name, ev.start_ns * 1e-9,
                     (ev.start_ns + ev.duration_ns) * 1e-9)
                    for ev in line.events]
    return Trace(devices, modules, async_ops)


def describe(path):
    """Planes, lines and the commonest event names: what to read by hand
    before writing a metric against a trace."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            names, n, first = {}, 0, None
            for ev in line.events:
                n += 1
                names[ev.name] = names.get(ev.name, 0.0) + ev.duration_ns
                if first is None:
                    first = {"name": ev.name, "start_ns": ev.start_ns,
                             "duration_ns": ev.duration_ns,
                             "stats": [(k, str(v)[:60])
                                       for k, v in list(ev.stats)[:12]]}
            top = sorted(names.items(), key=lambda kv: -kv[1])[:15]
            lines.append({"line": line.name, "events": n, "first": first,
                          "top_ns": top})
        out.append({"plane": plane.name, "lines": lines})
    return out


if __name__ == "__main__":
    import json
    import sys
    print(json.dumps(describe(sys.argv[1]), indent=1))
