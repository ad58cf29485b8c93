#!/usr/bin/env python3
"""The benchmark's entry: one cell, one seed, one window, one JSON line.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data found by name from ``BENCHMARK.json``:
``perfbench/configs/<config>.json`` (sizes, source, env),
``perfbench/models/<config>.py`` (program, weights and batches from the seed,
operation count, plain reference), ``perfbench/traffic/<traffic>.json`` (feed,
batch, chips, mesh, env, driver), ``perfbench/drivers/<driver>.py`` (the
sequence set-up -> window -> comparison), ``perfbench/limits/<cell>.json``
(the limit of each number compared) and ``perfbench/metrics/<quantity>.py``
(one reader per per-layer quantity). Adding any of them is adding files and one
entry; nothing here names a cell.

One process; no accelerator or too few chips means a non-zero exit and no
result line. ``--rehearse`` is the CPU rehearsal: tiny sizes from the files'
``rehearse`` blocks, any platform, and a last line that says so and carries
no metric.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(kind, name):
    """``perfbench/<kind>/<name>.py`` as a module, whatever characters the
    name has."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{kind}_{name}".replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric):
    """A per-layer metric's reader. ``<quantity>.<variant>`` (the same
    quantity under the name that moves another end-to-end metric) is read by
    ``perfbench/metrics/<quantity>.py``."""
    return load_module("metrics", metric.split(".")[0])


def apply_env(cell):
    """The ``env`` maps of the cell's configuration and traffic, set before
    the program is imported."""
    for source in (cell["cfg"], cell["traffic_params"]):
        for key, value in source.get("env", {}).items():
            os.environ[key] = str(value)


def load_cell(name, rehearse=False):
    """Everything ``BENCHMARK.json`` and the cell's files say about it."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = dict(cells[name])
    configs = {c["name"]: c for c in bench["configs"]}
    with open(os.path.join(ROOT, configs[cell["config"]]["file"])) as f:
        cfg = json.load(f)
    traffic = load_json("traffic", cell["traffic"] + ".json")
    if traffic["chips"] != cell["chips"]:
        raise SystemExit(f"{name}: BENCHMARK.json says {cell['chips']} "
                         f"chips, its traffic file {traffic['chips']}")
    if rehearse:
        cfg.update(cfg.get("rehearse", {}))
        traffic.update(traffic.get("rehearse", {}))

    def listed(metric):
        cells_of = metric.get("workloads")
        return cells_of is None or name in cells_of

    end_to_end = [m for m in bench["end_to_end"] if listed(m)]
    reported = {m["name"] for m in end_to_end}
    # a per-layer metric with no list of its own is read in every cell that
    # reports the end-to-end metric it moves
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]

    limits = load_json("limits", name + ".json")
    # a rehearsal's sizes have readings of their own, so limits of their own
    limits = limits.get("rehearse", {}) if rehearse else \
        {k: v for k, v in limits.items() if k != "rehearse"}
    cell.update(
        cfg=cfg, traffic_params=traffic, bench=bench, limits=limits,
        end_to_end=end_to_end, per_layer=per_layer)
    return cell


def find_devices(chips, rehearse):
    """The chips the cell asks for, or a non-zero exit."""
    import jax
    devs = jax.devices()
    if rehearse:
        if len(devs) < chips:
            raise SystemExit(f"rehearsal needs {chips} devices, has "
                             f"{len(devs)} (XLA_FLAGS="
                             f"--xla_force_host_platform_device_count)")
        return devs
    if devs[0].platform == "cpu":
        raise SystemExit(f"no accelerator: jax.devices() is {devs}; the "
                         "benchmark does not time the CPU")
    if len(devs) < chips:
        raise SystemExit(f"the cell asks for {chips} chips, "
                         f"jax.devices() has {len(devs)}")
    return devs


def main(argv=None, devices=find_devices):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    cell = load_cell(args.workload, args.rehearse)
    apply_env(cell)
    devs = devices(cell["chips"], args.rehearse)
    driver = load_module("drivers", cell["traffic_params"]["driver"])
    line = driver.run(cell, args, devs, T_PROCESS)
    if args.rehearse:
        # a rehearsal is never a chip run: no metric, no device number
        line = {"rehearsal": "passed" if line["correct"] else "failed",
                "correct": line["correct"], "attempted": line["attempted"],
                "failed": line["failed"], "metrics": {},
                "device": {k: line["device"][k]
                           for k in ("platform", "kind", "count")},
                "checks": line["checks"]}
    checks = line.pop("checks")
    for name, c in checks.items():
        print(f"check {name} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    line["checks"] = checks
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
