#!/usr/bin/env python3
"""Readings that the limits in ``perfbench/limits/<cell>.json`` are set from.

    python3 perfbench/calibrate.py --workload <cell> --seeds 12 --controls 3

One process on the chip, at the cell's own size. For each seed: the program's
checked first steps against the reference (the lower reading is the largest
over the seeds). For the first ``--controls`` seeds also the control (the
reference computed in ``--control-precision``, put in the program's place)
and each planted fault (the reference with the fault, in the program's
place): the upper reading is the smallest of each. One JSON line per
reading, a summary line last; every reading is also judged against the cell's
committed limits (``correct``). Not run by the benchmark's own runs.

    python3 perfbench/calibrate.py --workload <cell> --rejudge <readings.jsonl>

judges readings that an earlier call printed against the limits as they are
committed now (plain arithmetic, any machine): every ``program`` line has to
come out correct, every control and planted fault not.
"""
import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from perfbench import run as harness  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2_200_000_001)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--control-precision", default="fp8")
    ap.add_argument("--faults", default="half_batch")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--rejudge", metavar="JSONL")
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload, args.rehearse)
    if args.rejudge:
        return rejudge(cell, args.rejudge)
    cfg, traffic = cell["cfg"], cell["traffic_params"]
    harness.apply_env(cell)
    devices = harness.find_devices(cell["chips"], args.rehearse)
    import jax
    from perfbench import compare
    from perfbench import feed as feed_mod
    driver = harness.load_module("drivers", traffic["driver"])
    model = harness.load_module("models", cell["config"])

    summary = {}

    def note(kind, seed, numbers, **extra):
        correct, _ = compare.judge(numbers, cell["limits"])
        print(json.dumps({"kind": kind, "seed": seed, **numbers,
                          "correct": correct, **extra}), flush=True)
        for k, v in numbers.items():
            summary.setdefault(kind, {}).setdefault(k, []).append(v)

    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        program = model.Program(cfg, traffic, seed, devices)
        batches = model.make_batches(cfg, traffic, seed)
        window = feed_mod.Window(feed_mod.inner_iterator(
            traffic, batches, program.input_shardings(),
            program.input_names))
        record = driver.checked_steps(program, window, batches,
                                      traffic["check_steps"])
        program.close()
        del program, window, batches
        gc.collect()
        ref = model.reference(cfg, traffic, seed, devices=devices)
        numbers, where = compare.gaps(record, ref)
        note("program", seed, numbers, where=where,
             losses=record["losses"], ref_losses=ref["losses"])
        if i < args.controls:
            ctl = model.reference(cfg, traffic, seed,
                                  precision=args.control_precision,
                                  devices=devices)
            note("control_" + args.control_precision, seed,
                 compare.gaps(ctl, ref)[0])
            for fault in filter(None, args.faults.split(",")):
                bad = model.reference(cfg, traffic, seed, fault=fault,
                                      devices=devices)
                note("fault_" + fault, seed, compare.gaps(bad, ref)[0])
    print(json.dumps({"summary": {
        kind: {k: {"min": min(v), "max": max(v), "n": len(v)}
               for k, v in nums.items()}
        for kind, nums in summary.items()},
        "device": jax.devices()[0].device_kind}), flush=True)


def rejudge(cell, path):
    """Judge recorded readings against the cell's limits; exit non-zero if
    a sound run fails or a control or fault passes."""
    from perfbench import compare
    wrong = 0
    with open(path) as f:
        for text in f:
            if not text.startswith("{") or '"kind"' not in text:
                continue
            row = json.loads(text)
            correct, checks = compare.judge(row, cell["limits"])
            wanted = row["kind"] == "program"
            wrong += correct != wanted
            failed = [k for k, c in checks.items() if c["value"] > c["limit"]]
            print(f"{row['kind']} seed {row['seed']}: correct={correct}"
                  f" (wanted {wanted}) fails {failed}")
    print(f"limits {cell['limits']}: {wrong} readings judged against what "
          "they should be")
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
