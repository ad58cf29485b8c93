"""The harness end to end at a tiny size (``--rehearse``: interpreted
kernels, any platform), and its refusal to time the CPU."""
import json
import subprocess
import sys

import pytest

from _pb import BENCH, ROOT, cpu_env

RUN = [sys.executable, "perfbench/run.py"]


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_runs_the_cell_end_to_end(cell, trace):
    if trace and not cell["traffic"].startswith("train-fed"):
        pytest.skip("the traced path is the same code; the fed cells suffice")
    done = subprocess.run(
        RUN + ["--workload", cell["name"], "--seed", "2147483659",
               "--seconds", "1", "--trace", str(trace), "--rehearse"],
        cwd=ROOT, env=cpu_env(cell["chips"]), capture_output=True,
        text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] == "passed" and line["correct"] is True
    assert line["metrics"] == {} and line["attempted"] > 0
    assert "memory_peak_bytes" not in line["device"]
    assert list(line)[-1] == "checks"
    for name, check in line["checks"].items():
        assert f"check {name} = " in done.stderr
        assert check["value"] <= check["limit"]


def test_without_a_chip_there_is_no_result():
    done = subprocess.run(
        RUN + ["--workload", BENCH["workloads"][0]["name"], "--seed", "1",
               "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=cpu_env(), capture_output=True, text=True, timeout=300)
    assert done.returncode != 0
    assert "no accelerator" in done.stderr
    assert not any(l.startswith("{") for l in done.stdout.splitlines())


def test_unknown_cell_is_refused():
    done = subprocess.run(
        RUN + ["--workload", "no-such-cell", "--rehearse"], cwd=ROOT,
        env=cpu_env(), capture_output=True, text=True, timeout=300)
    assert done.returncode != 0 and "unknown workload" in done.stderr
