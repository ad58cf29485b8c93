"""bench.py contract: needs a chip, one JSON line, names its device.

``python bench.py`` prints ONE json line as the last line of stdout and
exits non-zero without an accelerator. These tests pin that on the CPU:
the refusal in a real subprocess, and the one-line record in-process on
a smoke config (BENCH_BATCH/BENCH_ITERS overridden -> the nested tiers
are skipped by design) with the chip check answered by the test.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

V5E = {"platform": "tpu", "device_kind": "TPU v5 lite", "count": 1}


def test_bench_refuses_to_time_the_cpu(tmp_path):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["BENCH_BATCH"] = "4"
    env["BENCH_ITERS"] = "2"
    res = subprocess.run([sys.executable, os.path.join(ROOT, "bench.py")],
                         capture_output=True, text=True, timeout=300,
                         cwd=str(tmp_path), env=env)
    assert res.returncode != 0, res.stdout[-2000:]
    assert "no accelerator" in res.stderr
    assert "resnet50_train_throughput" not in res.stdout


def test_bench_smoke_emits_one_json_line(monkeypatch, capsys):
    import _device
    import bench
    monkeypatch.setattr(_device, "require_chip", lambda: dict(V5E))
    monkeypatch.setenv("BENCH_BATCH", "4")
    monkeypatch.setenv("BENCH_ITERS", "2")
    bench.main()
    line = capsys.readouterr().out.strip().splitlines()[-1]
    rec = json.loads(line)
    assert rec["metric"] == "resnet50_train_throughput"
    assert rec["unit"] == "images/sec/chip"
    assert rec["device"] == V5E
    assert rec["value"] > 0
    assert rec["vs_baseline"] > 0
    assert rec["mfu"] == round(rec["effective_tflops"] / 197.0, 3)
    # smoke config: the ResNet record alone
    for nested in ("lstm_train_tokens_per_sec", "flash_attention",
                   "moe_dispatch", "multichip", "serving"):
        assert nested not in rec


def test_peak_comes_from_a_table_keyed_by_device_kind():
    import _device
    assert _device.peak_bf16_tflops("TPU v5 lite") == 197.0
    assert _device.PEAKS["TPU v5 lite"]["source"]
    with pytest.raises(KeyError, match="no published peak"):
        _device.peak_bf16_tflops("cpu")
    with pytest.raises(SystemExit, match="no accelerator"):
        _device.require_chip()      # the test process is on the CPU


def test_multichip_record_says_when_it_cannot_run():
    import bench
    rec = bench.bench_multichip(dict(V5E))
    assert "needs >= 2 devices" in rec["skipped"]
