"""BENCHMARK.json against the contract's limits, and every file a cell, a
configuration, a traffic mix, a driver or a per-layer metric needs is found
by its name: adding one is adding files and an entry."""
import json
import os
import re

import pytest

from _pb import BENCH, CELLS, METRICS, PB, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LINE = re.compile(r"^[^\t\n]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(BENCH["command"]) <= 32
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    for path in BENCH["paths"]:
        assert re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", path)
        assert os.path.isdir(os.path.join(ROOT, path))
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536
    # a full check with the full 24 cells fits the driver's budget
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


@pytest.mark.parametrize("config", BENCH["configs"],
                         ids=lambda c: c["name"])
def test_config_entry_and_files(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"]) and LINE.match(config["source"])
    assert LINE.match(config["why"])
    assert any(config["file"].startswith(p.rstrip("/") + "/")
               for p in BENCH["paths"])
    with open(os.path.join(ROOT, config["file"])) as f:
        body = json.load(f)
    assert body["source"] == config["source"]
    assert body["reduced"] == config["reduced"] and "assumed" in body
    assert len(config["reduced"]) <= 16
    assert os.path.isfile(os.path.join(PB, "models", config["name"] + ".py"))
    assert any(w["config"] == config["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_entry_and_files(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    for key in ("name", "config", "traffic"):
        assert NAME.match(cell[key])
    assert cell["chips"] in (1, 4) and LINE.match(cell["why"])
    assert cell["config"] in {c["name"] for c in BENCH["configs"]}
    with open(os.path.join(PB, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    assert traffic["chips"] == cell["chips"]
    assert os.path.isfile(os.path.join(PB, "drivers",
                                       traffic["driver"] + ".py"))
    with open(os.path.join(PB, "limits", cell["name"] + ".json")) as f:
        limits = json.load(f)
    assert limits and all(isinstance(v, float) for k, v in limits.items()
                          if k != "rehearse")


def test_cells_are_distinct_and_few_take_four_chips():
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs) and len(set(CELLS)) == len(CELLS)
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 4)
    assert all(w["name"] == "resnet50.train-zero1-x4" for w in four)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    end_to_end = metric in BENCH["end_to_end"]
    allowed = {"name", "unit", "better", "source", "workloads"} | (
        {"bound"} if end_to_end else {"layer", "moves"})
    assert set(metric) <= allowed
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    for cell in metric.get("workloads", []):
        assert cell in CELLS
    if end_to_end:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert LINE.match(metric["layer"])
        assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
        reader = os.path.join(PB, "metrics",
                              metric["name"].split(".")[0] + ".py")
        assert os.path.isfile(reader)
        with open(reader) as f:
            assert "def read(ctx)" in f.read()


def test_metric_names_unique_and_setup_present():
    names = [m["name"] for m in METRICS]
    assert len(set(names)) == len(names)
    assert "setup_s" in names and "train_rate" in names
    assert any("mfu" in m["name"] for m in BENCH["per_layer"])


def test_benchmark_imports_nothing_of_the_repos_other_benchmarks():
    for base, _, files in os.walk(PB):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(base, name)) as f:
                    text = f.read()
                assert not re.search(
                    r"^\s*(from|import)\s+(bench|benchmarks|chip_smoke)\b",
                    text, re.M), name
