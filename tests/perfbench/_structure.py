"""The benchmark's structural checks, each a function of a benchmark dict and
of the files it names, so that each runs over ``BENCHMARK.json`` as it
stands and over a copy with a configuration, a one-chip cell, a four-chip
cell and a per-layer metric appended (:func:`appended`): a later PR that
only appends entries and files passes every one of them."""
import copy
import json
import os
import re
from functools import reduce

from _pb import BENCH, PB, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LINE = re.compile(r"^[^\t\n]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

# PR 25's eleven, in their order, from this index of ``per_layer`` on
SPAN_RUN_AT = 9
FED = ["resnet50.train-fed"]
RATE = ["lstm-ptb-large.train-fed-seq128", "resnet50.train-resident",
        "resnet50.train-zero1-x4"]
SPAN_RUN = [
    ("input_fetch_busy_share", FED), ("input_slice_share", FED),
    ("input_h2d_share", FED), ("step_dispatch_ms", RATE),
    ("step_dispatch_ms.hostfed", FED),
    # only where the chip idles for 1% of the window or more: a share of
    # 8-11 ms of idle in 10 s is the offset's error
    ("idle_unattributed_share", RATE[2:]),
    ("idle_unattributed_share.hostfed", FED),
    ("conv_roofline", RATE[1:]), ("norm_act_share", RATE[1:]),
    ("lstm_backward_share", RATE[:1]), ("unscoped_share", RATE)]

LAGUNA = "laguna-xs2.train-fed-seq8k"
LFM2 = "lfm2-8b-a1b.train-fed-2x8k"
SDAR = "sdar-30b-a3b.train-fed-bd4-8k"
DECODERS = [LAGUNA, LFM2, SDAR]
# PR 37's twelve, appended after ``unscoped_share`` in this order: name,
# unit, better, source, layer, moves, workloads (None: no list)
TWELVE = [
    ("attention_share", "%", "lower", "device_trace", "kernels",
     "train_rate", DECODERS),
    ("moe_share", "%", "lower", "device_trace", "kernels", "train_rate",
     DECODERS),
    ("expert_matmul_roofline", "%", "higher", "device_trace", "kernels",
     "train_rate", DECODERS),
    ("moe_load_max_over_mean", "ratio", "lower", "program_counter",
     "kernels", "train_rate", DECODERS),
    ("window_attention_roofline", "%", "higher", "device_trace", "kernels",
     "train_rate", [LAGUNA]),
    ("short_conv_share", "%", "lower", "device_trace", "kernels",
     "train_rate", [LFM2]),
    ("short_conv_roofline", "%", "higher", "device_trace", "kernels",
     "train_rate", [LFM2]),
    ("block_diffusion_attention_roofline", "%", "higher", "device_trace",
     "kernels", "train_rate", [SDAR]),
    ("setup_compile_s", "s", "lower", "program_span",
     "step runtime and compile", "setup_s", None),
    ("setup_bind_s", "s", "lower", "program_span",
     "step runtime and compile", "setup_s", None),
    ("setup_input_s", "s", "lower", "program_span", "input pipeline",
     "setup_s", None),
    ("setup_unattributed_share", "%", "lower", "program_span",
     "whole set-up", "setup_s", None)]
# what every decoder cell reads beside its own entries of the twelve
DECODER_BASE = {"compiles_in_window", "device_idle_share", "mfu_step"}


class Files:
    """The checkout's files, under an overlay of files that exist only in
    memory (``{relative path: text or JSON value}``)."""

    def __init__(self, overlay=None):
        self.overlay = dict(overlay or {})

    def exists(self, rel):
        return rel in self.overlay or os.path.isfile(os.path.join(ROOT, rel))

    def text(self, rel):
        if rel in self.overlay:
            body = self.overlay[rel]
            return body if isinstance(body, str) else json.dumps(body)
        with open(os.path.join(ROOT, rel)) as f:
            return f.read()

    def json(self, rel):
        return json.loads(self.text(rel))


DISK = Files()


def pb(*parts):
    return os.path.relpath(os.path.join(PB, *parts), ROOT)


def cell_metrics(bench, name):
    """``(end-to-end names, per-layer names)`` a cell reports: a metric
    with a list where the list names the cell; a per-layer metric with none
    wherever the end-to-end metric it moves is reported (the rule of
    ``perfbench/run.py``'s ``load_cell``, written out again)."""
    end_to_end = {m["name"] for m in bench["end_to_end"]
                  if name in m.get("workloads", [name])}
    per_layer = {m["name"] for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in end_to_end)}
    return end_to_end, per_layer


def mesh_size(traffic):
    return reduce(lambda a, b: a * b, traffic.get("mesh", {}).values(), 1)


# -- checks of one entry ------------------------------------------------------

def check_config(bench, files, config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"]) and LINE.match(config["source"])
    assert LINE.match(config["why"])
    assert any(config["file"].startswith(p.rstrip("/") + "/")
               for p in bench["paths"])
    body = files.json(config["file"])
    assert body["source"] == config["source"]
    assert body["reduced"] == config["reduced"] and "assumed" in body
    assert len(config["reduced"]) <= 16
    assert all(NAME.match(key) for key in config["reduced"])
    assert files.exists(pb("models", config["name"] + ".py"))
    assert any(w["config"] == config["name"] for w in bench["workloads"])
    assert [c["file"] for c in bench["configs"]].count(config["file"]) == 1


def check_cell(bench, files, cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    for key in ("name", "config", "traffic"):
        assert NAME.match(cell[key])
    assert cell["chips"] in (1, 4) and LINE.match(cell["why"])
    assert cell["config"] in {c["name"] for c in bench["configs"]}
    traffic = files.json(pb("traffic", cell["traffic"] + ".json"))
    assert traffic["chips"] == cell["chips"]
    # a mesh, where the traffic names one, spans the cell's chips
    assert "mesh" not in traffic or mesh_size(traffic) == cell["chips"]
    assert files.exists(pb("drivers", traffic["driver"] + ".py"))
    limits = files.json(pb("limits", cell["name"] + ".json"))
    assert limits and all(isinstance(v, float) for k, v in limits.items()
                          if k != "rehearse")


def check_cell_reports(bench, files, cell):
    """``setup_s``, one more end-to-end metric, and a per-layer one."""
    end_to_end, per_layer = cell_metrics(bench, cell["name"])
    assert "setup_s" in end_to_end and len(end_to_end) >= 2, end_to_end
    assert per_layer


def check_metric(bench, files, metric):
    end_to_end = metric in bench["end_to_end"]
    allowed = {"name", "unit", "better", "source", "workloads"} | (
        {"bound"} if end_to_end else {"layer", "moves"})
    assert set(metric) <= allowed
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    cells = [w["name"] for w in bench["workloads"]]
    for cell in metric.get("workloads", []):
        assert cell in cells
    if end_to_end:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
        return
    assert LINE.match(metric["layer"])
    assert metric["moves"] in {m["name"] for m in bench["end_to_end"]}
    # every cell it lists reports the end-to-end metric it moves
    for cell in metric.get("workloads", []):
        assert metric["moves"] in cell_metrics(bench, cell)[0], (
            metric["name"], cell)
    reader = pb("metrics", metric["name"].split(".")[0] + ".py")
    assert files.exists(reader)
    assert "def read(ctx)" in files.text(reader)


# -- checks of the whole benchmark ------------------------------------------

def check_top_level(bench, files):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(bench["command"]) <= 32
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    for path in bench["paths"]:
        assert re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", path)
        assert os.path.isdir(os.path.join(ROOT, path))
    assert len(json.dumps(bench, indent=2)) <= 65536
    assert 1 <= len(bench["configs"]) <= 24
    assert 1 <= len(bench["workloads"]) <= 24
    assert 1 <= len(bench["end_to_end"]) <= 16
    assert 1 <= len(bench["per_layer"]) <= 128
    # a full check with the full 24 cells fits the driver's budget
    runs = 2 + 14 * 24
    assert runs * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def check_configs(bench, files):
    for config in bench["configs"]:
        check_config(bench, files, config)


def check_cells(bench, files):
    for cell in bench["workloads"]:
        check_cell(bench, files, cell)
        check_cell_reports(bench, files, cell)
    cells = [w["name"] for w in bench["workloads"]]
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs) and len(set(cells)) == len(cells)


def check_four_chip_cells(bench, files):
    """At most a quarter of the cells (one always may) take four chips, and
    each of them works all four: its traffic says 4 chips and its mesh's
    axes multiply to 4."""
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
    for cell in four:
        traffic = files.json(pb("traffic", cell["traffic"] + ".json"))
        assert traffic["chips"] == 4, cell["name"]
        assert "mesh" in traffic and mesh_size(traffic) == 4, cell["name"]


def check_metrics(bench, files):
    for metric in bench["end_to_end"] + bench["per_layer"]:
        check_metric(bench, files, metric)
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(set(names)) == len(names)
    assert "setup_s" in names and "train_rate" in names
    assert any("mfu" in m["name"] for m in bench["per_layer"])


def check_span_run(bench, files):
    """PR 25's eleven stand as one run, in their order, where they stood,
    with their lists and what they move; what is appended after them is
    a later PR's."""
    at, names = SPAN_RUN_AT, [n for n, _ in SPAN_RUN]
    assert [m["name"] for m in bench["per_layer"][at:at + len(names)]] \
        == names
    for metric, (name, cells) in zip(bench["per_layer"][at:], SPAN_RUN):
        assert metric["workloads"] == cells, name
        moves = "train_rate_hostfed" if cells == FED else "train_rate"
        assert metric["moves"] == moves, name


def check_twelve(bench, files):
    """PR 37's twelve entries, as its table has them, right after
    ``unscoped_share`` and in its order."""
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("unscoped_share") + 1
    assert names[at:at + len(TWELVE)] == [row[0] for row in TWELVE]
    for metric, row in zip(bench["per_layer"][at:], TWELVE):
        name, unit, better, source, layer, moves, cells = row
        want = {"name": name, "unit": unit, "better": better,
                "source": source, "layer": layer, "moves": moves}
        if cells is not None:
            want["workloads"] = cells
        assert metric == want, name


def check_decoder_cells(bench, files):
    """Each decoder cell reads the three metrics with no list that move its
    rate, the four ``setup_*`` ones, and its own entries of the twelve."""
    for cell in DECODERS:
        own = {row[0] for row in TWELVE
               if row[6] is None or cell in row[6]}
        assert DECODER_BASE | own <= cell_metrics(bench, cell)[1], cell


STRUCTURAL = [check_top_level, check_configs, check_cells,
              check_four_chip_cells, check_metrics, check_span_run,
              check_twelve, check_decoder_cells]


# -- a copy with what a later model_config PR appends ------------------------

NEW_CONFIG = "hybrid-ssm"
NEW_ONE_CHIP = "hybrid-ssm.train-fed-8k"
NEW_FOUR_CHIP = "hybrid-ssm.train-fed-tp4"
NEW_FOUR_CHIP_TRAFFIC = "train-fed-8k-tp4"
NEW_METRIC = "ssm_scan_roofline"


def appended(bench=BENCH, tag=""):
    """``(copy, files)``: ``bench`` with a configuration, a one-chip cell, a
    four-chip cell on the mesh ``{"model": 4}`` and a per-layer metric that
    lists the two appended, and the files they name, in memory only. Where
    ``bench`` already has all the four-chip cells its count allows, one-chip
    cells of the new configuration go in before the four-chip one, as its
    PR would have to add them. ``tag`` ends every name, for a second copy
    appended to the first."""
    bench = copy.deepcopy(bench)
    config, one, four_chip = (NEW_CONFIG + tag, NEW_ONE_CHIP + tag,
                              NEW_FOUR_CHIP + tag)
    source = f"https://example.org/hybrid-ssm{tag}/config.json"
    bench["configs"].append(
        {"name": config, "source": source,
         "file": f"perfbench/configs/{config}.json",
         "reduced": ["num_hidden_layers", "vocab_size"],
         "why": "state-space heads beside attention in every layer"})
    four = sum(w["chips"] == 4 for w in bench["workloads"]) + 1
    cells = [(one, f"train-fed-8k-x1{tag}", 1, {"data": 1})]
    while four > max(1, (len(bench["workloads"]) + len(cells) + 1) // 4):
        k = len(cells)
        cells.append((f"{one}-{k}", f"train-fed-8k-x1{tag}-{k}", 1,
                       {"data": 1}))
    cells.append((four_chip, NEW_FOUR_CHIP_TRAFFIC + tag, 4, {"model": 4}))
    overlay = {
        pb("configs", config + ".json"): {
            "source": source, "reduced": ["num_hidden_layers",
                                          "vocab_size"],
            "assumed": {}, "hidden_size": 64, "num_hidden_layers": 2},
        pb("models", config + ".py"): "def reference(*a): pass\n",
        pb("metrics", NEW_METRIC + tag + ".py"):
            "def read(ctx):\n    return None\n"}
    rate = next(m for m in bench["end_to_end"] if m["name"] == "train_rate")
    for name, traffic, chips, mesh in cells:
        bench["workloads"].append(
            {"name": name, "config": config, "traffic": traffic,
             "chips": chips, "why": f"8k documents on {chips} chip(s)"})
        overlay[pb("traffic", traffic + ".json")] = {
            "driver": "train_fit", "chips": chips, "mesh": mesh, "env": {}}
        overlay[pb("limits", name + ".json")] = {"loss_gap": 0.01}
        rate["workloads"].append(name)
    bench["per_layer"].append(
        {"name": NEW_METRIC + tag, "unit": "%", "better": "higher",
         "source": "device_trace", "layer": "kernels", "moves": "train_rate",
         "workloads": [one, four_chip]})
    return bench, Files(overlay)
