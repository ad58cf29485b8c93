"""The operation counts kept with the benchmark."""
import json
import os

import pytest

from _pb import PB
from perfbench import run as harness


def _cfg(name):
    with open(os.path.join(PB, "configs", name + ".json")) as f:
        return json.load(f)


def test_resnet50_lands_on_the_published_multiply_adds():
    cfg = _cfg("resnet50")
    model = harness.load_module("models", "resnet50")
    got = model.flops_per_item(cfg)
    want = cfg["published_multiply_adds"] * 2 * 3
    assert abs(got - want) / want < 0.02, (got, want)


def test_resnet50_parameter_count():
    import numpy as np
    model = harness.load_module("models", "resnet50")
    shapes = model.param_shapes(_cfg("resnet50"))
    assert sum(int(np.prod(s)) for s in shapes.values()) == 25549480


def test_lstm_lm_count_is_consistent():
    cfg = _cfg("lstm-ptb-large")
    model = harness.load_module("models", "lstm-ptb-large")
    H, V, L = cfg["hidden_size"], cfg["vocab_size"], cfg["num_layers"]
    assert model.flops_per_item(cfg) == 3 * (16 * H * H * L + 2 * H * V)


def test_lstm_recurrence_work_and_bound():
    cfg = _cfg("lstm-ptb-large")
    metric = harness.load_reader("lstm_recurrence_roofline")
    with open(os.path.join(PB, "peaks.json")) as f:
        peaks = json.load(f)["TPU v5 lite"]
    flops, nbytes = metric.work(cfg, 256, 128)
    assert flops == 2 * 256 * 1500 * 6000 * 128 * 2
    assert nbytes == 2 * 6000 * 1500 * 2 + 2 * 128 * 256 * 7500 * 2
    least, bound = metric.least_seconds(cfg, 256, 128, peaks)
    assert bound == "compute"
    assert least == pytest.approx(flops / 197e12)


def test_peaks_name_their_source():
    with open(os.path.join(PB, "peaks.json")) as f:
        peaks = json.load(f)
    for kind, row in peaks.items():
        assert row["source"] and row["bf16_flops_per_s"] > 0
        assert row["hbm_bytes_per_s"] > 0 and row["ici_bits_per_s"] > 0
    train_fit = harness.load_module("drivers", "train_fit")
    with pytest.raises(SystemExit):
        train_fit.cell_peaks("TPU v9 imaginary")
