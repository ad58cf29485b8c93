"""``correct`` comes out false when the timed path is broken underneath, and
when the reference is computed in the precision below the configuration's
(the control). Driven in this process at the rehearsal's size: the harness's
look for a chip is skipped, the rest of a run is the run's own code."""
import json
import os

import numpy as np
import pytest

from _pb import BENCH, CELLS

FOUR_CHIP_CELLS = [w["name"] for w in BENCH["workloads"] if w["chips"] == 4]
from perfbench import compare
from perfbench import run as harness


@pytest.fixture(autouse=True)
def _environment_restored():
    """A run sets the cell's ``env`` maps in this process; the tests that
    share the worker get the environment back as it was."""
    import os
    saved = dict(os.environ)
    yield
    os.environ.clear()
    os.environ.update(saved)


def _run(cell, capsys, seed=2147483659):
    harness.main(["--workload", cell, "--seed", str(seed), "--seconds",
                  "0.5", "--trace", "0", "--rehearse"])
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def _step_owner(cell):
    if cell.startswith("lstm"):
        from mxnet_tpu.perf.step_runtime import ModuleStepper
        return ModuleStepper, ("_params", "_states", "_aux", "_num_update")
    from mxnet_tpu.parallel.trainer import SPMDTrainer
    return SPMDTrainer, ("params", "states", "aux", "_num_update")


def _keep_first(x, parts=2):
    """All but the first 1/parts of the rows left out: the rest repeat the
    first part, so every mean over the batch is the mean over that part."""
    from mxnet_tpu.ndarray import NDArray
    raw = x._data if isinstance(x, NDArray) else x
    arr = np.asarray(raw)
    own = arr.shape[0] // parts
    arr = np.concatenate([arr[:own]] * parts)
    return NDArray(arr) if isinstance(x, NDArray) else arr


def _break_batches(monkeypatch, cell, parts):
    owner, _ = _step_owner(cell)
    sound = owner.step

    def broken(self, batch):
        if isinstance(batch, dict):
            batch = {n: _keep_first(v, parts) for n, v in batch.items()}
        else:
            batch.data = [_keep_first(v, parts) for v in batch.data]
            batch.label = [_keep_first(v, parts) for v in batch.label]
        return sound(self, batch)

    monkeypatch.setattr(owner, "step", broken)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell, capsys):
    line = _run(cell, capsys)
    assert line["correct"] is True and line["rehearsal"] == "passed"


@pytest.mark.parametrize("cell", CELLS)
def test_step_that_returns_its_state_unchanged_is_not_correct(
        cell, capsys, monkeypatch):
    owner, fields = _step_owner(cell)
    sound = owner.step

    def broken(self, batch):
        import jax
        kept = {f: jax.tree_util.tree_map(
            lambda v: v.copy() if hasattr(v, "copy") else v,
            getattr(self, f)) for f in fields}
        outs = sound(self, batch)
        for f, v in kept.items():
            setattr(self, f, v)
        return outs

    monkeypatch.setattr(owner, "step", broken)
    line = _run(cell, capsys)
    assert line["correct"] is False
    # nothing moved: the change's gap reads 1 on every leaf
    delta = [c["value"] for name, c in line["checks"].items()
             if name.startswith("delta_gap")]
    assert delta and delta[0] == pytest.approx(1.0, abs=0.05)


@pytest.mark.parametrize("cell", CELLS)
def test_half_of_the_batch_left_out_is_not_correct(cell, capsys,
                                                   monkeypatch):
    _break_batches(monkeypatch, cell, 2)
    line = _run(cell, capsys)
    assert line["correct"] is False


@pytest.mark.parametrize("cell", FOUR_CHIP_CELLS)
def test_exchange_between_chips_left_out_is_not_correct(cell, capsys,
                                                        monkeypatch):
    """With no exchange every chip trains on its own rows alone: planted by
    giving every chip the first chip's rows, so the global means the program
    takes are the means over one chip's share."""
    _break_batches(monkeypatch, cell, 4)
    line = _run(cell, capsys)
    assert line["correct"] is False


@pytest.mark.parametrize("config,traffic", [("resnet50", "train-resident"),
                                            ("lstm-ptb-large",
                                             "train-fed-seq128")])
def test_control_in_the_precision_below_is_not_correct(config, traffic):
    """The control: the reference computed in fp8, put in the program's
    place, fails at least one of the cell's numbers."""
    import jax
    cell = harness.load_cell(f"{config}.{traffic}", rehearse=True)
    model = harness.load_module("models", config)
    cfg, tr = cell["cfg"], cell["traffic_params"]
    ref = model.reference(cfg, tr, 41, devices=jax.devices())
    ctl = model.reference(cfg, tr, 41, precision="fp8",
                          devices=jax.devices())
    ok, checks = compare.judge(compare.gaps(ctl, ref)[0], cell["limits"])
    assert ok is False, checks
    same, _ = compare.judge(compare.gaps(ref, ref)[0], cell["limits"])
    assert same is True


@pytest.mark.parametrize("cell", CELLS)
def test_recorded_chip_readings_against_the_committed_limits(cell, capsys):
    """The readings the limits were set from (``calibrate.py`` on the chip,
    PERF.md section 2): under the limits as committed every sound run is
    correct, every control and planted fault is not. The fed cell runs the
    resident cell's step on the same seeds, so it has the same readings."""
    from perfbench import calibrate
    readings = os.path.join(os.path.dirname(__file__), "data", "readings")
    path = os.path.join(readings, cell + ".jsonl")
    if not os.path.exists(path):
        path = os.path.join(readings, "resnet50.train-resident.jsonl")
    assert calibrate.rejudge(harness.load_cell(cell), path) == 0
    said = capsys.readouterr().out
    assert "control_fp8" in said and "fault_half_batch" in said


def test_training_driver_refuses_a_metric_it_does_not_measure():
    driver = harness.load_module("drivers", "train_fit")
    cell = {"name": "x", "end_to_end": [
        {"name": "setup_s", "unit": "s"},
        {"name": "train_rate_hostfed", "unit": "items/s"}]}
    assert driver.end_to_end_values(cell, 3.0, 7.0) == {
        "setup_s": 3.0, "train_rate_hostfed": 7.0}
    cell["end_to_end"].append({"name": "latency_p95_ms", "unit": "ms"})
    with pytest.raises(SystemExit):
        driver.end_to_end_values(cell, 3.0, 7.0)


def test_comparison_arithmetic_by_hand():
    ref = {"losses": [2.0, 2.0], "grad_norms": {"a": 1.0, "b": 0.1,
                                                "c": 0.0},
           "delta_norms": {"a": 0.5, "b": 0.05, "c": 0.0}}
    prog = {"losses": [2.0, 2.1], "grad_norms": {"a": 1.1, "b": 0.1,
                                                 "c": 0.02},
            "delta_norms": {"a": 0.5, "b": 0.06, "c": 0.3}}
    numbers, where = compare.gaps(prog, ref)
    assert numbers["loss_gap"] == pytest.approx(0.05)
    # c's gradient is nought in the reference: held against the median (0.1)
    assert numbers["grad_gap_worst"] == pytest.approx(0.2)
    assert numbers["grad_gap_median"] == pytest.approx(0.1)     # a's
    assert where["grad_gap_worst"] == "c"
    assert where["leaves_left_out"] == 1
    # ... and left out of the change by that rule; b reads 0.01 / 0.275
    assert numbers["delta_gap_worst"] == pytest.approx(0.01 / 0.275)
    assert numbers["delta_gap_median"] == pytest.approx(0.005 / 0.275)
    ok, checks = compare.judge(numbers, {"loss_gap": 0.06,
                                         "grad_gap_worst": 0.3})
    assert ok and set(checks) == {"loss_gap", "grad_gap_worst"}
    ok, _ = compare.judge(numbers, {"loss_gap": 0.04, "grad_gap_worst": 0.3})
    assert not ok
