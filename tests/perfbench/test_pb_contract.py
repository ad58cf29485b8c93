"""BENCHMARK.json against the contract's limits, and every file a cell, a
configuration, a traffic mix, a driver or a per-layer metric needs is found
by its name: adding one is adding files and an entry."""
import copy
import os
import re

import pytest

import _structure as st
from _pb import BENCH, METRICS, PB


def test_top_level_keys_and_command():
    st.check_top_level(BENCH, st.DISK)


@pytest.mark.parametrize("config", BENCH["configs"],
                         ids=lambda c: c["name"])
def test_config_entry_and_files(config):
    st.check_config(BENCH, st.DISK, config)


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_entry_and_files(cell):
    st.check_cell(BENCH, st.DISK, cell)


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_reports_setup_another_end_to_end_and_a_layer(cell):
    st.check_cell_reports(BENCH, st.DISK, cell)


def test_cells_are_distinct_and_few_take_four_chips():
    st.check_cells(BENCH, st.DISK)
    st.check_four_chip_cells(BENCH, st.DISK)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    st.check_metric(BENCH, st.DISK, metric)


def test_metric_names_unique_and_setup_present():
    st.check_metrics(BENCH, st.DISK)


@pytest.mark.parametrize("check", st.STRUCTURAL, ids=lambda f: f.__name__)
def test_every_check_holds_on_the_file_as_it_stands(check):
    check(BENCH, st.DISK)


@pytest.mark.parametrize("check", st.STRUCTURAL, ids=lambda f: f.__name__)
def test_every_check_holds_with_a_config_two_cells_and_a_metric_appended(
        check):
    """What a ``model_config`` PR adds (a configuration, a one-chip cell, a
    four-chip cell whose mesh is ``{model: 4}``, a per-layer metric that
    lists both) passes every check without an edit to one."""
    check(*st.appended())


def test_the_appended_copy_holds_what_it_says_and_leaves_the_file_alone():
    before = copy.deepcopy(BENCH)
    bench, files = st.appended()
    assert BENCH == before
    assert len(bench["configs"]) == len(BENCH["configs"]) + 1
    assert [w["chips"] for w in bench["workloads"][-2:]] == [1, 4]
    four = files.json(st.pb("traffic", bench["workloads"][-1]["traffic"]
                            + ".json"))
    assert four["mesh"] == {"model": 4}
    assert bench["per_layer"][-1]["workloads"] == [st.NEW_ONE_CHIP,
                                                   st.NEW_FOUR_CHIP]
    assert bench["per_layer"][:-1] == BENCH["per_layer"]
    assert not os.path.exists(os.path.join(PB, "metrics",
                                           st.NEW_METRIC + ".py"))


@pytest.mark.parametrize("mesh", [{"model": 2}, {"data": 2, "model": 1},
                                  None], ids=["model2", "data2", "none"])
def test_a_four_chip_cell_that_leaves_chips_idle_is_refused(mesh):
    bench, files = st.appended()
    traffic = files.overlay[st.pb("traffic", st.NEW_FOUR_CHIP_TRAFFIC
                                  + ".json")]
    if mesh is None:
        del traffic["mesh"]
    else:
        traffic["mesh"] = mesh
    with pytest.raises(AssertionError):
        st.check_four_chip_cells(bench, files)


def test_more_four_chip_cells_than_a_quarter_is_refused():
    bench, files = st.appended()
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) == max(1, len(bench["workloads"]) // 4)
    # one more: a one-chip cell of the copy asks for four
    one = next(w for w in bench["workloads"] if w["chips"] == 1)
    one["chips"] = 4
    with pytest.raises(AssertionError):
        st.check_four_chip_cells(bench, files)


def test_a_copy_whose_four_chip_cells_are_full_makes_room_first():
    full, first = st.appended()
    bench, files = st.appended(full, tag="-2")
    files.overlay.update(first.overlay)
    assert len([w for w in bench["workloads"] if w["chips"] == 4]) == 3
    for check in st.STRUCTURAL:
        check(bench, files)


def test_a_metric_put_into_the_middle_or_a_cell_left_unreported_is_refused():
    bench, files = st.appended()
    bench["per_layer"].insert(st.SPAN_RUN_AT + 3, bench["per_layer"].pop())
    with pytest.raises(AssertionError):
        st.check_span_run(bench, files)
    bench, files = st.appended()
    rate = next(m for m in bench["end_to_end"] if m["name"] == "train_rate")
    rate["workloads"].remove(st.NEW_FOUR_CHIP)
    with pytest.raises(AssertionError):
        st.check_metrics(bench, files)


def test_benchmark_imports_nothing_of_the_repos_other_benchmarks():
    for base, _, files in os.walk(PB):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(base, name)) as f:
                    text = f.read()
                assert not re.search(
                    r"^\s*(from|import)\s+(bench|benchmarks|chip_smoke)\b",
                    text, re.M), name
