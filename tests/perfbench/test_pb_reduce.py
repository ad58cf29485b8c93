"""The reduction from a trace to busy time, named sums and gaps, checked by
hand on made-up intervals and on a small trace recorded on the chip."""
import os

import pytest

from _pb import ROOT
from perfbench import reduce

DATA = os.path.join(ROOT, "tests", "perfbench", "data")


def test_union_of_overlapping_and_nested_intervals():
    assert reduce.union_seconds([(0, 2), (1, 3), (5, 6), (5.2, 5.4)]) == 4.0
    assert reduce.union_seconds([]) == 0.0


def test_gaps_longest_first():
    got = reduce.gaps([(1, 2), (2.5, 3), (7, 8)], 0, 10)
    assert got == [(3, 7), (8, 10), (0, 1), (2, 2.5)]


def test_self_time_charges_a_loop_only_what_its_body_leaves():
    ops = [("while", 0.0, 10.0), ("cell", 1.0, 3.0), ("cell", 4.0, 6.0),
           ("add", 4.5, 5.0), ("copy", 11.0, 12.0)]
    got = reduce.self_seconds(ops)
    assert got == {"while": 6.0, "cell": 3.5, "add": 0.5, "copy": 1.0}


def test_busy_named_sums_and_gaps_of_a_made_up_trace():
    dev = "/device:TPU:0"
    trace = reduce.Trace(
        {dev: [("a", 0.0, 1.0), ("b", 3.0, 4.0), ("c", 4.5, 5.0)]},
        {dev: [("jit_step(1)", 0.0, 1.0), ("jit_step(1)", 3.0, 5.0)]})
    assert trace.steps() == [(0.0, 1.0), (3.0, 5.0)]
    assert trace.idle_gaps(host_calls=[(0.0, 0.1), (0.2, 2.9)]) == [
        (reduce.IN_NEXT, 2.0), (reduce.IN_STEP, 0.5)]
    assert trace.busy_seconds() == {dev: 2.5}
    assert trace.seconds_matching(lambda n: n in "ab") == (2.0, 2)


def test_device_planes_are_tpu_cores_only():
    assert reduce.is_device_plane("/device:TPU:0")
    assert not reduce.is_device_plane("/device:TPU:0 SparseCore 1")
    assert not reduce.is_device_plane("/host:CPU")


@pytest.mark.skipif(not os.path.isfile(os.path.join(DATA, "tiny.xplane.pb")),
                    reason="no recorded trace")
def test_recorded_chip_trace_reduces_to_the_hand_checked_numbers():
    import json
    with open(os.path.join(DATA, "tiny.expected.json")) as f:
        want = json.load(f)
    trace = reduce.load(os.path.join(DATA, "tiny.xplane.pb"))
    assert sorted(trace.devices) == want["devices"]
    dev = want["devices"][0]
    assert len(trace.devices[dev]) == want["events"]
    assert trace.busy_seconds()[dev] == pytest.approx(want["busy_s"],
                                                      rel=1e-4)
    seconds, count = trace.seconds_matching(
        lambda name: name.startswith(want["named"]["prefix"]))
    assert count == want["named"]["count"]
    assert seconds == pytest.approx(want["named"]["seconds"], rel=1e-4)
    assert len(trace.steps()) == want["steps"]
    label, seconds = trace.idle_gaps(1)[0]
    assert label == reduce.UNKNOWN      # no record of the host's calls
    assert seconds == pytest.approx(want["longest_gap"]["seconds"], rel=1e-3)
    assert reduce.short_name(trace.devices[dev][2][0], 40) == \
        "%convolution_tanh_fusion fusion bf16[102"


def test_gaps_between_steps_are_labelled_by_the_wrappers_clock():
    steps = [(0.0, 1.0), (1.5, 2.5), (4.0, 5.0)]
    calls = [(10.0, 10.1), (10.2, 10.25), (10.4, 11.9)]   # host clock
    found = [(2.5, 4.0), (1.0, 1.5), (0.2, 0.3)]
    assert reduce.label_gaps(found, steps, calls) == [
        (reduce.IN_NEXT, 1.5), (reduce.IN_BODY, 0.5),
        (reduce.IN_STEP, pytest.approx(0.1))]


def test_mfu_step_reads_the_steps_device_time_not_the_window():
    from perfbench import run as harness
    reader = harness.load_reader("mfu_step")
    dev = "/device:TPU:0"

    class Model:
        flops_per_item = staticmethod(lambda cfg: 3.0)
        items_per_batch = staticmethod(lambda cfg, traffic: 10)

    def ctx(steps, chips=1):
        ops = {dev: [("op", s, e) for s, e in steps]}
        programs = {dev: [("jit_step(1)", s, e) for s, e in steps]
                    + [("jit_small(2)", 9.0, 9.001)]}
        return {"trace": reduce.Trace(ops, programs), "model": Model,
                "cfg": {}, "traffic": {}, "chips": chips,
                "peaks": {"bf16_flops_per_s": 100.0}}

    # 2 steps of 30 FLOPs in 1.0 + 0.5 s of device time at a peak of 100/s
    back_to_back = reader.read(ctx([(0.0, 1.0), (1.0, 1.5)]))
    assert back_to_back == pytest.approx(100.0 * 60.0 / (1.5 * 100.0))
    # the host late with the second step: the same steps, the same reading
    assert reader.read(ctx([(0.0, 1.0), (6.0, 6.5)])) == back_to_back
    # four chips share a step's operations
    assert reader.read(ctx([(0.0, 1.0), (1.0, 1.5)], chips=4)) == \
        pytest.approx(back_to_back / 4)
    empty = {"trace": reduce.Trace({dev: [("op", 0.0, 1.0)]}), "model": Model,
             "cfg": {}, "traffic": {}, "chips": 1,
             "peaks": {"bf16_flops_per_s": 100.0}}
    assert reader.read(empty) is None
