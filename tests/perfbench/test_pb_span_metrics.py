"""The per-layer metrics that read the program's own spans and scopes, each
against a made-up ``ctx`` whose answer is worked out by hand."""
from collections import namedtuple

import pytest

import _structure as st
from _pb import BENCH
from perfbench import reduce, scopes
from perfbench import run as harness

DEV = "/device:TPU:0"
Span = namedtuple("Span", "name start_ns end_ns")


class Program:
    """Stands where ``mxnet_tpu.profiler`` does: spans on perf_counter_ns,
    and the step program's op map."""

    def __init__(self, spans=(), ops=None, kind="spmd-step"):
        self._spans = [Span(n, int(s * 1e9), int(e * 1e9))
                       for n, s, e in spans]
        self._ops, self._kind = ops or {}, kind

    def spans(self, lo, hi):
        return [s for s in self._spans if s.end_ns >= lo and s.start_ns <= hi]

    def op_scopes(self, kind):
        return self._ops if kind == self._kind else {}

    def self_totals(self, lo, hi):
        # none of the made-up spans below has a child inside the window
        # but fit.step, whose step.dispatch takes 1 ms of it
        own = {}
        for s in self.spans(lo, hi):
            own[s.name] = own.get(s.name, 0) + s.end_ns - s.start_ns
        own["fit.step"] -= own.get("step.dispatch", 0)
        return own


def make(spans=(), ops=None, device_ops=(), steps=(), window=(100.0, 10.0),
         **more):
    """A window of 10 s that starts at 100 s on the host's clock."""
    start, length = window
    trace = reduce.Trace({DEV: list(device_ops)} if device_ops else {},
                         {DEV: [("jit_step(1)", s, e) for s, e in steps]})
    ctx = {"profiler": Program(spans, ops, more.pop("kind", "spmd-step")),
           "trace": trace, "window_s": length,
           "feed": {"calls": [(start, start + 0.5)], "batches": len(steps)},
           "chips": 1, "cfg": {}, "traffic": {},
           "peaks": {"bf16_flops_per_s": 100.0}}
    ctx.update(more)
    return ctx


def read(metric, ctx):
    return harness.load_reader(metric).read(ctx)


NEW = [name for name, _ in st.SPAN_RUN]


def test_the_new_metrics_are_listed_for_the_cells_the_issue_names():
    # one run, in order, where PR 25 put it; entries after it are appended
    st.check_span_run(BENCH, st.DISK)


@pytest.mark.parametrize("metric", NEW)
def test_a_program_without_records_reads_nothing_and_raises_nothing(metric):
    class Model:
        pass

    # the parent commit: a profiler module with no spans() and no op map
    bare = make(model=Model)
    bare["profiler"] = None
    assert read(metric, bare) is None
    # spans and a map, but a rehearsal's trace: no device plane
    ctx = make(spans=[("fit.step", 101, 102)], ops={"a": "jit(f)/x/mul"},
               model=Model)
    if metric.split(".")[0] in ("step_dispatch_ms",):
        assert read(metric, ctx) is not None
    else:
        assert read(metric, ctx) is None


def test_input_shares_are_cut_to_the_window():
    spans = [("input.fetch", 99.0, 101.0),      # 1 s of it inside
             ("input.fetch", 103.0, 105.0),
             ("input.fetch", 109.5, 112.0),     # 0.5 s inside
             ("input.fetch", 120.0, 121.0),     # after the window
             ("input.slice", 103.0, 103.5), ("input.h2d", 103.5, 105.0),
             ("input.wait", 101.0, 103.0)]
    ctx = make(spans)
    assert read("input_fetch_busy_share", ctx) == pytest.approx(35.0)
    assert read("input_slice_share", ctx) == pytest.approx(5.0)
    assert read("input_h2d_share", ctx) == pytest.approx(15.0)
    assert read("input_h2d_share", make(spans[:3])) is None


def test_step_dispatch_is_the_least_whole_fit_step():
    # the device is the bottleneck: the first step finds it drained, every
    # later one waits a device step (50 ms) inside fit.step
    spans = [("fit.step", 101.0, 101.003), ("fit.step", 102.0, 102.050),
             ("fit.step", 103.0, 103.050), ("fit.step", 104.0, 104.051),
             ("step.dispatch", 101.0, 101.001),
             ("fit.step", 50.0, 60.0),          # before the window
             ("fit.step", 99.9995, 100.0005),   # cut by the window's start
             ("fit.step", 109.9995, 110.002)]   # and by its end
    assert read("step_dispatch_ms", make(spans)) == pytest.approx(3.0)
    assert read("step_dispatch_ms.hostfed", make(spans)) == \
        pytest.approx(3.0)
    # faster kernels (25 ms steps) do not move it; a slower dispatch does
    faster = [(n, s, s + (e - s) / 2 if e - s > 0.04 else e)
              for n, s, e in spans]
    assert read("step_dispatch_ms", make(faster)) == pytest.approx(3.0)
    slower = [(n, s, e + 0.002) for n, s, e in spans]
    assert read("step_dispatch_ms", make(slower)) == pytest.approx(5.0)


def test_scope_of_by_hand():
    assert scopes.scope_of(
        "jit(step)/jit(main)/transpose(jvp(Convolution/stage1_unit1_conv1))"
        "/conv_general_dilated") == ("Convolution/stage1_unit1_conv1", True)
    assert scopes.scope_of("jit(step)/jvp(BatchNorm/bn0)/reduce_sum") == \
        ("BatchNorm/bn0", False)
    assert scopes.scope_of(
        "jit(step)/transpose(jvp(RNN/lstm_rnn))/layer0/scan/while/body/"
        "dot_general") == ("RNN/lstm_rnn", True)
    assert scopes.scope_of("jit(step)/optimizer_update/jit(_where)/select_n"
                           ) == ("optimizer_update", False)
    assert scopes.scope_of("jit(fwd)/Convolution/conv0/conv_general_dilated"
                           ) == ("Convolution/conv0", False)
    # the step's own cotangent, a bare primitive, no name at all
    assert scopes.scope_of("jit(step)/transpose(jvp())/broadcast_in_dim") == \
        (None, False)
    assert scopes.scope_of("jit(step)/jit(main)/mul") == (None, False)
    assert scopes.scope_of(None) == (None, False)
    assert scopes.instruction("%fusion.12 = bf16[8]{0} fusion(%p), kind=kLoop"
                              ) == "fusion.12"


OPS = {"conv.1": "jit(step)/jvp(Convolution/stage1_unit1_conv1)/conv",
       "conv.2": "jit(step)/transpose(jvp(Convolution/conv0))/conv",
       "bn.1": "jit(step)/jvp(BatchNorm/bn0)/reduce_sum",
       "relu.1": "jit(step)/transpose(jvp(Activation/relu0))/select_n",
       "add.1": "jit(step)/jvp(elemwise_add/_plus0)/add",
       "fc.1": "jit(step)/jvp(FullyConnected/fc1)/dot_general",
       "sgd.1": "jit(step)/optimizer_update/mul",
       "glue.1": "jit(step)/transpose(jvp())/broadcast_in_dim"}


def two_steps():
    """Two steps of 10 s of device time each (on the device's own clock),
    the second's operations the first's; a small program between them whose
    instruction shares a name with the step's."""
    def step(at):
        return [("%conv.1 = bf16[8] convolution(%a, %b)", at, at + 2.0),
                ("%conv.2 = bf16[8] fusion(%a)", at + 2.0, at + 4.0),
                ("%bn.1 = f32[8] fusion(%a)", at + 4.0, at + 5.0),
                ("%relu.1 = bf16[8] fusion(%a)", at + 5.0, at + 5.5),
                ("%add.1 = bf16[8] fusion(%a)", at + 5.5, at + 6.0),
                ("%fc.1 = bf16[8] fusion(%a)", at + 6.0, at + 7.0),
                ("%sgd.1 = f32[8] fusion(%a)", at + 7.0, at + 8.0),
                ("%glue.1 = f32[8] broadcast(%a)", at + 8.0, at + 8.5),
                ("%copy.9 = f32[8] copy(%a)", at + 8.5, at + 9.0)]
    between = [("%conv.1 = f32[] add(%x, %y)", 10.2, 10.7)]
    return step(0.0) + between + step(11.0), [(0.0, 10.0), (11.0, 21.0)]


class Resnet:
    flops_per_item = staticmethod(lambda cfg: 1000.0)
    items_per_batch = staticmethod(lambda cfg, traffic: 4)
    # one dense layer under any name; a convolution's weight and a BN's
    # scale are not dense layers
    param_shapes = staticmethod(lambda cfg: {
        "conv0_weight": (8, 3, 3, 2), "bn0_gamma": (8,), "head": (10, 5)})


def test_device_time_is_charged_to_the_scope_of_each_instruction():
    device_ops, steps = two_steps()
    ctx = make(ops=OPS, device_ops=device_ops, steps=steps, model=Resnet)
    by_scope, step_seconds, unscoped = scopes.scoped_seconds(ctx)
    assert step_seconds == 20.0
    assert sorted(scopes.instruction(n) for n in unscoped) == [
        "copy.9", "glue.1"]
    assert by_scope[("Convolution/stage1_unit1_conv1", False)] == 4.0
    assert by_scope[("Convolution/conv0", True)] == 4.0
    assert by_scope[(None, False)] == 2.0       # glue.1 and copy.9
    # the program between the steps is nobody's: 9 of every 10 s are named
    assert sum(by_scope.values()) == pytest.approx(18.0)
    # convolutions: 8 s of 20; 2 steps x (1000 - 3*2*10*5) x 4 FLOPs at 100/s
    assert read("conv_roofline", ctx) == pytest.approx(
        100.0 * 2 * 700.0 * 4 / (8.0 * 100.0))
    # BN 2 s + ReLU 1 s + add 1 s of 20 s
    assert read("norm_act_share", ctx) == pytest.approx(20.0)
    assert read("unscoped_share", ctx) == pytest.approx(10.0)
    assert read("lstm_backward_share", ctx) is None
    # four chips share a step's operations
    assert read("conv_roofline", dict(ctx, chips=4)) == pytest.approx(
        100.0 * 2 * 700.0 / (8.0 * 100.0))


def test_a_loop_is_charged_what_its_body_leaves_and_the_rnn_its_backward():
    ops = {"while.1": "jit(step)/jvp(RNN/lstm_rnn)/layer0/scan/while",
           "cell.1": "jit(step)/jvp(RNN/lstm_rnn)/layer0/scan/while/body/"
                     "pallas_call",
           "while.2": "jit(step)/transpose(jvp(RNN/lstm_rnn))/layer0/scan/"
                      "while",
           "dot.2": "jit(step)/transpose(jvp(RNN/lstm_rnn))/layer0/scan/"
                    "while/body/dot_general",
           "head.1": "jit(step)/transpose(jvp(FullyConnected/pred))/dot"}
    device_ops = [("%while.1 = (s32[]) while(%t)", 0.0, 3.0),
                  ("%cell.1 = bf16[8] custom-call(%a)", 0.5, 1.5),
                  ("%while.2 = (s32[]) while(%t)", 3.0, 7.0),
                  ("%dot.2 = f32[8] fusion(%a)", 3.5, 6.5),
                  ("%head.1 = f32[8] fusion(%a)", 7.0, 10.0)]
    ctx = make(ops=ops, device_ops=device_ops, steps=[(0.0, 10.0)],
               kind="fused-step", model=Resnet)
    by_scope = scopes.scoped_seconds(ctx)[0]
    assert by_scope == {("RNN/lstm_rnn", False): 3.0,
                        ("RNN/lstm_rnn", True): 4.0,
                        ("FullyConnected/pred", True): 3.0}
    assert read("lstm_backward_share", ctx) == pytest.approx(40.0)
    assert read("unscoped_share", ctx) == 0.0
    assert read("conv_roofline", ctx) is None
    assert read("norm_act_share", ctx) is None


def test_idle_time_outside_every_fit_span_is_unattributed():
    # device clock: the window's first step starts at 5.0; the host
    # dispatched it at 101.0, so the offset is 96 s
    steps = [(5.0, 6.0), (8.0, 9.0), (9.5, 10.5)]
    device_ops = [("%a = f32[] add(%x, %y)", s, e) for s, e in steps]
    # idle on the device: [6, 8] and [9, 9.5] = [102, 104] and [105, 105.5]
    spans = [("fit.fetch", 100.0, 100.9), ("fit.step", 100.9, 101.2),
             ("step.dispatch", 101.0, 101.1),
             ("fit.fetch", 102.0, 103.5),        # covers 1.5 s of [102, 104]
             ("input.wait", 102.0, 103.5),       # a child: not counted twice
             ("fit.step", 103.5, 103.6),         # 0.1 s more
             ("fit.callbacks", 105.0, 105.25)]   # half of [105, 105.5]
    ctx = make(spans, device_ops=device_ops, steps=steps)
    assert scopes.host_offset(ctx) == pytest.approx(96.0)
    # 2.5 s idle; 1.5 + 0.1 + 0.25 attributed; 0.65 s left
    assert read("idle_unattributed_share", ctx) == pytest.approx(26.0)
    assert read("idle_unattributed_share.hostfed", ctx) == \
        pytest.approx(26.0)
    busy = make(spans, device_ops=[("%a = f32[] add(%x)", 5.0, 10.5)],
                steps=[(5.0, 10.5)])
    assert read("idle_unattributed_share", busy) is None


def test_the_builders_tables_by_hand():
    from perfbench import tables
    spans = [("fit.step", 101.0, 101.003), ("fit.step", 102.0, 102.050),
             ("fit.step", 103.0, 103.047), ("step.dispatch", 101.0, 101.001)]
    table = tables.span_table(make(spans))
    step = table["fit.step"]
    assert step["n"] == 3 and step["share_pct"] == pytest.approx(1.0)
    assert step["self_pct"] == pytest.approx(0.99)
    assert (step["median_ms"], step["p10_ms"], step["min_ms"]) == \
        pytest.approx((47.0, 3.0, 3.0))
    assert tables.scope_table(make(spans)) is None
    device_ops, steps = two_steps()
    found = tables.scope_table(make(ops=OPS, device_ops=device_ops,
                                    steps=steps))
    assert found["step_device_s"] == 20.0
    assert found["scoped_plus_unscoped_s"] == pytest.approx(18.0)
    assert found["by_type_s"]["Convolution"] == 4.0
    assert found["by_type_s"]["Convolution.bwd"] == 4.0
    assert found["by_type_s"]["unscoped"] == 2.0
    assert found["by_stage_s"]["stage1"] == 4.0
    assert found["by_stage_s"]["conv0"] == 4.0
    assert sorted(n for n, _ in found["unscoped_ops"]) == [
        "%copy.9", "%glue.1"] or len(found["unscoped_ops"]) == 2
