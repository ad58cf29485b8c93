"""The whole-program HBM memory model (compiler/memory.py): breakdown
goldens for the bundled micro models, the estimator validated against
LIVE pytree bytes (state_bytes_per_device) for ZeRO 0/1/2 on the
8-device mesh, and the MXTPU_HBM_BUDGET_MB bind gate — FusedStep and
SPMDTrainer.bind refuse over-budget programs with a typed
MemoryBudgetError naming contributors and the knobs that would fit,
and module_stepper re-raises instead of silently degrading to the
(equally over-budget) imperative path."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import perf
from mxnet_tpu.base import MXNetError
from mxnet_tpu.compiler import GraphIR, MemoryBudgetError, memory
from mxnet_tpu.io import DataBatch, DataDesc
from mxnet_tpu.parallel import (ShardingPlan, SPMDTrainer, make_mesh,
                                state_bytes_per_device)

MESH8 = make_mesh({"data": 8})
BATCH = 16
MB = float(1 << 20)


def _mlp_sym():
    h = mx.sym.FullyConnected(mx.sym.var("data"), num_hidden=32,
                              name="fc1")
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.FullyConnected(h, num_hidden=8, name="fc2")
    return mx.sym.SoftmaxOutput(h, name="softmax")


def _estimate(symb, shapes, plan=None, optimizer="sgd", remat=False,
              quant=None):
    arg_shapes, _, aux_shapes = symb.infer_shape(**shapes)
    all_shapes = dict(zip(symb.list_arguments(), arg_shapes))
    all_shapes.update(zip(symb.list_auxiliary_states(), aux_shapes))
    param_names = [n for n in symb.list_arguments() if n not in shapes]
    return memory.estimate_peak_bytes(
        GraphIR.from_symbol(symb), plan=plan, input_shapes=all_shapes,
        param_names=param_names, data_names=list(shapes),
        optimizer=optimizer, for_training=True, remat=remat,
        quant=quant)


# ---------------------------------------------------------------------------
# breakdown goldens: micro-LSTM and micro-ResNet
# ---------------------------------------------------------------------------

def test_micro_lstm_breakdown_golden():
    est = _estimate(memory._micro_lstm_symbol(),
                    {"data": (8, 16, 32), "softmax_label": (8, 16)})
    assert est is not None
    assert set(est.contributors) == {"params", "grads", "optimizer_state",
                                     "activations", "inputs_aux"}
    # sgd keeps one momentum slot: params == grads == optimizer_state
    assert est.contributors["params"] == est.contributors["grads"]
    assert est.contributors["params"] == est.contributors["optimizer_state"]
    # the packed RNN parameter block dominates the weight tree
    assert est.arrays["params"][0][0] == "lstm_parameters"
    # data (8,16,32) f32 = 16384 B rides in inputs_aux, undivided
    assert ("data", 8 * 16 * 32 * 4) in est.arrays["inputs_aux"]
    assert est.total == sum(est.contributors.values())
    assert est.notes == {"zero_degree": 1, "data_degree": 1,
                         "remat": False, "state_slots": 1,
                         "quantized_params": 0, "training": True}
    text = est.format_breakdown()
    for row in ("params", "grads", "optimizer_state", "activations",
                "inputs_aux", "peak total"):
        assert row in text


def test_micro_resnet_breakdown_golden():
    est = _estimate(memory._micro_resnet_symbol(),
                    {"data": (8, 3, 16, 16), "softmax_label": (8,)})
    assert est is not None
    # fc over the 8x8x8 pooled map: fc_weight (10, 512) f32 = 20480 B
    assert ("fc_weight", 10 * 512 * 4) in est.arrays["params"]
    assert ("data", 8 * 3 * 16 * 16 * 4) in est.arrays["inputs_aux"]
    # a convnet holding every activation for the backward is
    # activation-dominated — the shape the remat knob exists for
    assert est.contributors["activations"] > est.contributors["params"]
    assert est.top(1)[0][0] == "activations"


def test_remat_lowers_the_activation_term():
    symb = memory._micro_resnet_symbol()
    shapes = {"data": (8, 3, 16, 16), "softmax_label": (8,)}
    full = _estimate(symb, shapes, remat=False)
    remat = _estimate(symb, shapes, remat=True)
    # remat prices the liveness-scan peak, not the hold-everything sum
    assert remat.contributors["activations"] \
        < full.contributors["activations"]
    assert remat.notes["remat"] is True


def test_quantized_params_shrink_storage():
    symb = memory._micro_resnet_symbol()
    shapes = {"data": (8, 3, 16, 16), "softmax_label": (8,)}
    fp32 = _estimate(symb, shapes)
    q = _estimate(symb, shapes, quant={"fc_weight": "int8"})
    assert q.contributors["params"] \
        == fp32.contributors["params"] - 3 * (10 * 512)  # 4B -> 1B
    assert q.notes["quantized_params"] == 1


def test_state_slots_golden():
    assert memory.state_slots("adam") == 2
    assert memory.state_slots("rmsprop") == 1
    assert memory.state_slots("sgd") == 1
    assert memory.state_slots(None) == 0
    assert memory.state_slots(3) == 3
    assert memory.state_slots("exotic") == 1   # never undercount to 0


# ---------------------------------------------------------------------------
# the estimator vs live pytree bytes: ZeRO 0/1/2 on the 8-device mesh
# ---------------------------------------------------------------------------

def _bound_trainer(zero):
    np.random.seed(0)
    mx.random.seed(0)
    tr = SPMDTrainer(_mlp_sym(), optimizer="adam",
                     optimizer_params=dict(learning_rate=1e-3),
                     mesh=MESH8, shard_optimizer_state=zero)
    tr.bind(data_shapes={"data": (BATCH, 16)},
            label_shapes={"softmax_label": (BATCH,)})
    return tr


@pytest.mark.parametrize("zero", [0, 1, 2])
def test_estimator_matches_live_state_bytes(zero):
    """The static optimizer-state and param terms agree with the LIVE
    per-device pytree bytes (each leaf's own shard shape) within 5% —
    the tolerance documented in performance.md."""
    tr = _bound_trainer(zero)
    est = _estimate(tr._opt_res.symbol,
                    {"data": (BATCH, 16), "softmax_label": (BATCH,)},
                    plan=ShardingPlan(MESH8, zero=zero),
                    optimizer="adam")
    measured_state = state_bytes_per_device(tr.states)
    measured_params = state_bytes_per_device(tr.params)
    assert est.contributors["optimizer_state"] \
        == pytest.approx(measured_state, rel=0.05)
    assert est.contributors["params"] \
        == pytest.approx(measured_params, rel=0.05)


def test_estimator_sees_the_zero_8x_drop():
    """ZeRO's 8x optimizer-state drop — measured live in
    test_sharding_rules — is reproduced by the static model."""
    rep = _estimate(_mlp_sym(),
                    {"data": (BATCH, 16), "softmax_label": (BATCH,)},
                    plan=ShardingPlan(MESH8, zero=0), optimizer="adam")
    zero = _estimate(_mlp_sym(),
                     {"data": (BATCH, 16), "softmax_label": (BATCH,)},
                     plan=ShardingPlan(MESH8, zero=1), optimizer="adam")
    assert rep.contributors["optimizer_state"] \
        == 8 * zero.contributors["optimizer_state"]
    assert zero.notes["zero_degree"] == 8


# ---------------------------------------------------------------------------
# the MXTPU_HBM_BUDGET_MB bind gate
# ---------------------------------------------------------------------------

def _bound_module():
    mod = mx.mod.Module(_mlp_sym(), data_names=["data"],
                        label_names=["softmax_label"])
    mod.bind(data_shapes=[DataDesc("data", (BATCH, 16))],
             label_shapes=[DataDesc("softmax_label", (BATCH,))])
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    return mod


def test_fused_step_bind_over_budget_raises(monkeypatch):
    """module_stepper re-raises the typed error instead of silently
    degrading to the (equally over-budget) imperative fallback."""
    monkeypatch.setenv("MXTPU_HBM_BUDGET_MB", "0.001")
    mod = _bound_module()
    with pytest.raises(MemoryBudgetError) as exc:
        perf.module_stepper(mod)
    msg = str(exc.value)
    assert "FusedStep" in msg
    assert "MXTPU_HBM_BUDGET_MB=0.001" in msg
    assert "top contributors" in msg
    assert "knobs that would fit it" in msg
    assert "MXTPU_REMAT_MB" in msg          # activations held, remat off
    assert "peak total" in msg              # full breakdown appended
    assert exc.value.estimate is not None
    assert exc.value.estimate.total > 0.001 * MB
    assert isinstance(exc.value, MXNetError)


def test_fused_step_bind_within_budget_is_untouched(monkeypatch):
    monkeypatch.setenv("MXTPU_HBM_BUDGET_MB", "10000")
    stepper = perf.module_stepper(_bound_module())
    assert stepper is not None
    batch = DataBatch(
        data=[mx.nd.array(np.random.rand(BATCH, 16).astype(np.float32))],
        label=[mx.nd.array(np.zeros((BATCH,), np.float32))])
    stepper.step(batch)                     # the gate costs no behavior


def test_spmd_bind_over_budget_raises_before_state_replaced(monkeypatch):
    monkeypatch.setenv("MXTPU_HBM_BUDGET_MB", "0.001")
    tr = SPMDTrainer(_mlp_sym(), optimizer="adam",
                     mesh=MESH8, shard_optimizer_state=False)
    with pytest.raises(MemoryBudgetError) as exc:
        tr.bind(data_shapes={"data": (BATCH, 16)},
                label_shapes={"softmax_label": (BATCH,)})
    msg = str(exc.value)
    assert "SPMDTrainer.bind" in msg
    # state bytes present, ZeRO off, 8-wide data axis: the ZeRO knob
    # is on the menu
    assert "MXTPU_ZERO=1" in msg
    # the gate fired BEFORE any trainer state was replaced (the bind
    # contract): no params/states were allocated
    assert not getattr(tr, "params", None)
    assert not getattr(tr, "states", None)


def test_gate_is_the_steps_own_and_names_its_front_end(monkeypatch):
    """One gate, in ``FusedStep``, on one condition (a budget is set):
    ``SPMDTrainer.bind`` above and a ``FusedStep`` built by hand both
    reach it, each named in the error; a program whose shapes were not
    given cannot be priced and is not refused."""
    from mxnet_tpu import optimizer as opt_mod
    from mxnet_tpu.perf.step_runtime import FusedStep
    monkeypatch.setenv("MXTPU_HBM_BUDGET_MB", "0.001")
    symb = _mlp_sym()
    shapes = {"data": (BATCH, 16), "softmax_label": (BATCH,)}
    arg_shapes, _, _ = symb.infer_shape(**shapes)
    shapes = dict(zip(symb.list_arguments(), arg_shapes))
    names = [n for n in shapes if n not in ("data", "softmax_label")]
    sgd = opt_mod.create("sgd", learning_rate=0.1)
    with pytest.raises(MemoryBudgetError) as exc:
        FusedStep(symb, sgd, names, name="probe", input_shapes=shapes)
    assert "FusedStep('probe') bind: estimated peak HBM" in str(exc.value)
    assert FusedStep(symb, sgd, names, name="probe") is not None


def test_spmd_bind_within_budget_is_untouched(monkeypatch):
    monkeypatch.setenv("MXTPU_HBM_BUDGET_MB", "10000")
    tr = _bound_trainer(zero=1)
    assert tr.params                        # bind completed normally


def test_budget_gate_off_by_default():
    assert memory.hbm_budget_mb() is None
    # check_budget with no estimate or budget is a no-op, never a raise
    memory.check_budget(None, 100.0, "x")
    est = memory.MemoryEstimate({"params": 10}, {}, {})
    memory.check_budget(est, None, "x")


def test_budget_error_message_golden():
    """The error names the top contributors largest-first and every
    applicable knob, and appends the full breakdown."""
    est = memory.MemoryEstimate(
        contributors={"params": int(600 * MB), "grads": int(600 * MB),
                      "optimizer_state": int(1200 * MB),
                      "activations": int(500 * MB),
                      "inputs_aux": int(10 * MB)},
        arrays={"params": [("w", int(600 * MB))]},
        notes={"remat": False, "data_degree": 8, "quantized_params": 0,
               "zero_degree": 1, "state_slots": 2, "training": True})

    class _Plan:
        zero = False

    with pytest.raises(MemoryBudgetError) as exc:
        memory.check_budget(est, 1000.0, "FusedStep('net') bind",
                            plan=_Plan())
    msg = str(exc.value)
    assert "FusedStep('net') bind: estimated peak HBM 2910.0 MB" in msg
    assert "exceeds MXTPU_HBM_BUDGET_MB=1000" in msg
    assert ("top contributors: optimizer_state 1200.0 MB, "
            "grads 600.0 MB, params 600.0 MB") in msg
    assert "MXTPU_ZERO=1" in msg and "8x" in msg
    assert "MXTPU_REMAT_MB=250" in msg      # half the activation term
    assert "MXTPU_QUANT=1" in msg
    assert "peak total" in msg


def test_unpriceable_program_never_gates(monkeypatch):
    """A None estimate (shapes not inferable) must not refuse the bind:
    the model may only refuse programs it can actually price."""
    monkeypatch.setenv("MXTPU_HBM_BUDGET_MB", "0.001")
    memory.check_budget(None, memory.hbm_budget_mb(), "x")  # no raise


# ---------------------------------------------------------------------------
# the remat pass delegates its byte accounting here
# ---------------------------------------------------------------------------

def test_remat_pass_uses_the_memory_model():
    from mxnet_tpu.compiler.passes import RematPolicy
    assert RematPolicy._activation_bytes.__wrapped__ is not None \
        if hasattr(RematPolicy._activation_bytes, "__wrapped__") \
        else True
    symb = memory._micro_resnet_symbol()
    shapes = {"data": (8, 3, 16, 16), "softmax_label": (8,)}
    arg_shapes, _, aux_shapes = symb.infer_shape(**shapes)
    all_shapes = dict(zip(symb.list_arguments(), arg_shapes))
    all_shapes.update(zip(symb.list_auxiliary_states(), aux_shapes))
    ir = GraphIR.from_symbol(symb)
    total = memory.activation_bytes(ir, all_shapes)
    peak = memory.liveness_peak_bytes(ir, all_shapes)
    assert total is not None and peak is not None
    # the liveness peak can never exceed the hold-everything sum
    assert 0 < peak <= total
