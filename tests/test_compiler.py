"""Compiler layer (mxnet_tpu/compiler): pass framework, graph
fingerprints, persistent compilation cache.

Three contracts (docs/how_to/compiler.md):

* fingerprints are STABLE — same graph, same key, across processes —
  and SENSITIVE: any attr / shape / mesh / donation change is a new key;
* passes are value-preserving — DCE/CSE-transformed step programs are
  bitwise-identical to un-passed ones for Module, Gluon and SPMD (the
  donation-equivalence discipline of tests/test_perf_runtime.py);
* the cache can only ever cost a recompile — corrupt, truncated, or
  fault-injected (``compiler.cache.read``) entries are quarantined and
  the bind recompiles; it never serves a wrong program, never fails.

All CPU, tiny shapes, tmp-dir cache roots (the user cache is never
touched).
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import compiler, gluon
from mxnet_tpu.compiler import (CompilationCache, GraphIR, Pass,
                                PassContext, PassManager)
from mxnet_tpu.compiler.passes import (CommonSubexpressionElimination,
                                       DeadOpElimination)
from mxnet_tpu.gluon import nn
from mxnet_tpu.io import DataBatch, DataDesc
from mxnet_tpu.resilience import FaultPlan, faults


@pytest.fixture
def tmp_cache(tmp_path, monkeypatch):
    """Point the persistent cache at an isolated tmp root."""
    root = str(tmp_path / "executables")
    monkeypatch.setenv("MXTPU_COMPILE_CACHE_DIR", root)
    compiler.reset_stats()
    yield root
    compiler.reset_stats()


def mlp_symbol(num_hidden=16, name_prefix=""):
    data = mx.sym.var("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=num_hidden,
                                name=name_prefix + "fc1")
    act = mx.sym.Activation(fc1, act_type="relu",
                            name=name_prefix + "relu1")
    fc2 = mx.sym.FullyConnected(act, num_hidden=4,
                                name=name_prefix + "fc2")
    return mx.sym.SoftmaxOutput(fc2, mx.sym.var("softmax_label"),
                                name=name_prefix + "softmax")


def dup_branch_symbol():
    """A graph with a REAL duplicate subexpression, so CSE actually
    rewrites it (relu(fc1) computed twice, summed)."""
    data = mx.sym.var("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    b1 = mx.sym.Activation(fc1, act_type="relu", name="relu_a")
    b2 = mx.sym.Activation(fc1, act_type="relu", name="relu_b")
    merged = b1 + b2
    fc2 = mx.sym.FullyConnected(merged, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(fc2, mx.sym.var("softmax_label"),
                                name="softmax")


# ---------------------------------------------------------------------------
# fingerprints: golden stability + sensitivity
# ---------------------------------------------------------------------------

def test_fingerprint_stable_for_identical_construction():
    assert compiler.graph_fingerprint(mlp_symbol()) \
        == compiler.graph_fingerprint(mlp_symbol())


def test_fingerprint_changes_on_attr_shape_mesh_donation():
    base = compiler.graph_fingerprint(mlp_symbol())
    # attr change -> new graph fingerprint
    assert compiler.graph_fingerprint(mlp_symbol(num_hidden=32)) != base
    # name change -> new fingerprint (names are the dict calling
    # convention of the traced programs)
    assert compiler.graph_fingerprint(mlp_symbol(name_prefix="x_")) != base

    # shape change -> new PROGRAM key (structural fp is shape-free)
    import jax.numpy as jnp
    a8 = ({"data": jnp.zeros((8, 12))},)
    a4 = ({"data": jnp.zeros((4, 12))},)
    sig8, _ = compiler.fingerprint.aval_signature(a8)
    sig4, _ = compiler.fingerprint.aval_signature(a4)
    k8 = compiler.program_key("t", base, sig8)
    assert k8 != compiler.program_key("t", base, sig4)
    # donation change -> new program key
    assert k8 != compiler.program_key("t", base, sig8, donation=(0,))
    # mesh change -> new signature
    import jax
    from mxnet_tpu.parallel import make_mesh
    m1 = make_mesh({"data": 1}, devices=jax.devices()[:1])
    m2 = make_mesh({"data": 2}, devices=jax.devices()[:2])
    assert compiler.mesh_signature(m1) != compiler.mesh_signature(m2)
    assert compiler.mesh_signature(None) == "none"


def test_fingerprint_golden_across_processes():
    """Same model code in a fresh interpreter -> the same key. This is
    the property the whole persistent cache stands on."""
    prog = (
        "import mxnet_tpu as mx\n"
        "from mxnet_tpu import compiler\n"
        "data = mx.sym.var('data')\n"
        "fc1 = mx.sym.FullyConnected(data, num_hidden=16, name='fc1')\n"
        "act = mx.sym.Activation(fc1, act_type='relu', name='relu1')\n"
        "fc2 = mx.sym.FullyConnected(act, num_hidden=4, name='fc2')\n"
        "net = mx.sym.SoftmaxOutput(fc2, mx.sym.var('softmax_label'),"
        " name='softmax')\n"
        "print(compiler.graph_fingerprint(net))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=240)
    assert out.returncode == 0, out.stderr
    child_fp = out.stdout.strip().splitlines()[-1]
    assert child_fp == compiler.graph_fingerprint(mlp_symbol(
        name_prefix=""))


def test_code_salt_override_and_stability(monkeypatch):
    s1 = compiler.code_salt()
    assert s1 == compiler.code_salt()    # process-cached
    monkeypatch.setattr(compiler.fingerprint, "_CODE_SALT", None)
    monkeypatch.setenv("MXTPU_COMPILE_CACHE_SALT", "pinned")
    s2 = compiler.code_salt()
    assert s2 != s1
    monkeypatch.setattr(compiler.fingerprint, "_CODE_SALT", None)


# ---------------------------------------------------------------------------
# pass framework
# ---------------------------------------------------------------------------

def test_pass_manager_orders_by_requires():
    seen = []

    class A(Pass):
        name = "a"

        def run(self, ir, ctx):
            seen.append("a")
            return ir, {}

    class B(Pass):
        name = "b"
        requires = ("a",)

        def run(self, ir, ctx):
            seen.append("b")
            return ir, {}

    # registered b-first; requires puts a before b anyway
    mgr = PassManager([B(), A()])
    mgr.run(GraphIR.from_symbol(mlp_symbol()), PassContext())
    assert seen == ["a", "b"]


def test_pass_manager_rejects_unknown_and_cyclic_requires():
    class Needy(Pass):
        name = "needy"
        requires = ("nonexistent",)

        def run(self, ir, ctx):
            return ir, {}

    with pytest.raises(mx.base.MXNetError, match="unknown pass"):
        PassManager([Needy()]).schedule()

    class C1(Pass):
        name = "c1"
        requires = ("c2",)

        def run(self, ir, ctx):
            return ir, {}

    class C2(Pass):
        name = "c2"
        requires = ("c1",)

        def run(self, ir, ctx):
            return ir, {}

    with pytest.raises(mx.base.MXNetError, match="cycle"):
        PassManager([C1(), C2()]).schedule()


def test_dead_op_elimination_prunes_unreachable():
    # a Group symbol where only the first head is requested: the IR keeps
    # the full node list, DCE prunes the dead branch
    a = mx.sym.var("a")
    live = mx.sym.exp(a, name="live")
    dead = mx.sym.FullyConnected(a, num_hidden=7, name="deadfc")
    grp = mx.sym.Group([live, dead])
    ir = GraphIR.from_symbol(grp)
    ir.outputs = ir.outputs[:1]         # only 'live' requested
    before = len(ir.nodes)
    out, info = DeadOpElimination().run(ir, PassContext())
    assert info["removed"] >= 2         # deadfc + its weight/bias vars
    assert len(out.nodes) < before
    assert {n.name for n in out.nodes} == {"a", "live"}
    # the pruned graph still evaluates
    ex = out.to_symbol().simple_bind(None, grad_req="null", a=(3,))
    ex.forward(a=mx.nd.array(np.ones(3)))


def test_cse_merges_duplicates_and_respects_rng_and_aux():
    # duplicate pure subexpression: merged
    res = compiler.optimize(dup_branch_symbol())
    assert res.changed
    base_ops = GraphIR.from_symbol(dup_branch_symbol()).num_ops()
    opt_ops = GraphIR.from_symbol(res.symbol).num_ops()
    assert opt_ops < base_ops

    # sampling ops never merge (two Dropouts draw different masks)
    x = mx.sym.var("x")
    g_rng = mx.sym.Dropout(x, p=0.5) + mx.sym.Dropout(x, p=0.5)
    assert not compiler.optimize(g_rng).changed

    # aux-updating ops (BatchNorm running stats) never merge
    bn_in = mx.sym.var("bn_in")
    gamma, beta = mx.sym.var("gamma"), mx.sym.var("beta")
    mmean, mvar = mx.sym.var("mmean"), mx.sym.var("mvar")
    bn1 = mx.sym.BatchNorm(bn_in, gamma, beta, mmean, mvar, name="bn1")
    bn2 = mx.sym.BatchNorm(bn_in, gamma, beta, mmean, mvar, name="bn2")
    assert not compiler.optimize(bn1 + bn2).changed

    # stateful ops (Custom: per-invocation _op_state, user callbacks)
    # never merge — each invocation must keep firing
    @mx.operator.register("cse_probe_sqr")
    class _Prop(mx.operator.CustomOpProp):
        def infer_shape(self, in_shape):
            return in_shape, [in_shape[0]], []

        def create_operator(self, ctx, shapes, dtypes):
            class _Op(mx.operator.CustomOp):
                def forward(self, is_train, req, in_data, out_data, aux):
                    self.assign(out_data[0], req[0],
                                in_data[0] * in_data[0])
            return _Op()

    cin = mx.sym.var("cin")
    c1 = mx.sym.Custom(cin, op_type="cse_probe_sqr", name="c1")
    c2 = mx.sym.Custom(cin, op_type="cse_probe_sqr", name="c2")
    assert not compiler.optimize(c1 + c2).changed


def test_cse_skips_sparse_grad_and_keeps_add_bindable():
    """Merging identical sparse_grad Embeddings would flip the weight's
    tied-weight classification and make grad_req='add' un-bindable —
    passes must never make a bind fail, so these nodes don't merge."""
    data = mx.sym.var("data")
    w = mx.sym.var("emb_weight")
    e1 = mx.sym.Embedding(data, w, input_dim=10, output_dim=4,
                          sparse_grad=True, name="e1")
    e2 = mx.sym.Embedding(data, w, input_dim=10, output_dim=4,
                          sparse_grad=True, name="e2")
    net = mx.sym.sum(e1 + e2)
    assert not compiler.optimize(net).changed
    ex = net.simple_bind(None, grad_req="add", data=(3,),
                         emb_weight=(10, 4))
    ex.forward(is_train=True, data=mx.nd.array(np.zeros(3)))


def test_cse_never_mutates_the_original_symbol():
    sym = dup_branch_symbol()
    nodes_before = [(id(n), tuple(id(p) for p, _ in n.inputs))
                    for n in sym._topo_nodes()]
    compiler.optimize(sym)
    nodes_after = [(id(n), tuple(id(p) for p, _ in n.inputs))
                   for n in sym._topo_nodes()]
    assert nodes_before == nodes_after


def test_remat_policy_budget_and_annotations(monkeypatch):
    sym = mlp_symbol()
    shapes = {"data": (8, 12), "softmax_label": (8,),
              "fc1_weight": (16, 12), "fc1_bias": (16,),
              "fc2_weight": (4, 16), "fc2_bias": (4,)}
    # no budget, no mirror: remat off
    res = compiler.optimize(sym, input_shapes=shapes)
    assert res.annotations.get("remat") is False
    # a tiny budget flips the decision and reports the byte estimate
    monkeypatch.setenv("MXTPU_REMAT_MB", "0.0001")
    res2 = compiler.optimize(sym, input_shapes=shapes)
    assert res2.annotations.get("remat") is True
    assert res2.annotations.get("remat_activation_bytes_est", 0) > 0
    assert "remat=1" in res2.transform_sig
    # the explicit mirror knob forces it regardless of budget
    monkeypatch.delenv("MXTPU_REMAT_MB")
    monkeypatch.setenv("MXNET_BACKWARD_DO_MIRROR", "1")
    assert compiler.optimize(sym, input_shapes=shapes).remat is True


def test_remat_decision_is_bitwise_neutral(monkeypatch, tmp_cache):
    """Recompute-in-backward changes the schedule, never the values."""
    def run():
        batch = DataBatch(
            data=[mx.nd.array(np.random.RandomState(3).rand(4, 12))],
            label=[mx.nd.array(
                np.random.RandomState(4).randint(0, 4, (4,)).astype(
                    np.float32))])
        mx.random.seed(9)
        mod = mx.mod.Module(mlp_symbol())
        mod.bind(data_shapes=[DataDesc("data", (4, 12))],
                 label_shapes=[DataDesc("softmax_label", (4,))])
        mod.init_params(mx.init.Xavier())
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": 0.1})
        for _ in range(2):
            mod.forward(batch, is_train=True)
            mod.backward()
            mod.update()
        arg, _ = mod.get_params()
        return {n: v.asnumpy() for n, v in arg.items()}

    plain = run()
    monkeypatch.setenv("MXTPU_REMAT_MB", "0.0001")
    remat = run()
    for n in plain:
        assert np.array_equal(plain[n], remat[n]), n


def test_annotate_slot_runs_registered_annotators():
    from mxnet_tpu.compiler import passes as passes_mod

    def annot(ir, ctx):
        return {"quant_ready": ir.num_ops()}

    passes_mod.register_annotator(annot)
    try:
        res = compiler.optimize(mlp_symbol())
        assert res.annotations.get("quant_ready", 0) > 0
    finally:
        passes_mod._ANNOTATORS.remove(annot)


def test_graph_passes_kill_switch(monkeypatch):
    monkeypatch.setenv("MXTPU_GRAPH_PASSES", "0")
    sym = dup_branch_symbol()
    res = compiler.optimize(sym)
    assert res.symbol is sym and not res.changed and not res.annotations


# ---------------------------------------------------------------------------
# pass correctness: bitwise step equivalence vs un-passed graphs
# ---------------------------------------------------------------------------

def _module_params_after_steps(sym, steps=2, disable_passes=False,
                               fused=True, seed=7):
    if disable_passes:
        os.environ["MXTPU_GRAPH_PASSES"] = "0"
    try:
        rng = np.random.RandomState(0)
        batch = DataBatch(
            data=[mx.nd.array(rng.rand(4, 12).astype(np.float32))],
            label=[mx.nd.array(rng.randint(0, 4, (4,)).astype(np.float32))])
        mx.random.seed(seed)
        mod = mx.mod.Module(sym)
        mod.bind(data_shapes=[DataDesc("data", (4, 12))],
                 label_shapes=[DataDesc("softmax_label", (4,))])
        mod.init_params(mx.init.Xavier())
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": 0.5,
                                             "momentum": 0.9})
        if fused:
            from mxnet_tpu import perf
            stepper = perf.module_stepper(mod)
            assert stepper is not None
            for _ in range(steps):
                stepper.step(batch)
            stepper.sync_to_module()
        else:
            for _ in range(steps):
                mod.forward(batch, is_train=True)
                mod.backward()
                mod.update()
        arg, _ = mod.get_params()
        return {n: v.asnumpy() for n, v in arg.items()}
    finally:
        os.environ.pop("MXTPU_GRAPH_PASSES", None)


def test_module_step_bitwise_equal_with_and_without_passes():
    sym = dup_branch_symbol()       # CSE genuinely rewrites this graph
    assert compiler.optimize(sym).changed
    for fused in (True, False):
        passed = _module_params_after_steps(sym, fused=fused)
        unpassed = _module_params_after_steps(sym, disable_passes=True,
                                              fused=fused)
        assert passed.keys() == unpassed.keys()
        for n in passed:
            assert np.array_equal(passed[n], unpassed[n]), \
                f"{n} (fused={fused})"


def test_spmd_step_bitwise_equal_with_and_without_passes():
    import jax
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh

    rng = np.random.RandomState(0)
    x = rng.rand(8, 12).astype(np.float32)
    y = rng.randint(0, 4, (8,)).astype(np.float32)

    def run(disable_passes):
        if disable_passes:
            os.environ["MXTPU_GRAPH_PASSES"] = "0"
        try:
            mx.random.seed(21)
            mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
            tr = SPMDTrainer(dup_branch_symbol(), optimizer="sgd",
                             optimizer_params={"learning_rate": 0.1,
                                               "momentum": 0.9},
                             mesh=mesh)
            tr.bind(data_shapes={"data": (8, 12)},
                    label_shapes={"softmax_label": (8,)})
            for _ in range(2):
                tr.step({"data": x, "softmax_label": y})
            arg, _ = tr.get_params()
            return {n: v.asnumpy() for n, v in arg.items()}
        finally:
            os.environ.pop("MXTPU_GRAPH_PASSES", None)

    passed, unpassed = run(False), run(True)
    for n in passed:
        assert np.array_equal(passed[n], unpassed[n]), n


def test_gluon_step_bitwise_equal_with_and_without_passes():
    def run(disable_passes):
        if disable_passes:
            os.environ["MXTPU_GRAPH_PASSES"] = "0"
        try:
            mx.random.seed(11)
            np.random.seed(11)
            net = nn.Sequential(prefix="cmp_")
            with net.name_scope():
                net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
            net.initialize()
            tr = gluon.Trainer(net.collect_params(), "sgd",
                               {"learning_rate": 0.1, "momentum": 0.9})
            x = mx.nd.array(np.random.RandomState(3).rand(8, 12))
            y = mx.nd.array(np.random.RandomState(4).randint(0, 4, (8,)))
            loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
            for _ in range(2):
                with mx.autograd.record():
                    loss = loss_fn(net(x), y)
                loss.backward()
                tr.step(8)
            return {k: v.data().asnumpy()
                    for k, v in net.collect_params().items()}
        finally:
            os.environ.pop("MXTPU_GRAPH_PASSES", None)

    passed, unpassed = run(False), run(True)
    assert passed.keys() == unpassed.keys() and passed
    for k in passed:
        assert np.array_equal(passed[k], unpassed[k]), k


# ---------------------------------------------------------------------------
# persistent cache: roundtrip, corruption, faults, LRU, kill switch
# ---------------------------------------------------------------------------

def test_cache_roundtrip_and_stats(tmp_cache):
    store = CompilationCache(root=tmp_cache)
    key = "ab" + "0" * 62
    assert store.get(key) is None       # miss
    store.put(key, b"payload-bytes", meta={"kind": "test"})
    assert store.get(key) == b"payload-bytes"
    st = compiler.stats()["cache"]
    assert st["hits"] == 1 and st["misses"] == 1 and st["writes"] == 1


def test_cache_corrupt_entry_quarantined_and_recompiled(tmp_cache):
    store = CompilationCache(root=tmp_cache)
    key = "cd" + "1" * 62
    store.put(key, b"x" * 256)
    bin_path, man_path = store._paths(key)
    # flip a byte: digest mismatch -> invalidation -> miss, files gone
    with open(bin_path, "r+b") as f:
        f.seek(10)
        f.write(b"\xff")
    assert store.get(key) is None
    assert compiler.stats()["cache"]["invalidations"] == 1
    assert not os.path.exists(bin_path) and not os.path.exists(man_path)

    # truncated payload: same fallback
    store.put(key, b"y" * 256)
    with open(bin_path, "r+b") as f:
        f.truncate(100)
    assert store.get(key) is None
    assert compiler.stats()["cache"]["invalidations"] == 2

    # unreadable manifest: same fallback
    store.put(key, b"z" * 64)
    with open(man_path, "w") as f:
        f.write("{not json")
    assert store.get(key) is None
    assert compiler.stats()["cache"]["invalidations"] == 3


def test_cache_read_fault_site_falls_back_to_recompile(tmp_cache):
    """An injected fault at compiler.cache.read reads as a miss — the
    executor recompiles; the run NEVER fails on cache trouble."""
    store = CompilationCache(root=tmp_cache)
    key = "ef" + "2" * 62
    store.put(key, b"good")
    faults.arm(FaultPlan().arm("compiler.cache.read", nth=1, count=1,
                               exc="ioerror"))
    try:
        assert store.get(key) is None           # fault -> miss
        assert store.get(key) == b"good"        # next read recovers
        assert faults.stats()["fired"]["compiler.cache.read"] == 1
    finally:
        faults.disarm()
        faults.reset_stats()


def test_cache_fault_during_executor_bind_still_trains(tmp_cache):
    """End-to-end: arm the fault site, bind + step a module — the
    injected cache failure costs a recompile only."""
    faults.arm(FaultPlan().arm("compiler.cache.read", nth=1, count=2,
                               exc="ioerror"))
    try:
        params = _module_params_after_steps(mlp_symbol(), fused=False)
        assert all(np.isfinite(v).all() for v in params.values())
    finally:
        faults.disarm()
        faults.reset_stats()


def test_cache_lru_eviction_bounds_size(tmp_cache):
    store = CompilationCache(root=tmp_cache, max_bytes=300)
    keys = [f"{i:02d}" + str(i) * 62 for i in range(4)]
    for i, key in enumerate(keys):
        store.put(key, bytes(120))
    assert store.total_bytes() <= 300
    assert compiler.stats()["cache"]["evictions"] >= 2
    # newest entries survive
    assert store.get(keys[-1]) is not None
    assert store.get(keys[0]) is None


def test_cache_kill_switch(tmp_cache, monkeypatch):
    monkeypatch.setenv("MXTPU_COMPILE_CACHE", "0")
    import jax.numpy as jnp
    pj = compiler.PersistentJit(lambda x: x * 2, kind="t",
                                key_parts=("k",))
    out = pj(jnp.ones(3))
    assert np.allclose(np.asarray(out), 2.0)
    assert compiler.stats()["cache"]["writes"] == 0
    assert not os.path.exists(tmp_cache) or not any(os.scandir(tmp_cache))


def test_donated_programs_persist(tmp_cache):
    """Donated call sites (every training step) use the persistent
    store like any other program, and a warm load runs them."""
    import jax.numpy as jnp

    def f(xs):
        return [x + 1 for x in xs]

    def fresh():
        return compiler.PersistentJit(f, kind="donated", key_parts=("d",),
                                      donate_argnums=(0,))

    fresh()([jnp.ones(3)])
    assert compiler.stats()["cache"]["writes"] == 1
    (warm,) = fresh()([jnp.ones(3)])
    assert np.array_equal(np.asarray(warm), np.full(3, 2.0))
    assert compiler.stats()["programs"]["loaded"] == 1


def test_warm_load_keeps_the_programs_own_devices(tmp_cache):
    """A warm load hands deserialize_and_load the program's own device
    assignment: a one-device program compiled for device 3 of the
    8-device host loads back onto device 3 alone (without
    execution_devices it is spread over all 8 and dies on 'expected 8
    shards'), and a 4-device mesh program keeps its device order."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    def f(x):
        return x * 2 + 1

    devs = jax.devices()
    x1 = jax.device_put(jnp.arange(4.0), devs[3])
    mesh = Mesh(np.array(devs[4:8][::-1]), ("data",))
    x4 = jax.device_put(jnp.arange(8.0), NamedSharding(mesh, P("data")))
    for x in (x1, x4):
        compiler.PersistentJit(f, kind="devs", key_parts=("p",))(x)
    assert compiler.stats()["programs"]["compiled"] == 2
    for x in (x1, x4):
        warm = compiler.PersistentJit(f, kind="devs", key_parts=("p",))(x)
        assert np.array_equal(np.asarray(warm), np.asarray(x) * 2 + 1)
        assert warm.sharding.device_set == x.sharding.device_set
    st = compiler.stats()["programs"]
    assert st["loaded"] == 2 and st["invalid_load"] == 0, st


@pytest.fixture
def jax_cache_takes_everything(tmp_path):
    """JAX's own persistent cache in a tmp root, every compile written."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    was = {n: getattr(jax.config, n) for n in names}
    for n, v in zip(names, (str(tmp_path / "jax"), 0.0, -1)):
        jax.config.update(n, v)
    compilation_cache.reset_cache()
    yield
    for n, v in was.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()


def test_store_keeps_out_what_jax_cache_served(tmp_cache,
                                               jax_cache_takes_everything):
    """A compile that JAX's persistent cache answers hands back a
    deserialized executable; XLA:CPU serializes that again without its
    kernels ('Function <fusion> not found' when the reloaded entry
    runs). The store leaves such a program to JAX's cache."""
    import jax
    import jax.numpy as jnp

    def f(w, idx, x):
        e = jnp.transpose(jnp.take(w, idx, axis=0), (1, 0, 2)) * 2.0

        def step(c, t):
            c = jnp.tanh(c @ x + t)
            return c, c
        c, ys = jax.lax.scan(step, jnp.zeros((4, 16), jnp.float32), e)
        return ys.sum(0) + c

    rng = np.random.RandomState(0)
    args = (jnp.asarray(rng.rand(50, 16), jnp.float32),
            jnp.asarray(rng.randint(0, 50, (4, 7))),
            jnp.asarray(rng.rand(16, 16), jnp.float32))

    def run(key):
        jax.clear_caches()           # as a new process: nothing in memory
        return np.asarray(compiler.PersistentJit(
            f, kind="two-caches", key_parts=(key,))(*args))

    # one call site for every run: JAX's key takes the locations in
    # (configure_jax_cache), and the line a program is traced from is one
    got, seen = [], []
    for key in ("a", "b", "b", "a"):
        got.append(run(key))
        seen.append(compiler.stats())
    want = got[0]                    # fresh compile: both caches write
    assert seen[0]["cache"]["writes"] == 1
    for out in got[1:3]:             # same HLO, new store key: JAX answers
        assert np.array_equal(out, want)
    st = seen[2]
    assert st["programs"]["jax_cache_served"] == 2, st
    assert st["cache"]["writes"] == 1 and st["programs"]["loaded"] == 0, st
    assert np.array_equal(got[3], want)          # the store's own entry
    assert seen[3]["programs"]["loaded"] == 1


def test_store_keeps_what_jax_cache_served_where_it_can_be_rewritten(
        tmp_cache, jax_cache_takes_everything, monkeypatch):
    """Where an executable JAX's cache served may be serialized again (a
    TPU executable carries its code), the store writes it like a compiled
    one, so the next process loads it instead of tracing and lowering the
    program before JAX's cache answers; XLA:CPU is the one place it may
    not (the test above)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.compiler import aot
    monkeypatch.setattr(aot, "_rewritable", lambda compiled: True)

    def f(x):
        return jnp.tanh(x @ x) * 2.0

    x = jnp.asarray(np.random.RandomState(1).rand(8, 8), jnp.float32)
    seen = []
    for key in ("a", "b"):
        jax.clear_caches()
        compiler.PersistentJit(f, kind="rewritten", key_parts=(key,))(x)
        seen.append(compiler.stats())
    assert seen[0]["cache"]["writes"] == 1
    assert seen[1]["programs"]["jax_cache_served"] == 1, seen[1]
    assert seen[1]["cache"]["writes"] == 2, seen[1]


def test_persistent_jit_warm_load_skips_tracing(tmp_cache):
    import jax.numpy as jnp
    traces = [0]

    def make(key):
        def f(x):
            traces[0] += 1
            return x * 3 + 1
        return compiler.PersistentJit(f, kind="warm-test",
                                      key_parts=(key,))

    x = jnp.arange(4, dtype=jnp.float32)
    cold = make("samekey")
    r1 = np.asarray(cold(x))
    assert traces[0] == 1
    # a FRESH wrapper (fresh jit cache) over the same identity: the
    # executable loads from disk — the python body never runs again
    warm = make("samekey")
    r2 = np.asarray(warm(x))
    assert traces[0] == 1
    assert np.array_equal(r1, r2)
    st = compiler.stats()["programs"]
    assert st["compiled"] == 1 and st["loaded"] == 1


def test_persistent_jit_corrupt_executable_recompiles(tmp_cache):
    """An entry that passes the digest but holds garbage (not a
    serialized executable) is quarantined at load and recompiled."""
    import jax.numpy as jnp

    def f(x):
        return x - 5

    pj = compiler.PersistentJit(f, kind="garbage-test", key_parts=("g",))
    x = jnp.ones(3)
    # forge the exact key the wrapper will look up, with garbage bytes
    sig, canon = compiler.fingerprint.aval_signature((x,))
    key = compiler.program_key("garbage-test", "g", canon)
    compiler.default_cache().put(key, b"not-a-pickled-executable")
    out = np.asarray(pj(x))
    assert np.allclose(out, -4.0)
    st = compiler.stats()["programs"]
    assert st["compiled"] == 1
    assert compiler.stats()["programs"].get("invalid_load", 0) == 1


def test_executor_warm_start_across_processes(tmp_cache):
    """The acceptance contract: a second process running the same model
    records cache hits and compiles nothing."""
    prog = (
        "import json\n"
        "import numpy as np\n"
        "import mxnet_tpu as mx\n"
        "from mxnet_tpu import compiler\n"
        "from mxnet_tpu.io import DataDesc, DataBatch\n"
        "data = mx.sym.var('data')\n"
        "fc1 = mx.sym.FullyConnected(data, num_hidden=16, name='fc1')\n"
        "act = mx.sym.Activation(fc1, act_type='relu', name='relu1')\n"
        "fc2 = mx.sym.FullyConnected(act, num_hidden=4, name='fc2')\n"
        "net = mx.sym.SoftmaxOutput(fc2, mx.sym.var('softmax_label'),"
        " name='softmax')\n"
        "mod = mx.mod.Module(net)\n"
        "mod.bind(data_shapes=[DataDesc('data', (4, 12))],"
        " label_shapes=[DataDesc('softmax_label', (4,))])\n"
        "mod.init_params(mx.init.Xavier())\n"
        "batch = DataBatch(data=[mx.nd.array(np.ones((4, 12)))],"
        " label=[mx.nd.array(np.zeros(4))])\n"
        "mod.forward(batch, is_train=True)\n"
        "mod.backward()\n"
        "print(json.dumps(compiler.stats()))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MXTPU_COMPILE_CACHE_DIR=tmp_cache)

    def run():
        out = subprocess.run([sys.executable, "-c", prog], env=env,
                             capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr
        return json.loads(out.stdout.strip().splitlines()[-1])

    cold = run()
    assert cold["programs"]["compiled"] >= 2    # fwd + fwd_bwd
    assert cold["cache"]["hits"] == 0
    warm = run()
    assert warm["cache"]["hits"] >= 2
    assert warm["programs"]["loaded"] >= 2
    assert warm["programs"]["compiled"] == 0


# ---------------------------------------------------------------------------
# in-process program sharing (the executor satellite)
# ---------------------------------------------------------------------------

def test_executors_share_programs_by_fingerprint(tmp_cache):
    sym = mlp_symbol()
    shapes = dict(data=(4, 12), softmax_label=(4,))
    ex1 = sym.simple_bind(None, grad_req="write", **shapes)
    # no shared_exec threading — the fingerprint registry dedups anyway
    ex2 = sym.simple_bind(None, grad_req="write", **shapes)
    assert ex1._fwd is ex2._fwd
    assert ex1._fwd_bwd is ex2._fwd_bwd
    assert compiler.stats()["programs"]["shared"] >= 1
    # and reshape() keeps sharing through the same route
    ex3 = ex1.reshape(data=(8, 12), softmax_label=(8,))
    assert ex3._fwd is ex1._fwd


def test_placed_executor_reshape_keeps_identity_share(tmp_cache):
    """The ctx_group (placed) path is outside the fingerprint registry —
    reshape() must still reuse the per-group segment jits through the
    shared_exec identity route."""
    with mx.AttrScope(ctx_group="stage1"):
        data = mx.sym.var("data")
        fc1 = mx.sym.FullyConnected(data, num_hidden=16, name="pl_fc1")
        act1 = mx.sym.Activation(fc1, act_type="relu")
    with mx.AttrScope(ctx_group="stage2"):
        fc2 = mx.sym.FullyConnected(act1, num_hidden=4, name="pl_fc2")
        net = mx.sym.SoftmaxOutput(fc2, mx.sym.var("softmax_label"),
                                   name="softmax")
    g2c = {"stage1": mx.Context("cpu", 0), "stage2": mx.Context("cpu", 1)}
    ex = net.simple_bind(mx.cpu(), grad_req="write", group2ctx=g2c,
                         data=(8, 10), softmax_label=(8,))
    ex2 = ex.reshape(data=(4, 10), softmax_label=(4,))
    assert ex2._fwd is ex._fwd and ex2._fwd_bwd is ex._fwd_bwd
    out = ex2.forward(is_train=False,
                      data=mx.nd.array(np.ones((4, 10), np.float32)))
    assert out[0].shape == (4, 4)


def test_structurally_different_graphs_do_not_share(tmp_cache):
    shapes = dict(data=(4, 12), softmax_label=(4,))
    ex1 = mlp_symbol().simple_bind(None, grad_req="write", **shapes)
    ex2 = mlp_symbol(num_hidden=32).simple_bind(None, grad_req="write",
                                                **shapes)
    assert ex1._fwd is not ex2._fwd


def test_compiler_stats_shape():
    st = compiler.stats()
    assert set(st) == {"cache", "programs", "passes"}
    for k in ("hits", "misses", "invalidations", "writes", "evictions"):
        assert k in st["cache"]
    for k in ("compiled", "loaded", "bypassed", "shared"):
        assert k in st["programs"]
