"""What the program records of its own set-up (``mxnet_tpu.profiler``):
every materialization of a program as one ``compile.materialize`` span over
a child a phase, what JAX compiles beside them, ``bind`` and its stages under
both front ends, the iterators' construction and the import; and that a
steady step records none of it."""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import compiler, profiler

PHASES = ["compile.store_get", "compile.lower", "compile.backend",
          "compile.op_map", "compile.store_put"]


@pytest.fixture
def tmp_cache(tmp_path, monkeypatch):
    root = str(tmp_path / "executables")
    monkeypatch.setenv("MXTPU_COMPILE_CACHE_DIR", root)
    compiler.reset_stats()
    yield root
    compiler.reset_stats()


def since():
    return time.perf_counter_ns()


def by_name(found, name):
    return [s for s in found if s.name == name]


def children(found, parent):
    return [s for s in found if s.parent == parent.seq]


def ns(span):
    return span.end_ns - span.start_ns


def compile_counts():
    return {k: v for k, v in profiler.counters().items()
            if k.startswith("compile.")}


def grew(before):
    now = compile_counts()
    return {k: now[k] - before.get(k, 0) for k in now
            if now[k] != before.get(k, 0)}


def program(kind, body=lambda x: x * 3 + 1):
    return compiler.PersistentJit(body, kind=kind, key_parts=(kind,))


# -- materialization ---------------------------------------------------------

def test_an_empty_store_compiles_under_one_span_whose_phases_add_up(
        tmp_cache):
    t0, counts = since(), compile_counts()
    assert np.allclose(program("setup-cold")(jnp.ones(4)), 4.0)
    found = profiler.spans(t0)
    whole, = by_name(found, "compile.materialize")
    assert whole.args["kind"] == "setup-cold"
    assert whole.args["source"] == "compiled"
    assert whole.args["cause"] == "first"
    assert len(whole.args["key"]) == 12 and whole.args["sig"]
    assert "invalid_load" not in whole.args
    assert [s.name for s in children(found, whole)] == PHASES
    phase = {s.name: s for s in children(found, whole)}
    assert phase["compile.store_get"].args == {"bytes": 0}
    assert phase["compile.store_put"].args["bytes"] > 0
    assert phase["compile.op_map"].args["instructions"] >= \
        phase["compile.op_map"].args["named"] >= 0
    # what JAX reports of its own phases lies under ours, once each
    assert [s.name for s in children(found, phase["compile.lower"])] == [
        "jax.trace", "jax.lower"]
    backend, = children(found, phase["compile.backend"])
    assert backend.name == "jax.backend_compile"
    assert "setup_cold" in backend.args["fun"] or backend.args["fun"]
    # self times over the whole tree add up to the span: nothing twice
    tree, own = [whole], {}
    for s in found:                     # by start: a cause before its effect
        if s.parent in {t.seq for t in tree}:
            tree.append(s)
    for s in tree:
        own[s.seq] = own.get(s.seq, 0) + ns(s)
        if s is not whole:
            own[s.parent] -= ns(s)
    assert len(tree) == 9 and min(own.values()) >= 0
    assert sum(own.values()) == ns(whole)
    by_self = profiler.self_totals(t0)
    assert by_self["compile.materialize"] == own[whole.seq]
    assert by_self["compile.lower"] == own[phase["compile.lower"].seq]
    assert grew(counts) == {"compile.materialized": 1, "compile.compiled": 1}


def test_a_second_instance_over_the_same_store_loads(tmp_cache):
    program("setup-warm")(jnp.ones(4))
    t0, counts = since(), compile_counts()
    assert np.allclose(program("setup-warm")(jnp.ones(4)), 4.0)
    found = profiler.spans(t0)
    whole, = by_name(found, "compile.materialize")
    assert whole.args["source"] == "loaded" and whole.args["cause"] == "first"
    assert [s.name for s in children(found, whole)] == [
        "compile.store_get", "compile.load"]
    got, _ = children(found, whole)
    assert got.args["bytes"] > 0
    assert not [s for s in found if s.name.startswith("jax.")]
    assert grew(counts) == {"compile.materialized": 1, "compile.loaded": 1}
    cold, = [s for s in profiler.spans()
             if s.name == "compile.materialize"
             and s.args["kind"] == "setup-warm"
             and s.args["source"] == "compiled"]
    assert cold.args["key"] == whole.args["key"]


def test_a_drifted_shape_is_a_new_signature(tmp_cache):
    run = program("setup-drift")
    run(jnp.ones(4))
    run(jnp.ones(4))                    # the same program: no span
    t0 = since()
    run(jnp.ones(5))
    whole, = by_name(profiler.spans(t0), "compile.materialize")
    assert whole.args["cause"] == "new_signature"
    assert whole.args["source"] == "compiled"
    first, = [s for s in profiler.spans()
              if s.name == "compile.materialize"
              and s.args["kind"] == "setup-drift"
              and s.args["cause"] == "first"]
    assert first.args["key"] != whole.args["key"]


def test_a_corrupted_entry_is_thrown_out_first(tmp_cache):
    x = jnp.ones(3)
    _, canon = compiler.fingerprint.aval_signature((x,))
    key = compiler.program_key("setup-garbage", "setup-garbage", canon)
    compiler.default_cache().put(key, b"not-a-pickled-executable")
    t0 = since()
    assert np.allclose(program("setup-garbage")(x), 4.0)
    found = profiler.spans(t0)
    whole, = by_name(found, "compile.materialize")
    assert whole.args["invalid_load"] is True
    assert whole.args["source"] == "compiled"
    assert [s.name for s in children(found, whole)] == [
        "compile.store_get", "compile.load"] + PHASES[1:]


def test_the_counters_say_what_compiler_stats_says(tmp_cache):
    counts = compile_counts()
    program("setup-count")(jnp.ones(4))             # compiled
    program("setup-count")(jnp.ones(4))             # loaded
    program("setup-count")(jnp.ones((2, 2)))        # compiled
    stats = compiler.stats()["programs"]
    got = grew(counts)
    assert got["compile.loaded"] == stats["loaded"] == 1
    assert got["compile.compiled"] + got.get("compile.jax_cache_served", 0) \
        == stats["compiled"] == 2
    assert got["compile.materialized"] == stats["compiled"] + stats["loaded"]


def test_a_compile_that_fails_is_bypassed_and_says_so(tmp_cache):
    def body(x):
        raise ValueError("no such program")

    t0, counts = since(), compile_counts()
    with pytest.raises(ValueError):
        program("setup-bypass", body)(jnp.ones(2))
    whole, = by_name(profiler.spans(t0), "compile.materialize")
    assert whole.args["source"] == "bypassed"
    assert grew(counts) == {}       # no executable was made


# -- what JAX compiles beside them -------------------------------------------

def test_a_plain_jit_is_heard_with_its_name_and_only_its_outermost_phase():
    @jax.jit
    def setup_inner(x):
        return x + 1

    @jax.jit
    def setup_outer(x):
        return setup_inner(x) * setup_inner(x + 2)

    x = jnp.ones(3).block_until_ready()     # its own program: made before
    t0 = since()
    with profiler.span("t.caller") as caller:
        setup_outer(x).block_until_ready()
    heard = [s for s in profiler.spans(t0) if s.name.startswith("jax.")]
    found = [s for s in heard if "setup_outer" in s.args["fun"]]
    assert [s.name for s in found] == ["jax.trace", "jax.lower",
                                       "jax.backend_compile"]
    # the inner function's traces lie inside the outer one's: no record
    assert found[0].args["fun"] == "setup_outer"
    assert not [s for s in heard if "setup_inner" in s.args["fun"]]
    assert {s.parent for s in found} == {caller.seq}
    for s in found:
        assert s.thread == threading.get_ident() and ns(s) > 0
    t1 = since()
    setup_outer(x).block_until_ready()      # nothing new to make
    assert not [s for s in profiler.spans(t1) if s.name.startswith("jax.")]


# -- bind --------------------------------------------------------------------

def mlp():
    data = mx.sym.var("data")
    net = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    net = mx.sym.Activation(net, act_type="relu", name="relu1")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


# fc1 8 x 10 + 8, fc2 4 x 8 + 4, float32
MLP_BYTES = 4 * (80 + 8 + 32 + 4)


def feed(rows=32, batch=8):
    rng = np.random.RandomState(0)
    return mx.io.PrefetchingIter(mx.io.NDArrayIter(
        rng.rand(rows, 10).astype(np.float32),
        rng.randint(0, 4, rows).astype(np.float32), batch_size=batch))


def trainer(momentum=0.9):
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh
    tr = SPMDTrainer(mlp(), optimizer="sgd",
                     optimizer_params={"learning_rate": 0.1,
                                       "momentum": momentum},
                     mesh=make_mesh({"data": 1}, devices=jax.devices()[:1]))
    return tr.bind(data_shapes={"data": (8, 10)},
                   label_shapes={"softmax_label": (8,)})


def test_spmd_bind_is_one_span_over_plan_params_and_state():
    t0 = since()
    before = profiler.counters().get("bind.param_bytes", 0)
    trainer()
    found = profiler.spans(t0)
    whole, = by_name(found, "bind")
    assert whole.args == {"front": "spmd"}
    stages = [s for s in children(found, whole) if s.name.startswith("bind.")]
    assert [s.name for s in stages] == ["bind.plan", "bind.params",
                                        "bind.state"]
    assert stages[1].args == {"bytes": MLP_BYTES, "leaves": 4}
    assert stages[2].args == {"leaves": 4}      # one momentum a parameter
    assert profiler.counters()["bind.param_bytes"] - before == MLP_BYTES
    assert sum(ns(s) for s in stages) <= ns(whole)


def test_module_bind_names_its_stages():
    mod = mx.mod.Module(mlp())
    t0 = since()
    mod.bind(data_shapes=[("data", (8, 10))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params()
    mod.init_optimizer(optimizer="sgd", optimizer_params={
        "learning_rate": 0.1, "momentum": 0.9})
    found = profiler.spans(t0)
    binds = by_name(found, "bind")
    assert [s.args for s in binds] == [
        {"front": "module", "stage": stage}
        for stage in ("bind", "init_params", "init_optimizer")]

    def stages(whole):
        return [s for s in children(found, whole)
                if s.name.startswith("bind.")]

    plan, = stages(binds[0])
    params, = stages(binds[1])
    state, = stages(binds[2])
    assert (plan.name, params.name, state.name) == (
        "bind.plan", "bind.params", "bind.state")
    assert params.args == {"bytes": MLP_BYTES, "leaves": 4}
    assert state.args == {"leaves": 0}          # no state loaded before
    # the step program and the state it holds come at the first step
    t1 = since()
    assert mod._fused_train_step() is not None
    found = profiler.spans(t1)
    last, = by_name(found, "bind")
    assert last.args == {"front": "module", "stage": "fused_step"}
    plan, state = stages(last)
    assert (plan.name, state.name) == ("bind.plan", "bind.state")
    assert state.args["leaves"] >= 4
    # a second bind is ignored, and records nothing
    t2 = since()
    mod.bind(data_shapes=[("data", (8, 10))],
             label_shapes=[("softmax_label", (8,))])
    assert not by_name(profiler.spans(t2), "bind")


# -- the iterators and the import --------------------------------------------

def test_an_ndarrayiter_records_its_construction_with_the_sources_bytes():
    data = np.zeros((32, 10), np.float32)
    label = np.zeros((32,), np.float32)
    t0 = since()
    before = profiler.counters().get("input.construct_bytes", 0)
    inner = mx.io.NDArrayIter(data, label, batch_size=8)
    made, = by_name(profiler.spans(t0), "input.construct")
    assert made.args == {"bytes": data.nbytes + label.nbytes}
    assert profiler.counters()["input.construct_bytes"] - before == \
        data.nbytes + label.nbytes
    t1 = since()
    mx.io.PrefetchingIter(inner)
    wrapped, = by_name(profiler.spans(t1), "input.construct")
    assert wrapped.args == {"bytes": 0}


def test_the_import_is_one_span_recorded_at_its_end():
    count, total = profiler.totals()["import.mxnet_tpu"]
    assert count == 1 and total > 0
    found = by_name(profiler.spans(), "import.mxnet_tpu")
    if found:                           # unless the ring has turned since
        assert found[0].parent == -1 and ns(found[0]) == total


# -- a steady step -----------------------------------------------------------

@pytest.mark.parametrize("front", ["spmd", "module"])
def test_a_steady_fit_records_no_span_of_set_up(front, tmp_cache):
    spent = []

    def callback(param):
        spent.append(since())

    if front == "spmd":
        tr = trainer()
        tr.fit(feed(), num_epoch=1)     # the first steps: compiled
        run = lambda: tr.fit(feed(rows=160), num_epoch=1,   # noqa: E731
                             batch_end_callback=callback)
    else:
        mod = mx.mod.Module(mlp())
        mod.fit(feed(), num_epoch=1, optimizer="sgd",
                optimizer_params={"learning_rate": 0.1})
        run = lambda: mod.fit(feed(rows=160), num_epoch=1,  # noqa: E731
                              batch_end_callback=callback)
    it_made = since()
    run()
    assert len(spent) == 20
    # from the first batch's end to the last's: the iterator of this fit
    # was made before, and so was everything fit does once; what an epoch's
    # end does (Module.fit puts the parameters back: a ``bind``) comes after
    found = [s for s in profiler.spans(spent[0], spent[-1])
             if s.start_ns >= spent[0] and s.end_ns <= spent[-1]]
    assert it_made < spent[0]
    assert not [s.name for s in found
                if s.name.startswith(("compile.", "bind", "jax.", "import.",
                                      "input.construct"))]
    mine = [s for s in found if s.thread == threading.get_ident()]
    assert len(by_name(mine, "fit.step")) == 19
    assert len(mine) <= 10 * 19          # at most 10 a step, as before
