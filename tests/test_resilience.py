"""Fault-tolerant training runtime (mxnet_tpu/resilience/).

Proves the three pillars under deterministic fault injection:
crash-safe checkpoints (kill-mid-write, flipped-byte corruption),
retry/backoff (fake clock, zero real sleeps), and auto-resume
(``fit(resume='auto')`` matches an uninterrupted run bitwise on CPU).
"""
import json
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, resilience, sym
from mxnet_tpu.resilience import (CheckpointCorrupt, FaultPlan,
                                  InjectedFault, InjectedKill, RetryExhausted,
                                  RetryPolicy, checkpoint as rckpt, faults)


@pytest.fixture(autouse=True)
def _clean_faults():
    """Every test starts disarmed with fresh counters."""
    faults.disarm()
    resilience.reset_stats()
    yield
    faults.disarm()
    resilience.reset_stats()


def _mlp(nclass=4):
    data = sym.Variable("data")
    fc1 = sym.FullyConnected(data, name="fc1", num_hidden=16)
    act = sym.Activation(fc1, name="relu1", act_type="relu")
    fc2 = sym.FullyConnected(act, name="fc2", num_hidden=nclass)
    return sym.SoftmaxOutput(fc2, name="softmax")


def _blobs(n=200, nclass=4, dim=10, seed=0):
    rng = np.random.RandomState(seed)
    centers = rng.randn(nclass, dim) * 4
    X = np.zeros((n, dim), np.float32)
    y = np.zeros((n,), np.float32)
    for i in range(n):
        X[i] = centers[i % nclass] + rng.randn(dim) * 0.5
        y[i] = i % nclass
    return X, y


def _params(seed=0):
    rng = np.random.RandomState(seed)
    return ({"fc_weight": nd.array(rng.randn(3, 4).astype(np.float32)),
             "fc_bias": nd.array(np.zeros(3, np.float32))}, {})


def _net():
    return sym.FullyConnected(sym.Variable("data"), name="fc", num_hidden=3)


# -- retry policy (fake clock, no real sleeps) -------------------------------

def test_retry_backoff_schedule_with_fake_clock():
    now = [0.0]
    sleeps = []

    def sleep(s):
        sleeps.append(s)
        now[0] += s

    pol = RetryPolicy(max_retries=4, base_delay=0.1, max_delay=1.0,
                      multiplier=2.0, jitter=0.0, clock=lambda: now[0],
                      sleep=sleep)
    calls = [0]

    def flaky():
        calls[0] += 1
        if calls[0] <= 3:
            raise IOError("transient")
        return "ok"

    assert pol.call(flaky) == "ok"
    assert calls[0] == 4
    # exponential: 0.1, 0.2, 0.4 — capped at 1.0, no jitter
    np.testing.assert_allclose(sleeps, [0.1, 0.2, 0.4])


def test_retry_exhaustion_and_deadline():
    now = [0.0]
    pol = RetryPolicy(max_retries=2, base_delay=0.1, jitter=0.0,
                      clock=lambda: now[0],
                      sleep=lambda s: now.__setitem__(0, now[0] + s))

    def always_fails():
        raise IOError("down")

    with pytest.raises(RetryExhausted):
        pol.call(always_fails)

    # deadline: second retry would overrun the 0.25s budget
    now[0] = 0.0
    pol2 = RetryPolicy(max_retries=10, base_delay=0.1, jitter=0.0,
                       deadline=0.25, clock=lambda: now[0],
                       sleep=lambda s: now.__setitem__(0, now[0] + s))
    with pytest.raises(RetryExhausted, match="deadline"):
        pol2.call(always_fails)
    assert now[0] <= 0.25


def test_retry_fails_fast_on_permanent_oserror():
    pol = RetryPolicy(max_retries=5, sleep=lambda s: (_ for _ in ()).throw(
        AssertionError("must not sleep")))
    with pytest.raises(FileNotFoundError):
        pol.call(lambda: open("/nonexistent/nope/really", "rb"))


def test_retry_does_not_catch_non_transient():
    pol = RetryPolicy(max_retries=5, sleep=lambda s: (_ for _ in ()).throw(
        AssertionError("must not sleep")))

    def bad():
        raise ValueError("logic error")

    with pytest.raises(ValueError):
        pol.call(bad)


# -- fault plan --------------------------------------------------------------

def test_fault_plan_nth_call_is_deterministic():
    plan = FaultPlan(seed=3).arm("io.next", nth=2, exc="ioerror")
    faults.arm(plan)
    faults.fault_point("io.next")           # call 1: clean
    with pytest.raises(InjectedFault):
        faults.fault_point("io.next")       # call 2: fires
    faults.fault_point("io.next")           # call 3: clean again
    assert faults.stats()["fired"]["io.next"] == 1


def test_fault_plan_seeded_probability_reproducible():
    def trace(seed):
        faults.arm(FaultPlan(seed=seed).arm("x", prob=0.5))
        out = []
        for _ in range(20):
            try:
                faults.fault_point("x")
                out.append(0)
            except InjectedFault:
                out.append(1)
        return out

    assert trace(7) == trace(7)
    assert trace(7) != trace(8)


def test_fault_plan_from_env_spec():
    plan = FaultPlan.from_env("checkpoint.write:2:kill;kvstore.push:1", seed=0)
    assert plan.sites() == {"checkpoint.write", "kvstore.push"}
    faults.arm(plan)
    with pytest.raises(InjectedFault):
        faults.fault_point("kvstore.push")
    faults.fault_point("checkpoint.write")  # call 1 clean
    with pytest.raises(InjectedKill):
        faults.fault_point("checkpoint.write")


def test_num_dead_node_reports_armed_sites():
    kv = mx.kv.create("local")
    assert kv.num_dead_node() == 0
    faults.arm(FaultPlan().arm("kvstore.push", nth=99)
               .arm("checkpoint.write", nth=99))
    assert kv.num_dead_node() == 2
    faults.disarm()
    assert kv.num_dead_node() == 0


# -- atomic checkpoint + manifest --------------------------------------------

def test_kill_mid_write_leaves_last_good_checkpoint(tmp_path):
    prefix = str(tmp_path / "ck")
    net = _net()
    arg, aux = _params(seed=1)
    mx.model.save_checkpoint(prefix, 1, net, arg, aux)

    # the write of epoch 2 dies between tmp-write and rename
    faults.arm(FaultPlan().arm("checkpoint.write", nth=1, exc="kill",
                               count=99))
    arg2 = {k: v + 1.0 for k, v in arg.items()}
    with pytest.raises(InjectedKill):
        mx.model.save_checkpoint(prefix, 2, net, arg2, aux)
    faults.disarm()

    # epoch-1 checkpoint is intact and loads; epoch 2 never became visible
    assert not os.path.exists(prefix + "-0002.params")
    _, loaded, _ = mx.model.load_checkpoint(prefix, 1)
    np.testing.assert_array_equal(loaded["fc_weight"].asnumpy(),
                                  arg["fc_weight"].asnumpy())
    # discovery sees only the good epoch
    assert resilience.find_checkpoints(prefix) == [1]


def test_flipped_byte_rejected_and_falls_back(tmp_path, caplog):
    prefix = str(tmp_path / "ck")
    net = _net()
    arg, aux = _params(seed=1)
    mx.model.save_checkpoint(prefix, 1, net, arg, aux)
    arg2 = {k: v * 2.0 for k, v in arg.items()}
    mx.model.save_checkpoint(prefix, 2, net, arg2, aux)

    pfile = prefix + "-0002.params"
    blob = bytearray(open(pfile, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    open(pfile, "wb").write(bytes(blob))

    with pytest.raises(CheckpointCorrupt):
        rckpt.verify_manifest(prefix, 2)

    import logging
    with caplog.at_level(logging.WARNING):
        _, loaded, _ = mx.model.load_checkpoint(prefix, 2)
    np.testing.assert_array_equal(loaded["fc_weight"].asnumpy(),
                                  arg["fc_weight"].asnumpy())
    assert any("fell back" in r.message for r in caplog.records)


def test_manifest_contents_and_epochless_scheme(tmp_path):
    prefix = str(tmp_path / "ck")
    arg, aux = _params()
    # epoch-less save (Module.save naming scheme) also gets a manifest
    mx.model.save_checkpoint(prefix, None, _net(), arg, aux)
    assert os.path.exists(prefix + ".params")
    mpath = prefix + ".manifest.json"
    assert os.path.exists(mpath)
    doc = json.loads(open(mpath).read())
    assert doc["epoch"] is None
    assert set(doc["files"]) == {"symbol", "params"}
    for entry in doc["files"].values():
        assert len(entry["sha256"]) == 64 and entry["size"] > 0
    # discovery works across both naming schemes
    mx.model.save_checkpoint(prefix, 4, _net(), arg, aux)
    found = resilience.find_checkpoints(prefix)
    assert set(found) == {None, 4}
    # and a corrupt epoch-less file falls back to the numbered one
    blob = bytearray(open(prefix + ".params", "rb").read())
    blob[-1] ^= 0xFF
    open(prefix + ".params", "wb").write(bytes(blob))
    ep, _, _, _, _ = rckpt.load_checkpoint_ex(prefix, None)
    assert ep == 4


def test_find_checkpoints_orders_by_epoch_not_mtime(tmp_path):
    prefix = str(tmp_path / "ck")
    arg, aux = _params()
    mx.model.save_checkpoint(prefix, 3, _net(), arg, aux)
    # epoch 1 written later (e.g. restored from backup in copy order):
    # epoch number, not mtime, is the recency key
    mx.model.save_checkpoint(prefix, 1, _net(), arg, aux)
    assert resilience.find_checkpoints(prefix)[0] == 3


def test_missing_manifest_treated_as_torn_when_others_have_one(tmp_path):
    prefix = str(tmp_path / "ck")
    arg, aux = _params()
    mx.model.save_checkpoint(prefix, 1, _net(), arg, aux)
    arg2 = {k: v * 3.0 for k, v in arg.items()}
    mx.model.save_checkpoint(prefix, 2, _net(), arg2, aux)
    # simulate a writer killed between the params rename and the manifest
    # write: epoch-2 params visible, manifest absent -> torn, not legacy
    os.remove(prefix + "-0002.manifest.json")
    ep, _, loaded, _, _ = rckpt.load_checkpoint_ex(prefix, rckpt.AUTO)
    assert ep == 1
    np.testing.assert_array_equal(loaded["fc_weight"].asnumpy(),
                                  arg["fc_weight"].asnumpy())


def test_stale_states_file_not_paired_without_manifest_entry(tmp_path):
    prefix = str(tmp_path / "ck")
    arg, aux = _params()
    mx.model.save_checkpoint(prefix, 1, _net(), arg, aux, states=b"old-opt")
    # re-save without optimizer states: the stale .states stays on disk
    # but the fresh manifest no longer records it
    mx.model.save_checkpoint(prefix, 1, _net(), arg, aux)
    assert os.path.exists(prefix + "-0001.states")
    _, _, _, _, states = rckpt.load_checkpoint_ex(prefix, 1)
    assert states is None


def test_module_save_epochless_and_load(tmp_path):
    X, y = _blobs(n=80)
    it = mx.io.NDArrayIter(X, y, batch_size=20)
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.fit(it, optimizer="sgd", optimizer_params={"learning_rate": 0.1},
            num_epoch=1)
    prefix = str(tmp_path / "m")
    mod.save(prefix, save_optimizer_states=True)
    assert os.path.exists(prefix + ".params")
    assert os.path.exists(prefix + ".states")
    doc = json.loads(open(prefix + ".manifest.json").read())
    assert "states" in doc["files"]
    mod2 = mx.mod.Module.load(prefix, load_optimizer_states=True)
    a1, _ = mod.get_params()
    a2 = mod2._arg_params
    for k in a1:
        np.testing.assert_array_equal(a1[k].asnumpy(), a2[k].asnumpy())


def test_optimizer_states_write_is_atomic(tmp_path):
    kv = mx.kv.create("local")
    kv.set_optimizer(mx.optimizer.create("sgd", learning_rate=0.1))
    kv.init("3", nd.array(np.ones(4, np.float32)))
    kv.push("3", nd.array(np.ones(4, np.float32)))
    fname = str(tmp_path / "opt.states")
    kv.save_optimizer_states(fname)
    assert os.path.exists(fname)
    assert not os.path.exists(fname + ".tmp")
    # a kill during the states write must not clobber the existing file
    before = open(fname, "rb").read()
    faults.arm(FaultPlan().arm("checkpoint.write", nth=1, exc="kill"))
    kv.push("3", nd.array(np.full(4, 5.0, np.float32)))
    with pytest.raises(InjectedKill):
        kv.save_optimizer_states(fname)
    faults.disarm()
    assert open(fname, "rb").read() == before
    kv.load_optimizer_states(fname)


# -- retry wiring through kvstore and io -------------------------------------

def test_kvstore_push_retries_injected_fault(monkeypatch):
    # make the default policy sleepless for the test
    from mxnet_tpu.resilience import retry as rretry
    monkeypatch.setattr(rretry, "_default",
                        RetryPolicy(max_retries=3, base_delay=0.0,
                                    jitter=0.0, sleep=lambda s: None))
    faults.arm(FaultPlan().arm("kvstore.push", nth=1, exc="ioerror")
               .arm("kvstore.pull", nth=1, exc="timeout"))
    kv = mx.kv.create("local")
    kv.init("9", nd.array(np.full(3, 2.0, np.float32)))
    kv.push("9", nd.array(np.ones(3, np.float32)))      # retried through
    out = nd.array(np.zeros(3, np.float32))
    kv.pull("9", out=out)                                # retried through
    np.testing.assert_allclose(out.asnumpy(), np.ones(3))
    st = resilience.stats()
    assert st["retry"]["retries"]["kvstore.push"] == 1
    assert st["retry"]["retries"]["kvstore.pull"] == 1
    assert st["faults"]["fired"] == {"kvstore.push": 1, "kvstore.pull": 1}
    monkeypatch.setattr(rretry, "_default", None)


def test_kvstore_init_barrier_checkpoint_read_sites_retry(monkeypatch,
                                                          tmp_path):
    """The kvstore.init, kvstore.barrier and checkpoint.read fault sites
    ride the same retry/backoff path as push/pull (tpu-lint
    registry-consistency: every armed site must be exercised here)."""
    from mxnet_tpu.resilience import retry as rretry
    monkeypatch.setattr(rretry, "_default",
                        RetryPolicy(max_retries=3, base_delay=0.0,
                                    jitter=0.0, sleep=lambda s: None))
    faults.arm(FaultPlan().arm("kvstore.init", nth=1, exc="ioerror")
               .arm("kvstore.barrier", nth=1, exc="timeout")
               .arm("checkpoint.read", nth=1, exc="ioerror"))
    kv = mx.kv.create("local")
    kv.init("w", nd.array(np.ones(3, np.float32)))  # init site retried
    kv.barrier()                                    # barrier site retried
    out = nd.array(np.zeros(3, np.float32))
    kv.pull("w", out=out)
    np.testing.assert_allclose(out.asnumpy(), np.ones(3))
    blob = tmp_path / "state.bin"
    blob.write_bytes(b"payload")
    # checkpoint.read: first attempt faults, retry reads the real bytes
    assert rckpt.read_bytes_guarded(str(blob)) == b"payload"
    st = resilience.stats()
    assert st["retry"]["retries"]["kvstore.init"] == 1
    assert st["retry"]["retries"]["kvstore.barrier"] == 1
    assert st["retry"]["retries"]["checkpoint.read"] == 1
    assert st["faults"]["fired"] == {"kvstore.init": 1,
                                     "kvstore.barrier": 1,
                                     "checkpoint.read": 1}
    monkeypatch.setattr(rretry, "_default", None)


def test_data_iter_fetch_retries_and_stopiteration_passes(monkeypatch):
    from mxnet_tpu.resilience import retry as rretry
    monkeypatch.setattr(rretry, "_default",
                        RetryPolicy(max_retries=2, base_delay=0.0,
                                    jitter=0.0, sleep=lambda s: None))
    X, y = _blobs(n=40)
    it = mx.io.NDArrayIter(X, y, batch_size=20)
    faults.arm(FaultPlan().arm("io.next", nth=1, exc="ioerror"))
    batches = list(it)            # first fetch faults, is retried; ends clean
    assert len(batches) == 2
    assert resilience.stats()["retry"]["retries"]["io.next"] == 1
    monkeypatch.setattr(rretry, "_default", None)


def test_resilience_monitor_callback_logs_counters(caplog):
    import logging
    cb = mx.callback.ResilienceMonitor(frequent=1)
    faults.arm(FaultPlan().arm("io.next", nth=1, exc="ioerror"))
    with pytest.raises(InjectedFault):
        faults.fault_point("io.next")
    faults.disarm()
    param = mx.callback.BatchEndParam(epoch=0, nbatch=0, eval_metric=None,
                                      locals=None)
    with caplog.at_level(logging.WARNING):
        cb(param)
    assert cb.stats["faults"]["fired"] == {"io.next": 1}
    assert any("faults[io.next]=1" in r.message for r in caplog.records)


# -- auto-resume -------------------------------------------------------------

def _fit(mod, train_iter, num_epoch, **kw):
    mod.fit(train_iter, optimizer="adam",
            optimizer_params={"learning_rate": 0.01},
            initializer=mx.init.Xavier(), num_epoch=num_epoch, **kw)


def test_fit_auto_resume_matches_uninterrupted_run(tmp_path):
    X, y = _blobs()

    def make_iter():
        return mx.io.NDArrayIter(X, y, batch_size=50)

    # uninterrupted 4-epoch run
    np.random.seed(0)
    mx.random.seed(0)
    ref_mod = mx.mod.Module(_mlp(), context=mx.cpu())
    _fit(ref_mod, make_iter(), 4)
    ref = {k: v.asnumpy() for k, v in ref_mod.get_params()[0].items()}

    # same run "preempted" after epoch 2 (checkpointing each epoch) ...
    prefix = str(tmp_path / "run")
    np.random.seed(0)
    mx.random.seed(0)
    first = mx.mod.Module(_mlp(), context=mx.cpu())
    _fit(first, make_iter(), 2, checkpoint_prefix=prefix)

    # ... then auto-resumed in a fresh module: continues at epoch 2 and
    # lands on bitwise-identical final parameters (optimizer state +
    # update counters restored)
    resumed = mx.mod.Module(_mlp(), context=mx.cpu())
    _fit(resumed, make_iter(), 4, checkpoint_prefix=prefix, resume="auto")
    got = {k: v.asnumpy() for k, v in resumed.get_params()[0].items()}
    assert set(got) == set(ref)
    for k in ref:
        np.testing.assert_array_equal(ref[k], got[k], err_msg=k)


def test_fit_auto_resume_skips_corrupt_newest(tmp_path):
    X, y = _blobs(n=100)

    def make_iter():
        return mx.io.NDArrayIter(X, y, batch_size=50)

    prefix = str(tmp_path / "run")
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    _fit(mod, make_iter(), 3, checkpoint_prefix=prefix)
    # corrupt the newest checkpoint; resume must fall back to epoch 2
    pfile = prefix + "-0003.params"
    blob = bytearray(open(pfile, "rb").read())
    blob[len(blob) // 3] ^= 0x01
    open(pfile, "wb").write(bytes(blob))

    resumed = mx.mod.Module(_mlp(), context=mx.cpu())
    _fit(resumed, make_iter(), 3, checkpoint_prefix=prefix, resume="auto")
    # it resumed from epoch 2 and re-ran epoch 3, rewriting a valid ckpt
    rckpt.verify_manifest(prefix, 3)


def test_fit_auto_resume_fresh_start_when_no_checkpoint(tmp_path):
    X, y = _blobs(n=100)
    it = mx.io.NDArrayIter(X, y, batch_size=50)
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    _fit(mod, it, 1, checkpoint_prefix=str(tmp_path / "none"),
         resume="auto")   # no checkpoint on disk: trains from scratch
    assert os.path.exists(str(tmp_path / "none") + "-0001.params")


def test_fit_kill_mid_write_then_auto_resume_completes(tmp_path):
    """The acceptance scenario: a run killed between checkpoint rename
    boundaries resumes with fit(resume='auto') and reaches the same final
    parameters as an uninterrupted run of the same seed."""
    X, y = _blobs()

    def make_iter():
        return mx.io.NDArrayIter(X, y, batch_size=50)

    np.random.seed(0)
    mx.random.seed(0)
    ref_mod = mx.mod.Module(_mlp(), context=mx.cpu())
    _fit(ref_mod, make_iter(), 3)
    ref = {k: v.asnumpy() for k, v in ref_mod.get_params()[0].items()}

    prefix = str(tmp_path / "run")
    np.random.seed(0)
    mx.random.seed(0)
    victim = mx.mod.Module(_mlp(), context=mx.cpu())
    # epoch-1 checkpoint writes 4 files (symbol, params, states, and
    # since the iterator became checkpointable its iter.json) + manifest
    # = 5 passes of the checkpoint.write site; the kill fires during
    # epoch 2's checkpoint
    faults.arm(FaultPlan().arm("checkpoint.write", nth=6, exc="kill",
                               count=99))
    with pytest.raises(InjectedKill):
        _fit(victim, make_iter(), 3, checkpoint_prefix=prefix)
    faults.disarm()

    resumed = mx.mod.Module(_mlp(), context=mx.cpu())
    _fit(resumed, make_iter(), 3, checkpoint_prefix=prefix, resume="auto")
    got = {k: v.asnumpy() for k, v in resumed.get_params()[0].items()}
    for k in ref:
        np.testing.assert_array_equal(ref[k], got[k], err_msg=k)


# -- SPMDTrainer checkpoints -------------------------------------------------

def _trainer_and_batch():
    import jax

    from mxnet_tpu.parallel import SPMDTrainer, make_mesh
    net = _mlp()
    mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
    tr = SPMDTrainer(net, optimizer="sgd",
                     optimizer_params={"learning_rate": 0.1}, mesh=mesh)
    tr.bind(data_shapes={"data": (20, 10)},
            label_shapes={"softmax_label": (20,)})
    X, y = _blobs(n=20)
    return tr, {"data": X, "softmax_label": y}


def test_trainer_checkpoint_manifest_and_restore_latest(tmp_path):
    tr, batch = _trainer_and_batch()
    tr.step(batch)
    tr.save_checkpoint(str(tmp_path), step=1, epoch=1)
    tr.step(batch)
    tr.save_checkpoint(str(tmp_path), step=2, epoch=2)
    assert os.path.exists(str(tmp_path / "step_2" / "manifest.json"))
    w2 = np.asarray(tr.params["fc1_weight"])

    # corrupt the newest checkpoint: restore_latest falls back to step_1
    victim = None
    for root, _, names in os.walk(str(tmp_path / "step_2")):
        for n in names:
            if n != "manifest.json" and os.path.getsize(
                    os.path.join(root, n)) > 64:
                victim = os.path.join(root, n)
                break
        if victim:
            break
    blob = bytearray(open(victim, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    open(victim, "wb").write(bytes(blob))

    tr2, _ = _trainer_and_batch()
    restored = tr2.restore_latest(str(tmp_path))
    assert restored == 1
    assert tr2._num_update == 1
    assert not np.array_equal(np.asarray(tr2.params["fc1_weight"]), w2)


def test_trainer_fit_resume_continues_trajectory(tmp_path):
    X, y = _blobs(n=40)

    def make_iter():
        return mx.io.NDArrayIter(X, y, batch_size=20)

    # bind() draws initial params from mx.random's host RNG: seed it the
    # same way before the reference and the preempted run (tr_b's init is
    # irrelevant — the checkpoint overwrites it)
    mx.random.seed(0)
    tr_ref, _ = _trainer_and_batch()
    tr_ref.fit(make_iter(), num_epoch=4)
    ref = np.asarray(tr_ref.params["fc1_weight"])

    ckdir = str(tmp_path / "trainer")
    mx.random.seed(0)
    tr_a, _ = _trainer_and_batch()
    tr_a.fit(make_iter(), num_epoch=2, checkpoint_dir=ckdir)
    tr_b, _ = _trainer_and_batch()
    tr_b.fit(make_iter(), num_epoch=4, checkpoint_dir=ckdir, resume="auto")
    assert tr_b._num_update == tr_ref._num_update
    np.testing.assert_array_equal(np.asarray(tr_b.params["fc1_weight"]), ref)


def test_trainer_step_fault_site():
    tr, batch = _trainer_and_batch()
    faults.arm(FaultPlan().arm("trainer.step", nth=1, exc="ioerror"))
    with pytest.raises(InjectedFault):
        tr.step(batch)
    faults.disarm()
    tr.step(batch)  # recovers on the next step
    assert tr._num_update == 1
