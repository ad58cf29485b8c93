#!/usr/bin/env python
"""Cold-start vs warm-start of the persistent compilation cache.

The metric pair the compiler layer exists for: ``compile_cold_start_s``
(fresh process, empty cache — bind + first fused step pays full
trace+XLA-compile) vs ``cache_warm_start_s`` (fresh process, warm cache
— the same programs deserialize from the executable store under
``JAX_COMPILATION_CACHE_DIR``). Each measurement is a REAL subprocess:
in-process jit caches cannot contaminate it, exactly like a serving cold
start or a ``resume='auto'`` relaunch.

This is a HOST measurement and says so (``"backend": "cpu"``): the
children are pinned to ``JAX_PLATFORMS=cpu``, because a chip belongs to
one process and two fresh processes cannot take turns on it inside a
parent that holds it. That is also why bench.py, which holds the chip,
does not run this: it is its own command. The chip's cold/warm compile
times are what ``chip_smoke.py`` prints per phase.

``python benchmarks/bench_compile_cache.py`` prints the record
(``value`` is ``cold/warm``); ``--child`` is the measured payload (used
by ci/compiler_smoke.py too). Both caches (JAX's and the executable
store) live under one fixed directory inside the checkout,
``.cache/bench-compile-cache``, emptied before the cold run.
"""
import json
import os
import shutil
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

CHILD_STEPS = 2


def child():
    """Measured payload: bind a micro LSTM module, run an inference
    forward (the serving cold-start program) and a training
    forward+backward (the ``resume='auto'`` program) — the default-on,
    always-cacheable executor programs. Prints ONE json line: seconds
    from model build to the synced end of step 2, plus the compiler
    stats snapshot (hits/misses/loads/compiles) the parent asserts on.
    """
    sys.path.insert(0, ROOT)
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import compiler
    from mxnet_tpu.io import DataBatch, DataDesc

    t0 = time.perf_counter()
    data = mx.sym.var("data")
    embed = mx.sym.Embedding(data, input_dim=40, output_dim=16,
                             name="embed")
    embed = mx.sym.SwapAxis(embed, dim1=0, dim2=1)
    stack = mx.rnn.FusedRNNCell(16, num_layers=2, mode="lstm",
                                prefix="lstm_")
    out, _ = stack.unroll(6, inputs=embed, merge_outputs=True,
                          layout="TNC")
    pred = mx.sym.Reshape(out, shape=(-1, 16))
    pred = mx.sym.FullyConnected(pred, num_hidden=40, name="pred")
    label = mx.sym.Reshape(mx.sym.var("softmax_label"), shape=(-1,))
    net = mx.sym.SoftmaxOutput(pred, label, name="softmax")
    mod = mx.mod.Module(net)
    mod.bind(data_shapes=[DataDesc("data", (4, 6))],
             label_shapes=[DataDesc("softmax_label", (4, 6))])
    mx.random.seed(7)
    mod.init_params(mx.init.Xavier())
    rng = np.random.RandomState(0)
    batch = DataBatch(
        data=[mx.nd.array(rng.randint(0, 40, (4, 6)).astype(np.float32))],
        label=[mx.nd.array(rng.randint(0, 40, (4, 6)).astype(np.float32))])
    for _ in range(CHILD_STEPS):
        mod.forward(batch, is_train=False)      # serving program
        mod.forward(batch, is_train=True)       # training program
        mod.backward()
    float(mod.get_outputs()[0].asnumpy().ravel()[0])    # host-read sync
    ready_s = time.perf_counter() - t0
    print(json.dumps({"ready_s": round(ready_s, 4),
                      "stats": compiler.stats()}))


def empty_cache_dir():
    """The fixed, emptied cache root of one cold->warm experiment."""
    root = os.path.join(ROOT, ".cache", "bench-compile-cache")
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root)
    return root


def run_child(cache_dir, extra_env=None):
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=cache_dir,
               MXTPU_RETRACE_STRICT="1")
    env.pop("MXTPU_COMPILE_CACHE_DIR", None)    # the store follows the root
    env.pop("XLA_FLAGS", None)      # one CPU device is plenty and fast
    env.update(extra_env or {})
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child"],
        env=env, capture_output=True, text=True, timeout=560)
    if out.returncode != 0:
        raise RuntimeError(f"compile-cache child failed:\n{out.stderr}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def run(quiet=False):
    """Two cold->warm child runs; returns the record."""
    cache_dir = empty_cache_dir()
    cold = run_child(cache_dir)
    warm = run_child(cache_dir)
    cold_s = float(cold["ready_s"])
    warm_s = float(warm["ready_s"])
    rec = {
        "metric": "cache_warm_speedup",
        "value": round(cold_s / warm_s, 3) if warm_s else 0.0,
        "unit": "x",
        "backend": "cpu",
        "compile_cold_start_s": round(cold_s, 4),
        "cache_warm_start_s": round(warm_s, 4),
        "cold_compiles": cold["stats"]["programs"]["compiled"],
        "warm_loads": warm["stats"]["programs"]["loaded"],
        "warm_hits": warm["stats"]["cache"]["hits"],
        "warm_compiles": warm["stats"]["programs"]["compiled"],
    }
    if not quiet:
        print(json.dumps(rec))
    return rec


if __name__ == "__main__":
    if "--child" in sys.argv:
        child()
    else:
        run()
