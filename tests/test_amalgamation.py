"""Amalgamation: the single-artifact predict library runs standalone.

Reference analogue: amalgamation/ building mxnet_predict-all.cc into a
lone predict lib. The test generates + compiles the artifact, then
drives it from a subprocess whose cwd is an empty temp dir with NO
MXTPU_REPO and the repo scrubbed from PYTHONPATH — the embedded
package zip inside the .so is the only source of mxnet_tpu code.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import mxnet_tpu as mx

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LIB = os.path.join(ROOT, "amalgamation", "libmxnet_predict-all.so")


@pytest.fixture(scope="module")
def amalgam_lib():
    # always rebuilt (2 s): the library embeds the package as it was when
    # it was built, and a stale one tests yesterday's code
    subprocess.run(
        [sys.executable,
         os.path.join(ROOT, "amalgamation", "amalgamation.py"),
         "--compile"], check=True, capture_output=True)
    return LIB


def test_amalgamation_standalone_predict(amalgam_lib, tmp_path):
    # build a checkpoint with the full framework (server side)
    rng = np.random.RandomState(0)
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.var("data"), num_hidden=3, name="fc"),
        name="softmax")
    W = rng.randn(3, 4).astype(np.float32)
    b = rng.randn(3).astype(np.float32)
    (tmp_path / "model-symbol.json").write_text(net.tojson())
    np.savez(tmp_path / "params.npz", **{"arg:fc_weight": W, "arg:fc_bias": b})
    os.rename(tmp_path / "params.npz", tmp_path / "model.params")
    x = rng.rand(2, 4).astype(np.float32)
    np.save(tmp_path / "x.npy", x)
    logits = x @ W.T + b
    expect = np.exp(logits - logits.max(1, keepdims=True))
    expect /= expect.sum(1, keepdims=True)
    np.save(tmp_path / "expect.npy", expect)

    # client side: empty cwd, no repo anywhere — only the .so
    driver = tmp_path / "driver.py"
    driver.write_text(textwrap.dedent("""
        import ctypes, sys
        import numpy as np
        lib = ctypes.CDLL(%r)
        lib.MXGetLastError.restype = ctypes.c_char_p
        u, vp = ctypes.c_uint, ctypes.c_void_p
        def ck(r):
            if r != 0:
                raise RuntimeError(lib.MXGetLastError().decode())
        sym = open("model-symbol.json").read().encode()
        params = open("model.params", "rb").read()
        x = np.load("x.npy")
        h = vp()
        keys = (ctypes.c_char_p * 1)(b"data")
        indptr = (u * 2)(0, 2)
        shp = (u * 2)(*x.shape)
        ck(lib.MXPredCreate(sym, params, len(params), 1, 0, 1, keys,
                            indptr, shp, ctypes.byref(h)))
        ck(lib.MXPredSetInput(h, b"data", x.ctypes.data_as(
            ctypes.POINTER(ctypes.c_float)), x.size))
        ck(lib.MXPredForward(h))
        out = np.zeros((x.shape[0], 3), np.float32)
        ck(lib.MXPredGetOutput(h, 0, out.ctypes.data_as(
            ctypes.POINTER(ctypes.c_float)), out.size))
        np.testing.assert_allclose(out, np.load("expect.npy"),
                                   rtol=1e-4, atol=1e-5)
        print("AMALGAM_OK")
    """ % str(amalgam_lib)))

    env = dict(os.environ)
    env.pop("MXTPU_REPO", None)
    env["MXTPU_PREDICT_PLATFORM"] = "cpu"
    # scrub the repo from PYTHONPATH but keep ambient site/plugin paths
    pp = [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
          if p and os.path.abspath(p) != ROOT]
    env["PYTHONPATH"] = os.pathsep.join(pp)
    proc = subprocess.run([sys.executable, str(driver)], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=560)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "AMALGAM_OK" in proc.stdout
    # an archive has no checkout to keep a compile cache in: the caches
    # are off there, not failing a write per program
    for complaint in ("compile cache write", "persistent compilation cache"):
        assert complaint not in proc.stderr, proc.stderr
