"""Test configuration: run the suite on a virtual 8-device CPU mesh.

Mirrors the reference's device-agnostic test strategy (SURVEY.md §4:
``default_context()`` switchable, model-parallel tests on two CPU contexts) —
multi-chip sharding is validated on virtual CPU devices; the real TPU chip is
exercised by chip_smoke.py. The compile caches stay where ``import
mxnet_tpu`` puts them (``<checkout>/.cache/jax``); tests of the executable
store itself take ``tmp_path`` roots.
"""
import os

# must be set before jax is imported anywhere in the test process
if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

assert jax.devices()[0].platform == "cpu", jax.devices()

# XLA:CPU's default matmul precision is bf16-like (~2e-3 error) which breaks
# finite-difference gradient checks; tests run at full precision (the bench
# path explicitly opts into bfloat16 on the MXU instead)
jax.config.update("jax_default_matmul_precision", "highest")

# float64 available in tests (reference numeric checks cross-validate against
# fp64; NDArray still defaults new arrays to float32)
jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seed():
    np.random.seed(0)
    import mxnet_tpu as mx
    mx.random.seed(0)
    yield
