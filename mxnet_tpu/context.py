"""Device contexts mapped onto jax devices.

Reference: include/mxnet/base.h:141 ``Context`` (devtype/devid) and
python/mxnet/context.py (ctx scope :206). In the rebuild a Context names a
jax.Device; ``tpu`` is the first-class accelerator and ``gpu`` is accepted as
an alias for it so reference example scripts run unchanged.

Which device a Context names (``Context.jax_device``):

* ``tpu(i)``/``gpu(i)`` on a host with an accelerator is accelerator device
  ``i`` or an ``MXNetError`` — never another chip. On a host with none (the
  CPU test mesh) it maps onto CPU device ``i % n`` so reference ctx lists
  like ``[mx.gpu(i) for i in range(8)]`` run on the virtual devices.
* ``cpu(i)`` is a CPU device where JAX lists one; where the runtime lists
  only accelerators it is accelerator device ``i % n``, so host-staging code
  written against ``mx.cpu()`` keeps running.

Because of the two compat mappings a Context is not proof of where a number
was taken: programs that report one (chip_smoke.py, bench.py, benchmarks/)
check ``jax.devices()[0].platform`` up front and print it.
"""
from __future__ import annotations

import threading
from typing import Optional

import jax

from .base import MXNetError

__all__ = ["Context", "cpu", "gpu", "tpu", "current_context", "num_gpus", "num_tpus"]


def _jax_devices(device_type: str):
    devs = jax.devices()
    if device_type == "cpu":
        # accelerator-only runtime: see the module docstring
        return [d for d in devs if d.platform == "cpu"] or devs
    return [d for d in devs if d.platform != "cpu"]


class Context:
    """A device context. ``with Context('tpu', 0):`` sets the default."""

    _default = threading.local()
    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 4: "cpu_shared", 5: "tpu"}
    devstr2type = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "cpu_shared": 4, "tpu": 5}

    def __init__(self, device_type: str, device_id: int = 0):
        if isinstance(device_type, Context):
            device_type, device_id = device_type.device_type, device_type.device_id
        if device_type not in Context.devstr2type:
            raise MXNetError(f"unknown device type {device_type}")
        self.device_type = device_type
        self.device_id = device_id
        self._old = None

    @property
    def device_typeid(self) -> int:
        return Context.devstr2type[self.device_type]

    @property
    def jax_device(self) -> Optional[jax.Device]:
        if self.device_type in ("gpu", "tpu"):
            devs = _jax_devices("tpu")
            if devs:
                if not 0 <= self.device_id < len(devs):
                    raise MXNetError(
                        f"{self} requested, but this host has "
                        f"{len(devs)} accelerator device(s): {devs}")
                return devs[self.device_id]
        devs = _jax_devices("cpu")
        if not devs:
            return None
        return devs[self.device_id % len(devs)]

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    __str__ = __repr__

    def __enter__(self):
        self._old = getattr(Context._default, "ctx", None)
        Context._default.ctx = self
        return self

    def __exit__(self, *args):
        Context._default.ctx = self._old
        return False

    def empty_cache(self):
        """Reference: Storage pool release (src/storage/); XLA owns HBM here."""
        return None

    @staticmethod
    def default_ctx() -> "Context":
        ctx = getattr(Context._default, "ctx", None)
        if ctx is not None:
            return ctx
        return tpu(0) if num_tpus() > 0 else cpu(0)


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def gpu(device_id: int = 0) -> Context:
    """Accelerator context. Alias of tpu for reference-script compatibility."""
    return Context("gpu", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def current_context() -> Context:
    return Context.default_ctx()


def num_gpus() -> int:
    return len(_jax_devices("tpu"))


def num_tpus() -> int:
    return len(_jax_devices("tpu"))
