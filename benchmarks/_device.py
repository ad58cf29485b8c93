"""The device a benchmark ran on: refuse the CPU, name the chip, know its peak.

A timing taken on the CPU backend says nothing about the chip, so every
script here that reports a device number calls :func:`require_chip` before
it measures (it raises where JAX found no accelerator — no fallback) and
puts the returned stamp into what it prints.

One process drives the chip: a process that has touched JAX holds it, and a
child that needs it then fails or hangs. Nothing that calls
:func:`require_chip` may spawn a JAX child afterwards.
"""
import jax

# Published per-chip peaks, keyed by ``jax.Device.device_kind``. A kind that
# is not here is an error, not a default.
PEAKS = {
    "TPU v5 lite": {
        "bf16_tflops": 197.0,
        "source": 'Google Cloud documentation, "TPU v5e"',
    },
}


def device_stamp() -> dict:
    """``platform`` / ``device_kind`` / ``count`` as JAX reports them."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
            "count": len(devs)}


def require_chip() -> dict:
    """:func:`device_stamp`, or ``SystemExit`` when there is no accelerator."""
    stamp = device_stamp()
    if stamp["platform"] == "cpu":
        raise SystemExit(
            "no accelerator: jax.devices() is %s — this benchmark reports "
            "device numbers and does not time the CPU" % (jax.devices(),))
    return stamp


def peak_bf16_tflops(device_kind: str) -> float:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peak for device_kind {device_kind!r}; add it to "
            f"benchmarks/_device.py PEAKS with its source "
            f"(known: {sorted(PEAKS)})")
    return PEAKS[device_kind]["bf16_tflops"]
