"""The device gate and the table of peaks.

The benchmark measures the chip and nothing else: a run whose first JAX
device is not a TPU, or that finds fewer chips than its cell asks for, or a
chip that is not in :data:`PEAKS`, stops before any work and prints no
result."""

from __future__ import annotations

__all__ = ["PEAKS", "PEAKS_SOURCE", "DeviceGateError", "gate", "describe",
           "memory_peak_bytes"]

#: Published peaks per ``device_kind``.
PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,          # bf16 (matrix unit)
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}
PEAKS_SOURCE = ("Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
                "819 GB/s HBM bandwidth, 16 GB HBM per chip")


class DeviceGateError(RuntimeError):
    """No chip, too few chips, or a chip without published peaks."""


def gate(chips: int):
    """The local devices, once they pass the gate."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise DeviceGateError(
            f"JAX found no TPU (first device is {devs[0].platform}); nothing was run")
    if len(devs) < chips:
        raise DeviceGateError(f"the cell needs {chips} chips, JAX found {len(devs)}")
    if devs[0].device_kind not in PEAKS:
        raise DeviceGateError(
            f"no published peaks for {devs[0].device_kind!r} in perfbench's table")
    return devs


def describe(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(devs) -> int:
    """Peak bytes in use on the fullest device (0 where not reported)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs]
    return int(max(peaks))
