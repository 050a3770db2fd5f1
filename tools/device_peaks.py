"""The chip a measurement needs, and its published peak.

The peaks are `benchmark/peaks.py`'s table, read and never copied: the
yardstick lives with the benchmark. A device that is not in that table
is an error, never a default: an MFU against the wrong peak is a wrong
number with a right-looking name."""
from __future__ import annotations

import importlib.util
import os

_PEAKS_PY = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "peaks.py")


def _benchmark_peaks():
    """`benchmark/peaks.py`, by its path: `benchmark/` is no package."""
    spec = importlib.util.spec_from_file_location(
        "_benchmark_peaks_py", _PEAKS_PY)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def require_tpu(what: str):
    """``jax.devices()[0]`` if it is a TPU; otherwise stop. A lane that
    reports a per-chip rate, a device time or an MFU has nothing to
    report from another backend, and must not time the CPU under a
    device metric's name."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"{what}: needs a TPU, jax.devices()[0] is {dev.platform!r} "
            f"({dev.device_kind}) — run it through the chip tool")
    return dev


def device_stamp() -> dict:
    """What every printed result says about where it ran."""
    import jax
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def bf16_peak_flops(device) -> float:
    return _benchmark_peaks().peak(device.device_kind, "bf16_flops_per_s")
