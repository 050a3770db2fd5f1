"""The chip a measurement needs, and its published peak — the one table
`bench.py` and `tools/mfu_report.py` divide by.

Keyed by the `device_kind` JAX reports. A device that is not in the
table is an error, never a default: an MFU against the wrong peak is a
wrong number with a right-looking name."""
from __future__ import annotations

# Google Cloud documentation, "TPU v5e" (system architecture): 197 TFLOP/s
# bf16 per chip. jax reports that chip as device_kind "TPU v5 lite".
BF16_PEAK_FLOPS = {"TPU v5 lite": 197e12}


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
    try:
        return BF16_PEAK_FLOPS[device.device_kind]
    except KeyError:
        raise SystemExit(
            f"no published bf16 peak for device_kind "
            f"{device.device_kind!r}: add it to tools/device_peaks.py "
            f"with its source") from None
