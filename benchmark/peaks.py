"""Published peaks of the chips the benchmark may run on: the one table
every utilization in the benchmark divides by.

Keyed by the `device_kind` JAX reports. A device that is not in the
table is an error, never a default: an MFU against the wrong peak is a
wrong number with a right-looking name. (A copy of what
`tools/device_peaks.py` holds: the yardstick lives with the benchmark,
where a PR that changes the program cannot move it.)"""

# Google Cloud documentation, "TPU v5e" (system architecture): per chip
# 197 TFLOP/s in bf16, 16 GB of HBM at 819 GB/s. A multiply-add counts
# as two operations. JAX reports that chip as device_kind "TPU v5 lite".
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peak(device_kind: str, what: str) -> float:
    """The published peak `what` of one chip of `device_kind`."""
    try:
        return PEAKS[device_kind][what]
    except KeyError:
        raise SystemExit(
            f"no published {what} for device_kind {device_kind!r}: add it "
            f"to benchmark/peaks.py with its source") from None
