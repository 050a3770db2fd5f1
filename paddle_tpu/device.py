"""paddle.device 2.0-preview (reference: python/paddle/device.py —
set_device/get_device/is_compiled_with_*)."""
from __future__ import annotations

from .fluid import core

__all__ = ["set_device", "get_device", "is_compiled_with_cuda",
           "is_compiled_with_tpu", "TPUPlace", "CPUPlace"]

from .fluid.core import TPUPlace, CPUPlace

# Resolved on first use, not at import: importing paddle_tpu must not
# initialize a backend.
_current = None
_current_idx = 0


def _default_device() -> str:
    global _current
    if _current is None:
        _current = "tpu" if core.is_compiled_with_tpu() else "cpu"
    return _current


def set_device(device: str):
    """'tpu', 'tpu:0', 'cpu' (reference accepts 'gpu:N')."""
    global _current, _current_idx
    kind = device.split(":")[0]
    idx = int(device.split(":")[1]) if ":" in device else 0
    if kind in ("tpu", "gpu", "cuda"):
        if not core.is_compiled_with_tpu():
            raise RuntimeError("no TPU backend available")
        _current, _current_idx = "tpu", idx
        return TPUPlace(idx)
    if kind == "cpu":
        _current, _current_idx = "cpu", 0
        return CPUPlace()
    raise ValueError(f"unknown device {device!r}")


def get_device() -> str:
    cur = _default_device()
    return cur + (f":{_current_idx}" if cur != "cpu" else "")


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return core.is_compiled_with_tpu()
