"""Op kernels: milliseconds a step in the three flash-attention kernels,
`flash_fwd` + `flash_bwd_dkv` + `flash_bwd_dq`, by the names their
`pallas_call`s carry (the parts and their calls a step are in the
`scopes` line, under `kernels`)."""
import importlib.util
import os
import sys


def shared():
    """`benchmark/trace_scopes.py`, by path; one instance a process, so
    that every reader finds the one reduction the harness's trace got."""
    name = "_benchmark_trace_scopes"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "trace_scopes.py"))
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


shared().watch()

KERNELS = ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq")


def compute(run):
    scopes = shared().last()
    if not scopes or not scopes["chips"]:
        return None
    found = [scopes["kernels"][k]["s"] for k in KERNELS
             if k in scopes["kernels"]]
    if not found:
        return None  # no flash kernel ran, or they carry no name yet
    return sum(found) / scopes["steps"] * 1e3
