"""Op kernels: the selective scan's share of the chip's roofline: the
least time the chip could take for what the op requires a step (the
larger of FLOP over the bf16 peak and bytes over the HBM peak;
`ssm_required` in the configuration's .py counts both, forward +
backward, nothing recomputed) over the device time measured under its
scopes (`device_ms.ssm`, which includes the recomputed forward)."""
import importlib.util
import os
import sys


def helper():
    """`benchmark/scope_union.py`, by path; one instance a process, so
    that every reader finds the one set of intervals the trace gave."""
    name = "_benchmark_scope_union"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "scope_union.py"))
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


helper().watch()

OPS = ("selective_scan",)


def compute(run):
    ms = helper().ms_per_step(OPS)
    if ms is None:
        return None
    config, traffic, model = helper().cell_files()
    if not hasattr(model, "ssm_required"):
        return None  # a configuration that counts no scan
    return helper().roofline_pct(run, model.ssm_required(config, traffic), ms)
