"""Op kernels: Kimi delta attention's share of the chip's roofline: the
least time the chip could take for what the `kda` ops require a step
(the larger of FLOP over the bf16 peak and bytes over the HBM peak;
`kda_required` in the configuration's .py counts both, forward +
backward, nothing recomputed) over the device time measured under their
scopes (`device_ms.kda`, which includes the recomputed forward)."""
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

OPS = ("kda",)


def compute(run):
    ms = helper().ms_per_step(OPS)
    if ms is None:
        return None
    config, traffic, model = helper().cell_files()
    if not hasattr(model, "kda_required"):
        return None  # a configuration that counts no delta rule a channel
    return helper().roofline_pct(run, model.kda_required(config, traffic), ms)
