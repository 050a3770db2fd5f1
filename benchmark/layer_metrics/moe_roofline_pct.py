"""Op kernels: the routed expert layer's share of the chip's roofline:
the least time the chip could take for what router + held experts
require a step (the larger of FLOP over the bf16 peak and bytes over
the HBM peak; `moe_required` in the configuration's .py counts both,
forward + backward, the expected routed rows and no padded one) over
the device time measured under their scopes (`device_ms.moe`)."""
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

OPS = ("moe_router", "moe_expert_ffn")
# the grouped products: XLA expands `lax.ragged_dot` into kernels of its
# own, `%ragged-dot-none.N` and `%ragged-dot-metadata.N`, whose op_name
# keeps no Fluid scope; only `moe_expert_ffn` makes any
XLA_OWN = ("ragged-dot",)


def compute(run):
    ms = helper().ms_per_step(OPS, XLA_OWN)
    if ms is None:
        return None
    config, traffic, model = helper().cell_files()
    return helper().roofline_pct(run, model.moe_required(config, traffic), ms)
