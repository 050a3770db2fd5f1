"""Op kernels: milliseconds a step of device time under the `moe_router`
and `moe_expert_ffn` scopes (router, sort, gather, the grouped products,
scatter), forward, recomputed forward and backward, as the union of
their intervals (`scope_union.py`). The shared expert is plain `mul`
ops and is not in it."""
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
    return helper().ms_per_step(OPS, XLA_OWN)
