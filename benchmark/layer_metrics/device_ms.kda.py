"""Op kernels: milliseconds a step of device time under the `kda` scopes
(Kimi delta attention: the gates, the norms of q and k, the chunks'
decay-weighted products, the WY solve and the chunk-state scan),
forward, recomputed forward and backward: the union of their intervals
(`scope_union.py`), so that the chunk scan's body inside its `while`
counts once. The projections, the convolution, the output norm and gate
are other ops and are not in it."""
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
    return helper().ms_per_step(OPS)
