"""Op kernels: milliseconds a step of device time under the `moe_router`
scopes alone (the float32 logits at the highest matmul precision, the
softmax, the top-k, the renormalisation and the auxiliary loss's
scatter), forward, recomputed forward and backward, as the union of
their intervals (`scope_union.py`). `device_ms.moe` folds this into the
experts' time; where the router reads the layer's input it is a block of
its own above attention, and this is its cost."""
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

OPS = ("moe_router",)


def compute(run):
    return helper().ms_per_step(OPS)
