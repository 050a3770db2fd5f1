"""Op kernels: milliseconds a step of device time under the
`rotary_embedding` scopes (the frequencies, cos and sin, plain or
YaRN's, and the rotation of queries and keys), forward, recomputed
forward and backward, as the union of their intervals
(`scope_union.py`)."""
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

OPS = ("rotary_embedding",)


def compute(run):
    return helper().ms_per_step(OPS)
