"""Op kernels: milliseconds a step of device operations traced under the
scope `opt/`: the optimizer's ops (Adam, Momentum) over every state array. Read from the trace by
`trace_scopes.py`: the executor traces every Fluid op under
`<phase>/<op type>`, a fusion counts under its root's scope, and what
carries no phase is `unscoped`, never spread over the phases."""
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


def compute(run):
    scopes = shared().last()
    if not scopes or not scopes["chips"] or not scopes["device_scopes"]:
        return None  # no chip's plane, or a program without the scopes
    return scopes["phase_s"]["opt"] / scopes["steps"] * 1e3
