"""Executor: median host milliseconds of the program's `exe:feed` span
inside `Executor.run`: host batch to device arrays, the upload enqueued
and not awaited. Read from the host plane of the trace, so it is on the
device events' clock."""
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
    if not scopes or not scopes["chips"]:
        return None
    return scopes["stage_ms"].get("exe:feed")
