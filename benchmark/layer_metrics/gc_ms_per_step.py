"""Executor: host milliseconds a step the cyclic collector held the
interpreter over the window: the sum of the step records' `gc_s` (the
pauses that ended between one run's return and the next's) over the
window's steps."""
import importlib.util
import os


def step_records():
    """`benchmark/step_records.py`, by path."""
    spec = importlib.util.spec_from_file_location(
        "_benchmark_step_records", os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "step_records.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def compute(run):
    records = step_records().window(run)
    if not records:
        return None
    return sum(r.gc_s for r in records) / len(records) * 1e3
