"""Executor: nearest-rank 95th percentile, in host milliseconds, of the
program's own `exe:run` span (a step record's `run_s`) over the WINDOW's
steps: the program's view of the call whose median `dispatch_ms.train`
times from outside."""
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
    return run.percentile([r.run_s for r in records], 95) * 1e3
