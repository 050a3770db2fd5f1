"""Executor: median host milliseconds, over the window's steps, of
`Executor.run`'s self time: a step record's `self_s`, its `run_s` less its
six stage spans: the time between the stages, which no span covers."""
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
    return run.median([r.self_s for r in records]) * 1e3
