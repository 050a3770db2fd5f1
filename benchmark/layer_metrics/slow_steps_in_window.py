"""Executor: steps of the window the program flagged as slow: a period
(the caller's time before the call + the call) over three medians of the
block's and over the median by 50 ms (`telemetry.SLOW_STEPS`)."""
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
    helper = step_records()
    records = helper.window(run)
    if not records:
        return None
    return len(helper.slow(records))
