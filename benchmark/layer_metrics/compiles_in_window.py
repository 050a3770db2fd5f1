"""Executor: backend compiles counted by the benchmark's listener between
the window's first step and its last. Anything but 0 is a retrace."""


def compute(run):
    return run.counters.get("window_compiles")
