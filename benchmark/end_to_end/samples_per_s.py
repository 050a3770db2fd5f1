"""Samples of all the steps completed in the window over the window's
seconds (the global batch, on a mesh cell)."""


def compute(run):
    return run.rate
