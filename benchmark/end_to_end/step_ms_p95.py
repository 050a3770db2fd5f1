"""95th percentile (nearest rank) of the wall time of the window's steps,
each from the call of `exe.run` to the numpy loss it returns."""


def compute(run):
    return run.percentile(run.step_s, 95) * 1e3
