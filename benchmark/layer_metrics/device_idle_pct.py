"""Device: share of the traced window in which no operation ran on the
chip (averaged over the chips of a mesh cell)."""


def compute(run):
    trace = run.trace
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
