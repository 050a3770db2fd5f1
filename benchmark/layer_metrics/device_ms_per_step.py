"""Op kernels: milliseconds a step keeps the device busy: the union of
the device-operation intervals in the trace over the traced steps."""


def compute(run):
    trace = run.trace
    if not trace or not trace["steps"]:
        return None
    return trace["busy_s"] / trace["steps"] * 1e3
