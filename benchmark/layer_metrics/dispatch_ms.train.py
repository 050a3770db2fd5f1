"""Executor: median host milliseconds of `exe.run(..., return_numpy=False)`
until it returns (feed upload, placing the state arrays, the enqueue);
the wait for the device is the `fetch` span, apart."""


def compute(run):
    spans = run.spans.get("exe.run")
    if not spans:
        return None
    return run.median([end - start for start, end in spans]) * 1e3
