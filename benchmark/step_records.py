"""The window's step records: what the program's own ring
(`paddle_tpu.fluid.telemetry.STEPS`, one record an `Executor.run`) holds
of the steps `run.py` timed from outside.

The two sides share a clock, `time.perf_counter`, and nothing else: a
record's `t0` is the call's entry, `run.spans["warmup"]` ends before the
window's first step. So the window's records are those of the most
frequent block (the step; the start-up program is another block) whose
`t0` lies after the warm-up's end, the first `len(run.step_s)` of them:
the ten traced steps that follow the window are left out. The program
is found through `sys.modules`, never imported from here.
"""
import sys
from collections import Counter


def window(run):
    """The records of the window's steps in order, or None: a program
    without the ring, no warm-up span, or no record after it."""
    telemetry = sys.modules.get("paddle_tpu.fluid.telemetry")
    ring = getattr(telemetry, "STEPS", None)
    warmup = run.spans.get("warmup")
    if not ring or not warmup or not run.step_s:
        return None
    after = warmup[-1][1]
    records = [r for r in list(ring) if r.t0 >= after]
    if not records:
        return None
    step, _ = Counter(r.block for r in records).most_common(1)[0]
    return [r for r in records if r.block == step][:len(run.step_s)]


def slow(records):
    """Those of `records` the program flagged as slow steps."""
    telemetry = sys.modules["paddle_tpu.fluid.telemetry"]
    flagged = {r.seq for r in list(telemetry.SLOW_STEPS)}
    return [r for r in records if r.seq in flagged]
