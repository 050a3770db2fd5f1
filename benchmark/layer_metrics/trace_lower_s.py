"""Executor: seconds JAX spent tracing the program's Python into jaxprs
and lowering them to MLIR, all signatures of the run together: what no
compile cache removes from set-up. The program's `jax.monitoring`
listener sums them (`fluid/telemetry.py`); read from its registry at the
end of the run. A jit traced inside another counts twice in the first."""
import sys

COUNTERS = ("jax_trace_seconds_total", "jax_lower_seconds_total")


def compute(run):
    telemetry = sys.modules.get("paddle_tpu.fluid.telemetry")
    if telemetry is None or run.trace is None:
        return None  # no program, or no chip's trace: a rehearsal
    families = [telemetry.REGISTRY.get(name) for name in COUNTERS]
    if None in families:
        return None  # a program without the counters
    return sum(f.value() for f in families)
