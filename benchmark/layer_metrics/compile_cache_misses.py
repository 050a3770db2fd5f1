"""Compile cache: executables JAX compiled and wrote to the persistent
cache during the run, counted by the program's `jax.monitoring`
listener: 0 on a warm machine, where `compile_s` alone cannot tell a
load from a compile."""
import sys

COUNTERS = ("jax_compile_cache_misses_total",)


def compute(run):
    telemetry = sys.modules.get("paddle_tpu.fluid.telemetry")
    if telemetry is None or run.trace is None:
        return None  # no program, or no chip's trace: a rehearsal
    families = [telemetry.REGISTRY.get(name) for name in COUNTERS]
    if None in families:
        return None  # a program without the counters
    return sum(f.value() for f in families)
