"""Compile cache: seconds JAX spent in backend compiles during set-up
(a load from the persistent cache counts as a short one), summed by the
benchmark's `jax.monitoring` listener."""


def compute(run):
    return run.counters.get("setup_compile_s")
