"""Seconds from the process's start to the window's first step: imports,
program build, startup program, batch pool, and the warm-up steps with
their traces and compiles (or loads from the compile cache)."""


def compute(run):
    return run.setup_s
