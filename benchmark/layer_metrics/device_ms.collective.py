"""Mesh: milliseconds a step of device time in the collective operations
XLA puts into a mesh cell's step and names itself (`%all-reduce`,
`%all-gather`, `%reduce-scatter`, `%all-to-all`, `%collective-permute`,
their `-start` / `-done` halves included): the union of their intervals
by instruction name, mean of the chips' planes (`scope_union.py`; a
`top` list of the other reductions can drop a collective, the
intervals keep every operation). Compute that overlaps a collective is
not taken off: this is the exchange's device time, not its exposed
part. Off a mesh no such operation runs and the metric is left out.

What it cannot see: an exchange whose instruction NAME starts otherwise,
which XLA on the TPU also emits: a `%fusion` that wraps an all-reduce,
`%async-collective-*`, `%send` / `%recv` (a permute's halves). The dp4
cell has none (9.54 ms here, 9.28 its `%all-reduce` row; PR 38). A cell
with another exchange (all-to-all across expert ranks) checks this
union against its `device_ops` rows first and, where they part, a
reader of its own matches the HLO opcode in the instruction's text."""
import importlib.util
import os
import sys


def helper():
    """`benchmark/scope_union.py`, by path; one instance a process, so
    that every reader finds the one set of intervals the trace gave."""
    name = "_benchmark_scope_union"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "scope_union.py"))
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


helper().watch()

# prefixes of XLA's instruction names; `all-reduce` also takes
# `all-reduce-start.N`, `all-reduce-done.N` and `all-reduce-scatter.N`
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


def compute(run):
    return helper().ms_per_step((), COLLECTIVES)
