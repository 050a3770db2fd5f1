"""The four readers of the program's step records (`exe_ms.run_p95`,
`exe_ms.self`, `gc_ms_per_step`, `slow_steps_in_window`) and
`benchmark/step_records.py` under them, off the chip: the window found
by the clock on rings made by hand, the four entries the manifest
lists for every cell (since PR 38), and a `--tiny --trace 1` rehearsal
through the real manifest that names the four.
"""
import importlib.util
import json
import os
import re
import subprocess
import sys
import types
from collections import deque

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
READERS = {"exe_ms.run_p95": ("ms", "program_span", "step_ms_p95"),
           "exe_ms.self": ("ms", "program_span", "samples_per_s"),
           "gc_ms_per_step": ("ms", "program_counter", "samples_per_s"),
           "slow_steps_in_window": ("count", "program_counter",
                                    "samples_per_s")}
STEP, STARTUP = 7, 3  # two blocks' numbers


def load(path):
    spec = importlib.util.spec_from_file_location(
        "_sr_" + re.sub(r"\W", "_", os.path.relpath(path, REPO)), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def reader(name):
    return load(os.path.join(BENCH, "layer_metrics", name + ".py"))


def record(seq, block, t0, run_ms, self_ms=0.5, gc_ms=0.0):
    return types.SimpleNamespace(
        seq=seq, block=block, t0=t0, run_s=run_ms * 1e-3,
        self_s=self_ms * 1e-3, gc_s=gc_ms * 1e-3)


def a_run(steps, warmup=(10.0, 20.0)):
    stats = load(os.path.join(BENCH, "stats.py"))
    return types.SimpleNamespace(
        spans={"warmup": [warmup]} if warmup else {}, step_s=[0.1] * steps,
        median=stats.median, percentile=stats.percentile)


@pytest.fixture()
def program(monkeypatch):
    """A stand-in for the program's telemetry module, as the readers
    find it: through `sys.modules`."""
    fake = types.SimpleNamespace(STEPS=deque(maxlen=4096),
                                 SLOW_STEPS=deque(maxlen=32))
    monkeypatch.setitem(sys.modules, "paddle_tpu.fluid.telemetry", fake)
    return fake


def fill(program):
    """Start-up program, two warm-up steps, a window of twenty, ten
    traced steps, in the ring as a traced run leaves them."""
    ring = program.STEPS
    ring.append(record(1, STARTUP, 5.0, 900.0))
    ring.append(record(2, STEP, 11.0, 4000.0))         # warm-up: compiles
    ring.append(record(3, STEP, 15.5, 3000.0))
    for i in range(20):                                # the window
        ring.append(record(4 + i, STEP, 20.5 + i, 10.0 + i,
                           self_ms=0.3 + 0.01 * i,
                           gc_ms=8.0 if i == 4 else 0.5))
    ring.append(record(24, STARTUP, 40.9, 1.0))        # another program
    for i in range(10):                                # the traced ten
        ring.append(record(25 + i, STEP, 41.0 + i, 500.0, self_ms=50.0,
                           gc_ms=100.0))
    program.SLOW_STEPS.extend([ring[2], ring[12], ring[-1]])


def test_the_window_is_found_by_the_clock(program):
    fill(program)
    helper = load(os.path.join(BENCH, "step_records.py"))
    got = helper.window(a_run(20))
    assert [r.seq for r in got] == list(range(4, 24))
    # a shorter window takes the first of them, a longer what there is
    assert [r.seq for r in helper.window(a_run(5))] == [4, 5, 6, 7, 8]
    assert len(helper.window(a_run(50))) == 30
    # the records the program flagged, of these: warm-up's and the
    # traced steps' are not the window's
    assert [r.seq for r in helper.slow(got)] == [13]


@pytest.mark.parametrize("name,want", [
    # nearest rank: the 19th of twenty run_s of 10..29 ms
    ("exe_ms.run_p95", 28.0),
    # the median of 0.30..0.49
    ("exe_ms.self", 0.395),
    # (19 x 0.5 + 8.0) / 20
    ("gc_ms_per_step", 0.875),
    ("slow_steps_in_window", 1)])
def test_a_reader_reads_the_windows_records_alone(program, name, want):
    fill(program)
    assert reader(name).compute(a_run(20)) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_finds_nothing_and_says_none(program, monkeypatch, name):
    compute = reader(name).compute
    assert compute(a_run(20)) is None            # an empty ring
    program.STEPS.append(record(1, STEP, 5.0, 10.0))
    assert compute(a_run(20)) is None            # no record after warm-up
    fill(program)
    assert compute(a_run(20, warmup=None)) is None   # no warm-up span
    assert compute(a_run(0)) is None             # no step in the window
    # a program without the ring (the parent of the PR that brought it),
    # and no program at all
    monkeypatch.setitem(sys.modules, "paddle_tpu.fluid.telemetry",
                        types.SimpleNamespace())
    assert compute(a_run(20)) is None
    monkeypatch.delitem(sys.modules, "paddle_tpu.fluid.telemetry")
    assert compute(a_run(20)) is None


def check_manifest(m):
    """The four are listed, for every cell (a program of any cell has
    the ring), with the unit, source and end-to-end metric each reads
    for; their files are there."""
    by_name = {p["name"]: p for p in m["per_layer"]}
    for name, (unit, source, moves) in READERS.items():
        assert by_name[name] == {
            "name": name, "unit": unit, "better": "lower", "source": source,
            "layer": "Executor", "moves": moves}
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))


def test_the_four_are_entries_the_manifest_lists_for_every_cell():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        check_manifest(json.load(f))


def test_a_traced_rehearsal_names_the_four_with_no_value(tmp_path):
    """`run.py --tiny --trace 1` through the real manifest: a
    rehearsal's program has the ring, so each reader finds its window,
    and `run.py` blanks the values."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla_cache"),
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    res = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "bert_base.b128_s128", "--seed", str(2 ** 31 + 36), "--seconds",
         "1", "--trace", "1", "--tiny"],
        capture_output=True, text=True, env=env, timeout=600, cwd=REPO)
    assert res.returncode != 0 and "rehearsal" in res.stderr, \
        res.stderr[-2000:]
    last = json.loads(res.stdout.splitlines()[-1])
    for name, (unit, _, _) in READERS.items():
        assert last["metrics"][name] == {"value": None, "unit": unit}
    assert last["correct"] is False and last["attempted"] >= 1
