"""`benchmark/trace_scopes.py` and the per-layer readers on top of it, off
the chip: the reduction's arithmetic on a trace made by hand, the parsing
of HLO text and op_names, the pairing of the runtime's transfer events,
the program's stage spans read from a real profile of this CPU, and the
readers on empty and on made-up runs. No test here describes a TPU
topology.
"""
import importlib.util
import json
import os
import re
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
NEW_READERS = [
    "device_ms.fwd", "device_ms.bwd", "device_ms.opt", "flash_ms_per_step",
    "upload_exposed_ms", "exe_ms.feed", "exe_ms.place", "exe_ms.dispatch",
    "exe_ms.write_back", "trace_lower_s", "compile_cache_misses"]
KERNEL = ' = bf16[8] custom-call(), custom_call_target="tpu_custom_call"'


def load(path):
    spec = importlib.util.spec_from_file_location(
        "_ts_" + re.sub(r"\W", "_", os.path.relpath(path, REPO)), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture()
def ts(monkeypatch):
    """`trace_scopes.py` as the readers share it, with nothing left in it
    from another test."""
    monkeypatch.delitem(sys.modules, "_benchmark_trace_scopes", raising=False)
    return load(os.path.join(BENCH, "layer_metrics",
                             "device_ms.fwd.py")).shared()


def reader(name):
    return load(os.path.join(BENCH, "layer_metrics", name + ".py"))


def empty_run():
    return types.SimpleNamespace(trace=None, spans={}, counters={})


# ------------------------------------------------------------ the reduction
def test_reduce_on_a_trace_made_by_hand(ts):
    # one step, window 0..10. Device: [1,3] a forward matmul, [3,4] a
    # fusion whose root is a backward op, [4,5] Adam, [5,5.5] a copy XLA
    # inserted (no op_name), [6,7] and [7,8] two calls of one kernel
    device = {"/device:TPU:0": [
        (1.0, 3.0, "%dot.1 = f32[2] dot()", "jit__step(77)"),
        (3.0, 4.0, "%add_fusion.7 = f32[2] fusion(), kind=kLoop", "jit__step(77)"),
        (4.0, 5.0, "%sub.3 = f32[2] subtract()", "jit__step(77)"),
        (5.0, 5.5, "%copy.9 = f32[2] copy()", "jit__step(77)"),
        (6.0, 7.0, "%flash_fwd.1" + KERNEL, "jit__step(77)"),
        (7.0, 8.0, "%jvp_flash_fwd_.2" + KERNEL, "jit__step(77)"),
        # same instruction name in another module: not this module's scope
        (8.0, 8.5, "%dot.1 = f32[2] dot()", "jit__lambda(3)"),
    ]}
    modules = {"jit__step(77)": ({
        "dot.1": "jit(_step)/fwd/mul/dot_general",
        "add_fusion.7": "jit(_step)/bwd/relu_grad/jvp()/select_n",
        "sub.3": "jit(_step)/opt/adam/sub",
        "copy.9": "",
        "flash_fwd.1": "jit(_step)/fwd/flash_attn/flash_fwd/pallas_call",
        "jvp_flash_fwd_.2": "jit(_step)/bwd/flash_attn_grad/jvp(flash_fwd)/"
                            "pallas_call"},
        {"add_fusion.7": "bwd+opt"})}
    host = [(0.0, 0.2, "feed"), (0.2, 6.5, "exe.run"), (6.5, 10.0, "fetch"),
            (0.2, 0.6, "exe:feed"), (0.6, 1.0, "exe:place"),
            (5.4, 6.2, "compiled_step")]
    got = ts.reduce(device, host, [], modules)
    assert got["chips"] == 1 and got["steps"] == 1
    assert got["window_s"] == 10.0 and got["busy_s"] == 7.0
    assert got["phase_s"] == {"fwd": 3.0, "bwd": 2.0, "opt": 1.0,
                              "unscoped": 1.0}
    assert sum(got["phase_s"].values()) == got["ops_total_s"] == 7.0
    # the fusion went to its root's scope; the stranger stayed unscoped
    assert dict(got["device_scopes"]) == {
        "fwd/mul": 2.0, "fwd/flash_attn": 1.0, "bwd/relu_grad": 1.0,
        "bwd/flash_attn_grad": 1.0, "opt/adam": 1.0}
    assert got["device_scopes"][0] == ["fwd/mul", 2.0]
    assert dict(got["unscoped_ops"]) == {"%copy": 0.5, "%dot": 0.5}
    assert got["device_ops_scopes"][:2] == [
        ["%dot", 2.5, [["fwd/mul", 2.0], ["unscoped", 0.5]]],
        ["%add_fusion", 1.0, [["bwd/relu_grad", 1.0]]]]
    assert got["unknown_instructions"] == 1  # the stranger's alone
    assert got["mixed_s"] == {"bwd+opt": 1.0}
    # one kernel, whatever transform wrapped its second call
    assert got["kernels"] == {"flash_fwd": {
        "s": 2.0, "calls": 2.0, "fwd_s": 1.0, "bwd_s": 1.0}}
    # gaps [0,1], [5.5,6] and [8.5,10]: each piece to the INNERMOST
    # span over it, the nested span and not also the one around it
    idle = dict(got["idle_by_stage"])
    assert idle == pytest.approx({
        "feed": 0.2, "exe:feed": 0.4, "exe:place": 0.4,
        "compiled_step": 0.5, "fetch": 1.5})
    assert sum(idle.values()) == pytest.approx(got["idle_s"]) == 3.0
    assert got["stage_ms"] == pytest.approx(
        {"exe:feed": 400.0, "exe:place": 400.0, "compiled_step": 800.0})
    assert got["stage_calls"] == {"exe:feed": 1, "exe:place": 1,
                                  "compiled_step": 1}
    assert got["stage_cover"] == pytest.approx(1.6 / 6.3)
    assert got["uncovered_ms"] == pytest.approx({
        "before exe:feed": 0.0, "before exe:place": 0.0,
        "before compiled_step": 4400.0, "after compiled_step": 300.0})
    # its totals are trace_reduce's, on the same events
    tr = ts._tr
    old = tr.reduce({p: [(s, e, tr.short_name(t)) for s, e, t, _ in ev]
                     for p, ev in device.items()},
                    [h for h in host if h[2] in ts.WINDOW_SPANS], steps=1)
    assert (old["window_s"], old["busy_s"], old["idle_s"]) \
        == (got["window_s"], got["busy_s"], got["idle_s"])
    assert sum(s for _, s in old["idle_gaps"]) == pytest.approx(
        sum(idle.values()))


def test_a_gap_that_ends_with_an_upload_is_h2d_and_no_other(ts):
    device = {"/device:TPU:0": [(0.0, 1.0, "%a = f32[] add()", "m"),
                                (3.0, 4.0, "%a = f32[] add()", "m"),
                                (6.0, 7.0, "%a = f32[] add()", "m")]}
    host = [(0.0, 2.0, "exe.run"), (1.0, 1.5, "exe:feed"),
            (2.0, 7.0, "fetch")]
    # gap [1,3]: an upload [1.2, 3.0002] is still in flight when it ends;
    # gap [4,6]: an upload [4.1, 4.3] ended long before the gap did
    uploads = [(1.2, 3.0002, 100), (4.1, 4.3, 8)]
    got = ts.reduce(device, host, uploads, {})
    idle = dict(got["idle_by_stage"])
    assert idle == pytest.approx({"h2d": 1.8, "exe:feed": 0.2, "fetch": 2.0})
    assert sum(idle.values()) == pytest.approx(got["idle_s"])
    assert got["uploads"] == 2 and got["upload_bytes"] == 108
    # a plane that holds no operation is not a chip to average over
    device["/device:TPU:1"] = []
    assert ts.reduce(device, host, uploads, {})["chips"] == 1


def test_reduce_without_a_chip_or_without_the_benchmarks_spans(ts):
    host = [(0.0, 1.0, "exe.run"), (0.1, 0.3, "exe:place"),
            (0.3, 0.9, "compiled_step")]
    got = ts.reduce({}, host, [], {})
    assert got["chips"] == 0 and got["steps"] == 1
    assert "phase_s" not in got and "idle_by_stage" not in got
    assert got["stage_cover"] == pytest.approx(0.8)
    assert ts.reduce({}, [(0.1, 0.3, "exe:place")], [], {}) is None


def test_pair_uploads_joins_linearize_issue_and_done(ts):
    linearize = [(1.0, 10.0), (20.0, 20.1)]
    issued = [(10.0001, 10.0002, 154), (0.5, 0.6, 8), (30.0, 30.1, 8)]
    done = [(21.0, 21.1, 154), (0.7, 0.8, 8)]
    assert ts.pair_uploads(linearize, issued, done) == [
        (0.5, 0.8, 8), (1.0, 21.1, 154)]  # the last has no end: left out


def test_scope_of_and_the_hlo_text(ts):
    assert ts.scope_of("jit(_step)/fwd/mul/dot_general") == ("fwd", "fwd/mul")
    assert ts.scope_of("jit(_step)/bwd/mul_grad/transpose(jvp())/dot") \
        == ("bwd", "bwd/mul_grad")
    assert ts.scope_of("jit(loss)/transpose(jvp(fwd/flash_attn))/"
                       "flash_bwd_dq/pallas_call") == ("fwd", "fwd/flash_attn")
    assert ts.scope_of("jit(_step)/opt/adam/sub") == ("opt", "opt/adam")
    for stranger in ("jit(_step)/mul", "", None, "jit(_step)/prefwd/x"):
        assert ts.scope_of(stranger) == (None, None)
    text = '''HloModule jit__step, entry_computation_layout={()->f32[]}

%fused_computation.1 (p: f32[2]) -> f32[2] {
  %p = f32[2]{0} parameter(0)
  %dot.4 = f32[2]{0} dot(%p, %p), metadata={op_name="jit(_step)/bwd/mul_grad/transpose(jvp())/dot_general"}
  ROOT %sub.5 = f32[2]{0} subtract(%dot.4, %p), metadata={op_name="jit(_step)/opt/adam/sub" stack_frame_id=4}
}

%fused_computation.2 (p: f32[2]) -> f32[2] {
  %p.1 = f32[2]{0} parameter(0)
  ROOT %neg.6 = f32[2]{0} negate(%p.1), metadata={op_name="jit(_step)/opt/adam/neg"}
}

ENTRY %main () -> f32[] {
  %multiply_subtract_fusion.3 = f32[2]{0} fusion(%c), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_step)/opt/adam/sub" stack_frame_id=4}
  %negate_fusion = f32[2]{0} fusion(%c), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(_step)/opt/adam/neg"}
  %copy.2 = f32[2]{0} copy(%multiply_subtract_fusion.3)
  ROOT %jvp_flash_fwd_.1 = bf16[8]{0} custom-call(%copy.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(_step)/bwd/flash_attn_grad/jvp(flash_fwd)/pallas_call"}
}'''
    names, mixed = ts.op_names_of(text)
    assert names == {
        "p": "", "p.1": "", "copy.2": "",
        "dot.4": "jit(_step)/bwd/mul_grad/transpose(jvp())/dot_general",
        "sub.5": "jit(_step)/opt/adam/sub",
        "neg.6": "jit(_step)/opt/adam/neg",
        "multiply_subtract_fusion.3": "jit(_step)/opt/adam/sub",
        "negate_fusion": "jit(_step)/opt/adam/neg",
        "jvp_flash_fwd_.1": "jit(_step)/bwd/flash_attn_grad/jvp(flash_fwd)/"
                            "pallas_call"}
    # the update XLA merged into the gradient's fusion: one op_name, two
    # phases inside
    assert mixed == {"multiply_subtract_fusion.3": "bwd+opt"}
    assert ts.kernel_of(names["jvp_flash_fwd_.1"], "jvp_flash_fwd_.1") \
        == "flash_fwd"
    assert ts.kernel_of("jit(_step)/bwd/x_grad/transpose(bwd/x_grad)/"
                        "jvp(flash_bwd_dq)/pallas_call", "q") == "flash_bwd_dq"
    assert ts.kernel_of("", "_step.12") == "_step"
    # a module that ran finds the loaded module that holds its
    # instructions, not the first of its name
    startup = ("jit__step", {"fusion.1": "jit(_step)/fwd/fill_constant/x"},
               {})
    device = {"/device:TPU:0": [
        (0.0, 1.0, "%sub.5 = f32[2] subtract()", "jit__step(9)"),
        (1.0, 2.0, "%copy.2 = f32[2] copy()", "jit__step(9)"),
        (2.0, 3.0, "%fusion.1 = f32[2] fusion()", "jit__lambda(4)")]}
    assert ts.match_modules(device, [startup, ("jit__step", names, mixed)]) \
        == {"jit__step(9)": (names, mixed)}
    assert ts.instruction_name(
        "%multiply_subtract_fusion.3 = f32[2]{0} fusion(%c), kind=kLoop") \
        == "multiply_subtract_fusion.3"


def test_window_spans_are_the_harness_split_spans(ts):
    assert ts.WINDOW_SPANS == load(os.path.join(BENCH, "run.py")).SPLIT_SPANS


# -------------------------------------------- the program's part, on this CPU
def test_stage_spans_and_scopes_are_read_from_a_real_profile(ts, tmp_path):
    """A tiny program's steps, traced by `jax.profiler` alone (no
    profiler session of the program's): the host plane holds the six
    stage spans once a step, nested in the caller's span, and the loaded
    executable's text gives every phase its op_names."""
    import jax
    import numpy as np
    sys.path.insert(0, REPO)
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import core
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", shape=[8], dtype="float32")
        y = fluid.data("y", shape=[1], dtype="int64")
        p = fluid.layers.fc(fluid.layers.fc(x, 16, act="relu"), 4,
                            act="softmax")
        loss = fluid.layers.mean(fluid.layers.cross_entropy(p, y))
        fluid.optimizer.Momentum(0.1, 0.9).minimize(loss)
    exe, scope = fluid.Executor(), core.Scope()
    feed = {"x": np.ones((4, 8), "float32"), "y": np.zeros((4, 1), "int64")}
    exe.run(startup, scope=scope)
    for _ in range(2):  # both signatures of the step, before the trace
        exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    run = load(os.path.join(BENCH, "run.py"))
    jax.profiler.start_trace(str(tmp_path),
                             profiler_options=run.trace_options())
    try:
        for _ in range(3):
            with jax.profiler.TraceAnnotation("exe.run"):
                exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    finally:
        jax.profiler.stop_trace()
    device, host, uploads = ts.read(str(tmp_path))
    assert device == {} and uploads == []
    got = ts.reduce(device, host, uploads, {})
    assert got["chips"] == 0 and got["steps"] == 3
    assert got["stage_calls"] == dict.fromkeys(ts.STAGE_SPANS, 3)
    assert 0.5 < got["stage_cover"] <= 1.0
    runs = sorted(h for h in host if h[2] == "exe.run")
    for s, e, name in host:  # nested, and in the order a step passes them
        assert any(r0 <= s and e <= r1 for r0, r1, _ in runs), name
    first = sorted(h for h in host if runs[0][0] <= h[0] < runs[0][1])
    assert [n for _, _, n in first] == ["exe.run", *ts.STAGE_SPANS]
    # the scopes, from the loaded executables' own text
    loaded = ts.loaded_modules(jax.devices()[0].client)
    found = {ts.scope_of(v)[1] for name, names, _ in loaded
             if name == "jit__step" for v in names.values()}
    assert {"fwd/mul", "fwd/cross_entropy", "bwd/mul_grad",
            "bwd/relu_grad", "opt/momentum", "fwd/fill_constant"} <= found


def test_watch_leaves_the_profiler_alone_outside_the_harness(ts):
    import jax.profiler
    before = (jax.profiler.start_trace, jax.profiler.stop_trace)
    ts.watch()
    assert (jax.profiler.start_trace, jax.profiler.stop_trace) == before
    assert ts.last() is None


def test_the_rehearsal_prints_the_scopes_line(tmp_path):
    """Under `run.py` the readers' `watch()` follows the traced steps by
    one reduction: a `scopes` line before the `trace` line, with the
    program's spans once a traced step, and still no value and no new
    metric in a CPU's result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla_cache"))
    res = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "resnet50.b256_i224", "--seed", "7", "--seconds", "0.5", "--trace",
         "1", "--tiny"], capture_output=True, text=True, env=env,
        timeout=600, cwd=REPO)
    rows = [json.loads(line) for line in res.stdout.splitlines()]
    phases = [r.get("phase") for r in rows]
    assert phases.index("scopes") == phases.index("trace") - 1, res.stderr
    row = rows[phases.index("scopes")]
    assert "error" not in row, row.get("error")
    scopes = row["scopes"]
    assert scopes["chips"] == 0 and scopes["steps"] == 10
    assert scopes["stage_calls"] == dict.fromkeys(
        ["exe:feed", "exe:lookup", "exe:place", "compiled_step",
         "exe:write_back"], 10)  # the traced form fetches for itself
    assert not set(NEW_READERS) & set(rows[-1]["metrics"])


# ------------------------------------------------------------ the readers
@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_finds_nothing_in_an_empty_run(ts, name):
    assert reader(name).compute(empty_run()) is None


def test_the_trace_readers_on_a_made_up_reduction(ts, monkeypatch):
    made_up = {
        "chips": 1, "steps": 10,
        "phase_s": {"fwd": 0.5, "bwd": 1.0, "opt": 0.25, "unscoped": 0.1},
        "device_scopes": [["bwd/mul_grad", 0.7]],
        "kernels": {"flash_fwd": {"s": 0.2, "calls": 240.0},
                    "flash_bwd_dkv": {"s": 0.15, "calls": 120.0},
                    "flash_bwd_dq": {"s": 0.1, "calls": 120.0},
                    "other_kernel": {"s": 9.0, "calls": 1.0}},
        "stage_ms": {"exe:feed": 1.5, "exe:place": 1.0,
                     "compiled_step": 4.0, "exe:write_back": 0.75},
        "idle_by_stage": [["h2d", 0.15], ["fetch", 0.02],
                          ["exe:feed", 0.01]]}
    monkeypatch.setitem(ts._state, "last", made_up)
    run = empty_run()
    want = {"device_ms.fwd": 50.0, "device_ms.bwd": 100.0,
            "device_ms.opt": 25.0, "flash_ms_per_step": 45.0,
            "upload_exposed_ms": 16.0, "exe_ms.feed": 1.5,
            "exe_ms.place": 1.0, "exe_ms.dispatch": 4.0,
            "exe_ms.write_back": 0.75}
    for name, value in want.items():
        assert reader(name).compute(run) == pytest.approx(value), name
    # a program without the scopes, the kernel names or the spans (the
    # parent commit): nothing, not a zero
    bare = dict(made_up, device_scopes=[], kernels={}, stage_ms={},
                phase_s={"fwd": 0, "bwd": 0, "opt": 0, "unscoped": 1.85})
    monkeypatch.setitem(ts._state, "last", bare)
    for name in want:
        assert reader(name).compute(run) is None, name
    # and no chip's plane: nothing either
    monkeypatch.setitem(ts._state, "last", dict(made_up, chips=0))
    for name in want:
        assert reader(name).compute(run) is None, name


def test_the_counter_readers_read_the_programs_registry(monkeypatch):
    sys.path.insert(0, REPO)
    from paddle_tpu.fluid import telemetry
    registry = telemetry.MetricsRegistry()
    monkeypatch.setattr(telemetry, "REGISTRY", registry)
    on_a_chip = types.SimpleNamespace(trace={"busy_s": 1.0})
    readers = {n: reader(n) for n in ("trace_lower_s",
                                      "compile_cache_misses")}
    # a program without the counters: nothing
    assert [r.compute(on_a_chip) for r in readers.values()] == [None, None]
    registry.counter("jax_trace_seconds_total").inc(2.5)
    registry.counter("jax_lower_seconds_total").inc(1.25)
    registry.counter("jax_compile_cache_misses_total")
    assert readers["trace_lower_s"].compute(on_a_chip) == 3.75
    assert readers["compile_cache_misses"].compute(on_a_chip) == 0
    # a rehearsal's line keeps to the host metrics it had
    assert readers["trace_lower_s"].compute(empty_run()) is None


def check_manifest(m):
    """The eleven are in the manifest, each `better: lower`, wherever a
    later PR's entries put them; a `workloads` list is never empty and
    names cells the manifest has, and later PRs may append to it."""
    by_name = {p["name"]: p for p in m["per_layer"]}
    assert set(NEW_READERS) <= set(by_name)
    assert all(by_name[n]["better"] == "lower" for n in NEW_READERS)
    cells = {w["name"] for w in m["workloads"]}
    for p in m["per_layer"]:
        if "workloads" in p:
            assert p["workloads"] and set(p["workloads"]) <= cells, p["name"]
    # the flash kernels' milliseconds are read where the kernels run:
    # not at s128, which is XLA's dense path since PR 27
    flash = by_name["flash_ms_per_step"]["workloads"]
    assert "bert_base.b128_s128" not in flash
    assert {"qwen3_next_80b_a3b.b1_s4096", "phi4_mini_flash.b1_s4096",
            "bert_base.b32_s512_pad", "laguna_xs2.b1_s8192"} <= set(flash)


def test_the_manifest_has_the_eleven_and_its_workloads_lists_name_cells():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        check_manifest(json.load(f))


# ------------------------------------------------- the Mesh layer's reader
def test_collective_reader_takes_the_union_by_xla_s_own_names(monkeypatch):
    """Two chips' planes, two steps, window 0..20: an all-reduce in two
    halves on each chip, an all-gather nested in a reduce-scatter on one,
    and a fusion that is no collective; the union by instruction name,
    mean of the planes, a step."""
    collective = reader("device_ms.collective")
    union = collective.helper()
    step = "jit__step(9)"
    device = {
        "/device:TPU:0": [
            (1.0, 2.0, "%fusion.1 = f32[2] fusion()", step),
            (2.0, 3.0, "%all-reduce-start.1 = f32[2] all-reduce-start()",
             step),
            (5.0, 6.0, "%all-reduce-done.1 = f32[2] all-reduce-done()",
             step),
            (11.0, 14.0, "%reduce-scatter.3 = f32[2] reduce-scatter()",
             step),
            (12.0, 13.0, "%all-gather.2 = f32[2] all-gather()", step)],
        "/device:TPU:1": [
            (2.0, 4.0, "%all-reduce.7 = f32[2] all-reduce()", step),
            (30.0, 31.0, "%all-to-all.4 = f32[2] all-to-all()", step)]}
    host = [(0.0, 9.0, "exe.run"), (9.0, 10.0, "fetch"),
            (10.0, 19.0, "exe.run"), (19.0, 20.0, "fetch")]
    found = union.intervals(device, host, {step: ({}, {})})
    # chip 0: 1 + 1 + 3 (the nested all-gather counts once) = 5 s; chip
    # 1: 2 s (the all-to-all lies past the window); over 2 steps
    assert union.union_ms_per_step(found, (), collective.COLLECTIVES) \
        == pytest.approx((5.0 + 2.0) / 2 / 2 * 1e3)
    monkeypatch.setattr(union, "_state", dict(union._state, last=found))
    assert collective.compute(None) == pytest.approx(1750.0)
    # off a mesh no collective runs: nothing, not a zero
    alone = union.intervals({"/device:TPU:0": device["/device:TPU:0"][:1]},
                            host, {step: ({}, {})})
    monkeypatch.setattr(union, "_state", dict(union._state, last=alone))
    assert collective.compute(None) is None
    monkeypatch.setattr(union, "_state", dict(union._state, last=None))
    assert collective.compute(None) is None
    # the limit PERF.md section 3 names: an exchange under another NAME (a
    # fusion that wraps an all-reduce, an async wrapper, a permute's send
    # and recv) is not seen; the dp4 cell has none, a later cell checks
    hidden = union.intervals({"/device:TPU:0": [
        (1.0, 2.0, "%fusion.2 = f32[2] fusion(), calls=%all-reduce.9", step),
        (2.0, 3.0, "%async-collective-start.1 = f32[2] async-start()", step),
        (3.0, 4.0, "%send.1 = f32[2] send()", step),
        (4.0, 5.0, "%recv-done.1 = f32[2] recv-done()", step)]},
        host, {step: ({}, {})})
    monkeypatch.setattr(union, "_state", dict(union._state, last=hidden))
    assert collective.compute(None) is None


def test_step_mfu_is_required_flop_over_busy_device_time():
    step_mfu = reader("step_mfu")
    run = types.SimpleNamespace(
        trace={"busy_s": 2.0, "steps": 10}, chips=4, device_kind="k",
        flops_per_sample=1e9, samples_per_step=512,
        peak=lambda kind, what: 1e12)
    # 512e9 FLOP a step over 0.2 s x 4 chips x 1e12
    assert step_mfu.compute(run) == pytest.approx(64.0)
    for trace in (None, {"busy_s": 0.0, "steps": 10},
                  {"busy_s": 2.0, "steps": 0}):
        run.trace = trace
        assert step_mfu.compute(run) is None
