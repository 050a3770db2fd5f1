"""Profiler, timeline tool, op bench harness, debugger/net_drawer, and
contrib estimators (reference: platform/profiler.h, tools/timeline.py,
operators/benchmark/op_tester.cc, fluid/debugger.py, contrib/
memory_usage_calc.py, op_frequence.py, extend_optimizer/)."""
import contextlib
import json
import os
import re
import subprocess
import sys
import types

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import core, profiler


def _mlp_program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", shape=[8], dtype="float32")
        h = fluid.layers.fc(x, 16, act="relu")
        y = fluid.layers.fc(h, 4, act="softmax")
        loss = fluid.layers.mean(y)
    return main, startup, loss


# ----------------------------------------------------------------- profiler
def test_profiler_collects_and_reports(tmp_path, capsys):
    main, startup, loss = _mlp_program()
    exe = fluid.Executor()
    scope = core.Scope()
    ppath = str(tmp_path / "profile.json")
    with fluid.scope_guard(scope):
        exe.run(startup)
        with profiler.profiler(state="CPU", sorted_key="total",
                               profile_path=ppath):
            for _ in range(3):
                exe.run(main, feed={"x": np.ones((2, 8), "float32")},
                        fetch_list=[loss])
    out = capsys.readouterr().out
    assert "Profiling Report" in out
    assert "compiled_step" in out
    with open(ppath) as _pf:
        trace = json.load(_pf)
    names = {e["name"] for e in trace["traceEvents"]}
    assert "compiled_step" in names
    assert len(trace["traceEvents"]) >= 3


def test_profiler_record_event_nesting(tmp_path):
    profiler.start_profiler(state="CPU")
    with profiler.record_event("outer"):
        with profiler.record_event("inner"):
            pass
    from paddle_tpu.fluid.profiler import _prof
    names = [e.name for e in _prof.events]
    profiler.stop_profiler(profile_path=str(tmp_path / "p.json"))
    assert names == ["inner", "outer"]  # inner closes first


def test_profiler_eager_per_op_spans(tmp_path):
    # stateful op (py print path) forces the eager executor → per-op spans
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", shape=[4], dtype="float32")
        y = fluid.layers.fc(x, 4)
        arr = fluid.layers.create_array("float32")
        i = fluid.layers.fill_constant([1], "int64", 0)
        fluid.layers.array_write(y, i, arr)
    exe = fluid.Executor()
    scope = core.Scope()
    profiler.start_profiler(state="CPU")
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed={"x": np.ones((2, 4), "float32")},
                fetch_list=[y])
    from paddle_tpu.fluid.profiler import _prof
    names = {e.name for e in _prof.events}
    profiler.stop_profiler(profile_path="")
    assert "mul" in names or "elementwise_add" in names


def _train_program(optimizer):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", shape=[8], dtype="float32")
        y = fluid.data("y", shape=[1], dtype="int64")
        h = fluid.layers.fc(x, 16, act="relu")
        p = fluid.layers.fc(h, 4, act="softmax")
        loss = fluid.layers.mean(fluid.layers.cross_entropy(p, y))
        optimizer.minimize(loss)
    feed = {"x": np.ones((4, 8), "float32"), "y": np.zeros((4, 1), "int64")}
    return main, startup, loss, feed


def _step_block(exe):
    return [c for c in exe._compiled_cache.values()
            if getattr(c, "kind", None) == "compiled"][-1]


@pytest.mark.parametrize("optimizer,op", [
    (lambda: fluid.optimizer.Adam(1e-3), "adam"),
    (lambda: fluid.optimizer.Momentum(0.1, 0.9), "momentum")])
def test_compiled_step_carries_fluid_op_scopes(optimizer, op):
    """Every Fluid op is traced under `<phase>/<op type>`: the compiled
    module's instructions, fusions included, say in their op_name which
    op of which phase they came from (docs/OBSERVABILITY.md)."""
    import re
    import jax
    main, startup, loss, feed = _train_program(optimizer())
    exe, scope = fluid.Executor(), core.Scope()
    exe.run(startup, scope=scope)
    exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    text = _step_block(exe).lowered(
        scope, {k: jax.numpy.asarray(v) for k, v in feed.items()},
        jax.random.key(0)).compile().as_text()
    scopes = set(re.findall(r'op_name="jit\(_step\)/((?:fwd|bwd|opt)/\w+)',
                            text))
    assert {"fwd/mul", "fwd/relu", "fwd/softmax", "fwd/cross_entropy",
            "fwd/mean", "bwd/mul_grad", "bwd/relu_grad", "bwd/softmax_grad",
            "opt/" + op} <= scopes
    # the loss op (role 256) is forward; no grad op is, no optimizer op
    assert not [s for s in scopes if s.startswith("fwd/")
                and (s.endswith("_grad") or s == "fwd/" + op)]
    fusions = [ln for ln in text.splitlines() if " fusion(" in ln]
    assert fusions and all("op_name=" in ln for ln in fusions)


def test_profiler_session_does_not_change_the_lowered_step():
    """One call site, no extra frame: the step's lowered text WITH debug
    info (the Python stack it was traced under, which a Pallas kernel
    carries into the compile-cache key) is the same inside a profiler
    session and outside one."""
    def lowered_under(session):
        with fluid.unique_name.guard():  # the same variable names twice
            main, startup, loss, feed = _train_program(
                fluid.optimizer.SGD(0.1))
        exe, scope = fluid.Executor(), core.Scope()
        exe.run(startup, scope=scope)
        texts = []

        def two_steps():
            # the first traces the step, under the stack in question;
            # the second goes through the same frames to where the
            # executor calls the jitted step, and lowers it there
            exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
            block = _step_block(exe)
            jitted = block._jitted

            def lowering(*args):
                texts.append(jitted.lower(*args).as_text(debug_info=True))
                return jitted(*args)
            block._jitted = lowering
            exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
        with (profiler.profiler(state="CPU", profile_path="") if session
              else contextlib.nullcontext()):
            two_steps()  # one line: the test's own frame is in the text
        return texts[0]

    # one call site for all three (its line and column are in the text);
    # the first warms jax.numpy's own trace caches, which number the
    # locations of a cold trace differently
    _, plain, in_session = [lowered_under(s) for s in (False, False, True)]
    assert "executor.py" in plain  # the debug info is there
    assert plain == in_session


def test_stage_spans_once_a_step_nested_in_one_trace(tmp_path):
    """The six stage spans of the compiled one-dispatch path, in a
    session's events: once a step, in order, inside one another's gaps
    and never overlapping, all inside the step's one `exe:run` span, one
    trace id a step."""
    main, startup, loss, feed = _train_program(fluid.optimizer.SGD(0.1))
    exe, scope = fluid.Executor(), core.Scope()
    exe.run(startup, scope=scope)
    for _ in range(2):
        exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    stages = ["exe:feed", "exe:lookup", "exe:place", "compiled_step",
              "exe:write_back", "exe:fetch"]
    with profiler.profiler(state="CPU", profile_path=""):
        for _ in range(3):
            exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
        events = [e for e in profiler.snapshot_events()
                  if e["cat"] == "executor"]
    runs = [e for e in events if e["name"] == "exe:run"]
    events = [e for e in events if e["name"] != "exe:run"]
    assert sorted(e["name"] for e in events) == sorted(stages * 3)
    events.sort(key=lambda e: e["start"])
    assert [e["name"] for e in events] == stages * 3
    assert all(a["end"] <= b["start"] for a, b in zip(events, events[1:]))
    by_trace = {}
    for e in events:
        by_trace.setdefault(e["trace_id"], []).append(e["name"])
    assert None not in by_trace and list(by_trace.values()) == [stages] * 3
    # the parent: one a step, around that step's six and no other's
    runs.sort(key=lambda e: e["start"])
    assert [r["trace_id"] for r in runs] == list(by_trace)
    assert all(a["end"] <= b["start"] for a, b in zip(runs, runs[1:]))
    for i, run in enumerate(runs):
        inside = [e for e in events
                  if run["start"] <= e["start"] and e["end"] <= run["end"]]
        assert inside == events[6 * i:6 * i + 6]
    feed_span = events[0]
    assert feed_span["args"] == {"arrays": 2, "bytes": 4 * 8 * 4 + 4 * 4}
    assert events[1]["args"] == {"hit": True}
    assert events[2]["args"]["arrays"] >= 4
    # outside a session nothing is recorded, and nothing syncs
    exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    assert not profiler.is_profiling()


def test_a_jax_profiler_trace_holds_exe_run_its_stages_and_a_gc_pause(
        tmp_path):
    """Whoever starts `jax.profiler` finds the step there with no call
    into the program: `exe:run` around its six stages and a `gc:gen2`
    annotation over a collection, on one clock; and a step record's
    `t0`, mapped to wall time by a (wall, perf) anchor pair as the shard
    takes one, is where the trace has that run's `exe:run` start."""
    import gc
    import glob
    import time
    import jax
    from jax.profiler import ProfileData
    from paddle_tpu.fluid import telemetry
    main, startup, loss, feed = _train_program(fluid.optimizer.SGD(0.1))
    exe, scope = fluid.Executor(), core.Scope()
    exe.run(startup, scope=scope)
    for _ in range(2):
        exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    anchor_wall, anchor_perf = time.time(), time.perf_counter()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for step in range(3):
            exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
            if step == 1:
                gc.collect()
    finally:
        jax.profiler.stop_trace()
    records = list(telemetry.STEPS)[-3:]
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    stages = {"exe:feed", "exe:lookup", "exe:place", "compiled_step",
              "exe:write_back", "exe:fetch"}
    events, started_ns = [], None
    for plane in ProfileData.from_file(path).planes:
        started_ns = dict(plane.stats).get("profile_start_time", started_ns)
        for line in plane.lines:
            events += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                       for e in line.events
                       if e.name in stages | {"exe:run", "gc:gen2"}]
    runs = sorted(e for e in events if e[2] == "exe:run")
    assert len(runs) == 3
    for (start, end, _), record in zip(runs, records):
        inside = sorted(e for e in events if e[2] in stages
                        and start <= e[0] and e[1] <= end)
        assert {e[2] for e in inside} == stages and len(inside) == 6
        # an event starts `start` ns after the profile did, by the wall
        wall = anchor_wall + (record.t0 - anchor_perf)
        assert abs((started_ns + start) * 1e-9 - wall) < 5e-3
        assert (end - start) * 1e-9 == pytest.approx(record.run_s, abs=1e-3)
    (pause,) = [e for e in events if e[2] == "gc:gen2"]
    assert runs[1][1] <= pause[0] and pause[1] <= runs[2][0]
    assert (pause[1] - pause[0]) * 1e-9 == pytest.approx(
        records[2].gc_s, rel=0.5)
    assert records[2].gc_gen == 2


# ----------------------------------------------------------------- timeline
def test_timeline_merge(tmp_path):
    p0 = tmp_path / "p0.json"
    p1 = tmp_path / "p1.json"
    for i, p in enumerate((p0, p1)):
        p.write_text(json.dumps({"traceEvents": [
            {"name": f"op{i}", "ph": "X", "pid": 99, "tid": 1,
             "ts": 0, "dur": 10}]}))
    out = tmp_path / "t.json"
    r = subprocess.run(
        [sys.executable, "tools/timeline.py",
         "--profile_path", f"w0={p0},w1={p1}",
         "--timeline_path", str(out)],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    merged = json.loads(out.read_text())
    pids = {e["pid"] for e in merged["traceEvents"]}
    assert pids == {0, 1}
    names = {e.get("args", {}).get("name") for e in merged["traceEvents"]
             if e.get("ph") == "M"}
    assert names == {"w0", "w1"}


# ----------------------------------------------------------------- op bench
def test_op_bench_harness():
    sys.path.insert(0, "tools")
    try:
        from op_bench import bench_op, parse_inputs, parse_attrs
    finally:
        sys.path.pop(0)
    res = bench_op("softmax", parse_inputs("X:8x32:float32"),
                   parse_attrs(["axis=-1"]), repeat=5, warmup=1)
    assert res["op"] == "softmax"
    assert res["eager_ms"] > 0 and res["jit_ms"] > 0


# ------------------------------------------------------ debugger/net_drawer
def test_debugger_and_net_drawer(tmp_path):
    from paddle_tpu.fluid import debugger, net_drawer
    main, startup, loss = _mlp_program()
    text = debugger.pprint_program_codes(main)
    assert "softmax" in text and "mul" in text
    dot = net_drawer.draw_graph(startup, main,
                                path=str(tmp_path / "g.dot"))
    assert dot.startswith("digraph") and "softmax" in dot
    assert (tmp_path / "g.dot").exists()


# ------------------------------------------------------- contrib estimators
def test_memory_usage_and_op_freq_and_model_stat():
    from paddle_tpu.fluid.contrib import (memory_usage, op_freq_statistic,
                                          summary)
    main, startup, loss = _mlp_program()
    lo, hi = memory_usage(main, batch_size=32)
    assert 0 < lo < hi
    uni, adj = op_freq_statistic(main)
    assert uni["mul"] == 2
    assert any("mul->elementwise_add" == k for k in adj)
    params, flops = summary(main, print_table=False)
    assert params == 8 * 16 + 16 + 16 * 4 + 4
    assert flops > 0


def test_profiler_nested_sessions(tmp_path, capsys):
    """Inner profiler context must not end the outer session."""
    profiler.start_profiler(state="CPU")
    with profiler.record_event("a"):
        pass
    with profiler.profiler(state="CPU",
                           profile_path=str(tmp_path / "inner.json")):
        with profiler.record_event("b"):
            pass
    assert profiler.is_profiling()  # outer still live
    with profiler.record_event("c"):
        pass
    from paddle_tpu.fluid.profiler import _prof
    names = [e.name for e in _prof.events]
    profiler.stop_profiler(profile_path=str(tmp_path / "outer.json"))
    assert names == ["a", "b", "c"]
    assert not (tmp_path / "inner.json").exists()
    assert (tmp_path / "outer.json").exists()


def test_record_event_decorator():
    calls = []

    @profiler.RecordEvent("decorated")
    def fn(x):
        calls.append(x)
        return x + 1

    profiler.start_profiler(state="CPU")
    assert fn(1) == 2
    from paddle_tpu.fluid.profiler import _prof
    names = [e.name for e in _prof.events]
    profiler.stop_profiler(profile_path="")
    assert names == ["decorated"] and calls == [1]


def test_model_stat_excludes_optimizer_state_and_transpose():
    from paddle_tpu.fluid.contrib import summary
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", shape=[4], dtype="float32")
        y = fluid.layers.fc(x, 4)
        loss = fluid.layers.mean(y)
    p0, _ = summary(main, print_table=False)
    with fluid.program_guard(main, startup):
        fluid.optimizer.Adam(0.01).minimize(loss)
    p1, _ = summary(main, print_table=False)
    assert p0 == p1 == 4 * 4 + 4  # adam moments don't inflate the count
    # transpose_Y matmul flops use the transposed output dim
    m2, s2 = fluid.Program(), fluid.Program()
    with fluid.program_guard(m2, s2):
        a = fluid.data("a", shape=[8, 16], dtype="float32",
                       append_batch_size=False)
        b = fluid.data("b", shape=[32, 16], dtype="float32",
                       append_batch_size=False)
        fluid.layers.matmul(a, b, transpose_y=True)
    _, fl = summary(m2, print_table=False)
    assert fl == 2 * 8 * 16 * 32


def test_decoupled_decay_dygraph_mode():
    import paddle_tpu.fluid.dygraph as dygraph
    from paddle_tpu.fluid.dygraph import to_variable
    from paddle_tpu.fluid.contrib import extend_with_decoupled_weight_decay
    SGDW = extend_with_decoupled_weight_decay(fluid.optimizer.SGD)
    with dygraph.guard():
        net = dygraph.Linear(4, 4)
        opt = SGDW(weight_decay=0.5, learning_rate=0.1,
                   parameter_list=net.parameters())
        before = np.abs(net.weight.numpy()).sum()
        # zero input -> zero grads; only the decoupled decay moves W
        loss = fluid.layers.reduce_mean(
            net(to_variable(np.zeros((2, 4), "float32"))))
        loss.backward()
        opt.minimize(loss)
        after = np.abs(net.weight.numpy()).sum()
    np.testing.assert_allclose(after, before * (1 - 0.1 * 0.5), rtol=1e-5)


def test_extend_with_decoupled_weight_decay():
    from paddle_tpu.fluid.contrib import extend_with_decoupled_weight_decay
    AdamW = extend_with_decoupled_weight_decay(fluid.optimizer.Adam)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", shape=[4], dtype="float32")
        y = fluid.layers.fc(x, 4)
        loss = fluid.layers.mean(y)
        opt = AdamW(weight_decay=0.5, learning_rate=0.1)
        opt.minimize(loss)
    exe = fluid.Executor()
    scope = core.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        wname = [p.name for p in main.all_parameters()
                 if p.shape == (4, 4)][0]
        before = np.asarray(scope.find_var(wname).get_tensor().array).copy()
        exe.run(main, feed={"x": np.zeros((2, 4), "float32")},
                fetch_list=[loss])
        after = np.asarray(scope.find_var(wname).get_tensor().array)
    # zero input -> zero grad for W; decay still shrinks W (decoupled)
    assert np.abs(after).sum() < np.abs(before).sum()
    with pytest.raises(TypeError):
        extend_with_decoupled_weight_decay(object)


def test_multiprocess_dataloader_matches_inline():
    """use_multiprocess=True runs the generator in a child process with
    shared-memory batch transport (reference reader.py:684 multiprocess
    GeneratorLoader over mmap allocations) and must yield identical
    batches."""
    import numpy as np
    import paddle_tpu.fluid as fluid

    def make_reader():
        def reader():
            rng = np.random.RandomState(42)
            for i in range(7):
                yield {"x": rng.rand(4, 3).astype("float32"),
                       "y": np.full((4, 1), i, "int64")}
        return reader

    inline = fluid.DataLoader.from_generator(feed_list=[], capacity=4)
    inline.set_batch_generator(make_reader())
    mp_loader = fluid.DataLoader.from_generator(
        feed_list=[], capacity=4, use_multiprocess=True)
    mp_loader.set_batch_generator(make_reader())

    got_inline = list(inline)
    got_mp = list(mp_loader)
    assert len(got_inline) == len(got_mp) == 7
    for a, b in zip(got_inline, got_mp):
        assert set(a) == set(b)
        for k in a:
            np.testing.assert_array_equal(a[k], b[k])


def test_api_signatures_tool():
    """tools/api_signatures.py dumps the public surface without import
    failures (reference print_signatures.py for API-diff checking)."""
    import subprocess, sys, os
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    res = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "api_signatures.py"),
         "--module", "paddle_tpu.fluid.layers"],
        capture_output=True, text=True, timeout=180,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert res.returncode == 0, res.stderr[-1500:]
    lines = res.stdout.strip().splitlines()
    assert len(lines) > 150
    assert not any("import failed" in l for l in lines)
    assert any(l.startswith("paddle_tpu.fluid.layers.fc(") for l in lines)


def test_mfu_report_xla_cost_analysis():
    """tools/mfu_report.py: XLA's own cost analysis of the FULL compiled
    train step — flops, bytes accessed, arithmetic intensity — as one
    JSON-able dict. Those counts hold on the CPU; the step time and the
    MFU are the chip's, and without a TPU the timed report refuses."""
    import json
    from tools.mfu_report import report

    with pytest.raises(SystemExit, match="needs a TPU"):
        report("mnist", steps=2)
    out = report("mnist", timed=False)
    assert out["xla_flops_per_step"] > 1e6
    assert out["device"]["platform"] == "cpu"
    assert not {"step_ms", "achieved_tflops", "mfu_vs_bf16_peak"} & set(out)
    # bytes-accessed keys are optional per the tool's contract (some
    # jax/backends omit "bytes accessed" from cost_analysis)
    if "xla_bytes_accessed" in out:
        assert out["xla_bytes_accessed"] > 0
        assert out["flops_per_byte"] > 0
    json.dumps(out)


# ------------------------------------------------ one peaks table, read
def test_the_tools_divide_by_the_benchmarks_peaks_table():
    """tools/ keeps no peak of its own: for every kind the benchmark
    knows, `bf16_peak_flops` is `benchmark/peaks.py`'s number."""
    from tools import device_peaks
    peaks = device_peaks._benchmark_peaks()
    assert peaks.PEAKS
    for kind in peaks.PEAKS:
        assert device_peaks.bf16_peak_flops(
            types.SimpleNamespace(device_kind=kind)) == \
            peaks.peak(kind, "bf16_flops_per_s")


def test_a_device_kind_without_a_published_peak_is_an_error():
    """And the error sends its reader to the one table."""
    from tools.device_peaks import bf16_peak_flops
    with pytest.raises(SystemExit, match=r"add it to benchmark/peaks\.py"):
        bf16_peak_flops(types.SimpleNamespace(device_kind="TPU v0"))


# --------------------------------- the documents describe the tree as it is
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCUMENTS = ["README.md"] + sorted(
    "docs/" + f for f in os.listdir(os.path.join(ROOT, "docs"))
    if f.endswith(".md"))
# a reader's own script in a command line, not a file of this repo
NOT_OURS = {"train.py"}
_NOT_THE_TREE = {".git", "chiprun_out", ".chipcheck", "__pycache__",
                 ".pytest_cache"}


@pytest.fixture(scope="module")
def tree_files():
    files = set()
    for d, dirs, names in os.walk(ROOT):
        dirs[:] = [x for x in dirs if x not in _NOT_THE_TREE
                   and not x.startswith(".xla_cache")]
        files.update(os.path.relpath(os.path.join(d, n), ROOT)
                     for n in names)
    return files


def _code_spans(text):
    """What a document sets as code: inline spans, and the lines of
    fenced blocks."""
    fenced = False
    for line in text.splitlines():
        if line.lstrip().startswith("```"):
            fenced = not fenced
        elif fenced:
            yield line
        else:
            yield from re.findall(r"`([^`]+)`", line)


def _cited_paths(text):
    """The `*.py` / `*.md` paths among them, `:line` and `::test` cut
    off; a pattern (`<name>.py`, `test_*.py`) is no path."""
    for span in _code_spans(text):
        for token in re.split(r"[\s(),;=\[\]|]+", span):
            token = token.split(":", 1)[0]
            if token.startswith("./"):
                token = token[2:]
            if re.fullmatch(r"[\w./-]+\.(?:py|md)", token):
                yield token


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_file_a_document_cites_exists(document, tree_files):
    """A path a document cites is a file of this tree: as written from
    the root (`tools/op_bench.py`, `PERF.md`), or as the tail of one
    (`fluid/executor.py`, `ps_rpc.py`). A deleted harness or page that a
    document still sends its reader to fails here."""
    with open(os.path.join(ROOT, document)) as f:
        cited = set(_cited_paths(f.read())) - NOT_OURS
    assert cited, f"{document}: the rule found no path at all"
    missing = sorted(p for p in cited if p not in tree_files
                     and not any(f.endswith("/" + p) for f in tree_files))
    assert not missing, f"{document} cites files that do not exist: {missing}"
