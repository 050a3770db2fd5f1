"""The benchmark, off the chip: what the suite can hold it to on the CPU.

The rehearsal (`run.py --tiny`) runs every phase of a cell at a toy size
and must never call itself correct or give a metric a value; without a
TPU the command refuses; the manifest, the files it names and the
arithmetic of the yardstick (FLOP counts, percentile, trace reduction)
are checked against values worked out by hand. Every run of `run.py` is
a subprocess with a compile cache of its own: the harness sets process-
wide flags, which must not leak into this worker's other tests. No test
here describes a TPU topology.
"""
import importlib.util
import json
import math
import os
import re
import shutil
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
# what a traced rehearsal can read without a chip: the benchmark's own
# clocks and counters, and (listed since PR 38) the four readers of the
# program's step records, whose ring a rehearsal's program has too
HOST_READ = {"build_s", "compile_s", "compiles_in_window",
             "dispatch_ms.train", "exe_ms.run_p95", "exe_ms.self",
             "gc_ms_per_step", "slow_steps_in_window"}
# spans and counters of the program that their readers take through the
# chip's trace, or only once it is there: silent in a rehearsal
NEEDS_TRACE = {"exe_ms.feed", "exe_ms.place", "exe_ms.dispatch",
               "exe_ms.write_back", "trace_lower_s", "compile_cache_misses",
               "moe_rows_per_step", "attn_kv_blocks_per_step",
               "attn_grid_steps_per_step"}


def load(path):
    spec = importlib.util.spec_from_file_location(
        "_t_" + re.sub(r"\W", "_", os.path.relpath(path, REPO)), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def run_cell(tmp_path, root, *args, devices=1, pythonpath=REPO):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla_cache"),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.pop("PYTHONPATH", None)
    if pythonpath:
        env["PYTHONPATH"] = pythonpath
    res = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), *args],
        capture_output=True, text=True, env=env, timeout=600, cwd=root)
    rows = [json.loads(line) for line in res.stdout.splitlines()]
    return res, rows


def check_rehearsal(res, rows, group, cell):
    assert res.returncode != 0, res.stderr[-2000:]
    assert "rehearsal" in res.stderr
    last = rows[-1]
    extra = {"breakdown"} if group == "per_layer" else set()
    assert RESULT_KEYS <= set(last) <= RESULT_KEYS | extra, sorted(last)
    assert last["correct"] is False
    assert last["device"]["platform"] == "cpu"
    assert last["attempted"] >= 1 and last["failed"] == 0
    declared = {m["name"]: m["unit"] for m in manifest()[group]
                if cell in m.get("workloads", [cell])}
    assert set(last["metrics"]) <= set(declared)
    for name, got in last["metrics"].items():
        # a CPU's number never stands under a device metric's name
        assert got == {"value": None, "unit": declared[name]}
    return {r["phase"]: r for r in rows if "phase" in r}


def rehearsal_names(m, cell, trace):
    """(must, may): the names a rehearsal's line of `cell` has to carry
    and the names it may carry, from the manifest `m`. Untraced the two
    are one set, the cell's end-to-end metrics EXACTLY. Traced: what the
    host can read is there (`HOST_READ`), and nothing but the cell's
    per-layer entries that need no chip's trace (not `device_trace`, not
    `NEEDS_TRACE`); what only a chip's trace holds is left out, not
    invented. On PR 38's manifest the two are one set here too; an entry
    a later PR appends may read in a rehearsal or stay silent."""
    if not trace:
        names = {e["name"] for e in m["end_to_end"]
                 if cell in e.get("workloads", [cell])}
        return names, names
    return HOST_READ, {p["name"] for p in m["per_layer"]
                       if cell in p.get("workloads", [cell])
                       and p["source"] != "device_trace"} - NEEDS_TRACE


def test_rehearsal_names_are_exact_untraced_and_leave_room_traced():
    m = manifest()
    source = {p["name"]: p["source"] for p in m["per_layer"]}
    for cell in (w["name"] for w in m["workloads"]):
        must, may = rehearsal_names(m, cell, 0)
        assert must == may == {e["name"] for e in m["end_to_end"]}
        assert not may & set(source)  # no per-layer name in such a line
        must, may = rehearsal_names(m, cell, 1)
        assert must <= may and not may & NEEDS_TRACE
        assert "device_trace" not in {source[n] for n in may}
    # an entry a later PR appends for one cell may read there, need not,
    # and has no place in another cell's line
    m["per_layer"].append({"name": "toy_fetches", "source": "program_span",
                           "workloads": ["resnet50.b256_i224"]})
    grown = rehearsal_names(m, "resnet50.b256_i224", 1)
    assert "toy_fetches" in grown[1] - grown[0]
    assert "toy_fetches" not in rehearsal_names(m, "bert_base.b128_s128", 1)[1]


# ---------------------------------------------------------------- the runs
@pytest.mark.parametrize("cell,trace", [
    ("bert_base.b128_s128", 0), ("bert_base.b128_s128", 1),
    ("resnet50.b256_i224", 1)])
def test_tiny_rehearsal_runs_the_cell_and_never_reports_correct(
        tmp_path, cell, trace):
    res, rows = run_cell(tmp_path, REPO, "--workload", cell, "--seed",
                         str(2 ** 31 + 11), "--seconds", "1", "--trace",
                         str(trace), "--tiny")
    group = "per_layer" if trace else "end_to_end"
    phase = check_rehearsal(res, rows, group, cell)
    last = rows[-1]
    must, may = rehearsal_names(manifest(), cell, trace)
    assert must <= set(last["metrics"]) <= may, sorted(last["metrics"])
    if trace:
        assert phase["trace"]["trace"] is None
    else:
        # an untraced line carries the end-to-end metrics and no other:
        # no per-layer reader is loaded, so none can leak into it
        assert must == may == {"samples_per_s", "step_ms_p95", "mfu_pct",
                               "setup_s"}
    assert phase["start"]["compile_cache_dir"] == str(tmp_path / "xla_cache")
    assert phase["setup"]["compiles"] > 0
    assert phase["setup"]["devices_holding_parameters"] == 1
    assert len(phase["window"]["step_ms"]) == last["attempted"]
    assert phase["checks"]["no_compile_in_window"] is True
    assert phase["checks"]["losses_finite"] is True
    assert phase["checks"]["on_a_tpu"] is False
    # the CPU has no allocator counter: the footprint of the largest
    # loaded executable is what the line reports
    assert phase["memory"]["peak_bytes_in_use"] == [0]
    assert last["device"]["memory_peak_bytes"] \
        == phase["memory"]["largest_executable_bytes"] > 0


def test_same_seed_same_inputs(tmp_path):
    config = json.load(open(os.path.join(BENCH, "configs", "bert_base.json")))
    model = load(os.path.join(BENCH, "configs", "bert_base.py"))
    config, traffic = model.tiny(config, dict(
        batch=128, seq_len=128, predictions=19, dropout=0.0, pool=8))
    a, b, c = (model.make_batches(config, traffic, seed, 2)
               for seed in (2 ** 31 + 5, 2 ** 31 + 5, 7))
    assert all((a[i][k] == b[i][k]).all() for i in range(2) for k in a[i])
    assert not (a[0]["src_ids"] == c[0]["src_ids"]).all()
    assert not (a[0]["src_ids"] == a[1]["src_ids"]).all()
    # every prediction lies in its own sequence: the mask feeds split
    # over a data-parallel mesh where the batch does
    rows = a[0]["mask_pos"].ravel() // traffic["seq_len"]
    assert (rows == [i // traffic["predictions"]
                     for i in range(len(rows))]).all()


@pytest.mark.parametrize("args", [
    ("--workload", "bert_base.b128_s128"),
    ("--workload", "resnet50.b256_i224", "--trace", "1")])
def test_without_a_tpu_it_refuses_before_any_work(tmp_path, args):
    res, rows = run_cell(tmp_path, REPO, *args, "--seconds", "1")
    assert res.returncode != 0
    assert rows == [] and "needs a TPU" in res.stderr
    assert not (tmp_path / "xla_cache").exists()


def test_unknown_workload_is_refused(tmp_path):
    res, rows = run_cell(tmp_path, REPO, "--workload", "no_such.cell",
                         "--tiny")
    assert res.returncode != 0 and rows == []
    assert "no workload" in res.stderr


# ------------------------------------------- later cells are files, not edits
TOY_CONFIG_PY = '''
import numpy as np


def build(config, traffic):
    import paddle_tpu.fluid as fluid
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", shape=[config["width"]], dtype="float32")
        y = fluid.data("y", shape=[1], dtype="int64")
        h = fluid.layers.fc(x, config["width"], act="relu")
        p = fluid.layers.fc(h, config["classes"], act="softmax")
        loss = fluid.layers.mean(fluid.layers.cross_entropy(p, y))
        fluid.optimizer.SGD(0.1).minimize(loss)
    return main, startup, [loss]


def make_batches(config, traffic, seed, k):
    rng = np.random.default_rng(seed)
    b = traffic["batch"]
    return [{"x": rng.random((b, config["width"]), dtype=np.float32),
             "y": rng.integers(0, config["classes"], (b, 1), dtype=np.int64)}
            for _ in range(k)]


def flops_per_sample(config, traffic):
    return 6.0 * config["width"] * (config["width"] + config["classes"])


def tiny(config, traffic):
    return config, traffic
'''

TOY_METRIC_PY = '''
def compute(run):
    return float(len(run.spans.get("fetch", [])))
'''


@pytest.fixture()
def copy(tmp_path):
    """A copy of the benchmark alone, as a later PR would find it."""
    root = tmp_path / "copy"
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    return root


def test_a_new_config_cell_and_metric_are_files_and_manifest_entries(
        tmp_path, copy):
    """A throw-away configuration, workload and per-layer metric, added
    to a copy as files and manifest entries: no existing file of the
    benchmark is edited, and `run.py` runs them."""
    before = {p: open(p, "rb").read() for p in
              (os.path.join(d, f) for d, _, fs in os.walk(copy / "benchmark")
               for f in fs)}
    bench = copy / "benchmark"
    (bench / "configs" / "toy.json").write_text(json.dumps({
        "name": "toy", "width": 16, "classes": 4, "flags": {},
        "correct": {"first_loss_rel_tol": 0.5, "falling_n": 3}}))
    (bench / "configs" / "toy.py").write_text(TOY_CONFIG_PY)
    (bench / "workloads" / "toy.b8.json").write_text(json.dumps({
        "name": "toy.b8", "mesh": None,
        "traffic": {"batch": 8, "pool": 3}}))
    (bench / "layer_metrics" / "toy_fetches.py").write_text(TOY_METRIC_PY)
    m = json.loads((copy / "BENCHMARK.json").read_text())
    m["configs"].append({"name": "toy", "source": "none", "reduced": [],
                         "file": "benchmark/configs/toy.json", "why": "t"})
    m["workloads"].append({"name": "toy.b8", "config": "toy",
                           "traffic": "b8", "chips": 1, "why": "t"})
    m["per_layer"].append({"name": "toy_fetches", "unit": "count",
                           "better": "higher", "source": "program_span",
                           "layer": "Executor", "moves": "samples_per_s",
                           "workloads": ["toy.b8"]})
    (copy / "BENCHMARK.json").write_text(json.dumps(m))

    res, rows = run_cell(tmp_path, str(copy), "--workload", "toy.b8",
                         "--seconds", "0.5", "--trace", "1", "--tiny")
    assert res.returncode != 0 and rows, res.stderr[-2000:]
    assert "toy_fetches" in rows[-1]["metrics"]
    assert rows[-1]["correct"] is False
    for path, content in before.items():
        assert open(path, "rb").read() == content, path
    # and the metric of one cell is not read in another
    text = open(os.path.join(BENCH, "run.py")).read()
    names = [x["name"] for key in ("configs", "workloads", "per_layer")
             for x in manifest()[key]]
    assert [n for n in names if re.search(
        r"(?<![\w.])" + re.escape(n) + r"(?![\w.])", text)] == []


def test_the_manifest_grows_by_appended_entries_and_every_check_holds(copy):
    """The room a later PR has, shown and not promised: the REAL manifest
    grown in memory by a sixth configuration, an eighth cell on four
    chips, one more per-layer entry and one cell appended to a
    `workloads` list that is there; every manifest assertion of this
    directory's six files is a function of a manifest and holds on it,
    but for the toy's files, which it then supplies in a copy."""
    m = manifest()
    sizes = {k: len(m[k]) for k in ("configs", "workloads", "per_layer")}
    m["configs"].append({"name": "toy", "source": "none", "reduced": [],
                         "file": "benchmark/configs/toy.json", "why": "t"})
    m["workloads"].append({"name": "toy.dp4_b8", "config": "toy",
                           "traffic": "dp4_b8", "chips": 4, "why": "t"})
    m["per_layer"].append({"name": "toy_fetches", "unit": "count",
                           "better": "higher", "source": "program_span",
                           "layer": "Executor", "moves": "samples_per_s",
                           "workloads": ["toy.dp4_b8"]})
    listed = next(p for p in m["per_layer"] if p["name"] == "device_ms.attn")
    listed["workloads"].append("toy.dp4_b8")
    assert {k: len(m[k]) for k in sizes} == {k: n + 1
                                             for k, n in sizes.items()}
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 2
    checks = [check_manifest_keys_names_and_units,
              lambda m: check_manifest_names_files_that_exist(m, str(copy))]
    for sibling in ("test_trace_scopes", "test_step_records",
                    "test_qwen3_next_cell", "test_phi4_flash_cell",
                    "test_laguna_xs2_cell"):
        checks.append(load(os.path.join(
            REPO, "tests", "benchmark", sibling + ".py")).check_manifest)
    # the toy's files are not there yet, and that alone fails
    with pytest.raises(FileNotFoundError, match="toy"):
        check_manifest_names_files_that_exist(m, str(copy))
    bench = copy / "benchmark"
    (bench / "configs" / "toy.json").write_text(json.dumps({
        "name": "toy", "reduced": [], "width": 16, "classes": 4,
        "flags": {}, "correct": {"first_loss_rel_tol": 0.2,
                                 "falling_n": 3}}))
    (bench / "configs" / "toy.py").write_text(TOY_CONFIG_PY)
    (bench / "workloads" / "toy.dp4_b8.json").write_text(json.dumps({
        "name": "toy.dp4_b8", "mesh": {"dp": 4},
        "traffic": {"batch": 8, "pool": 3}}))
    (bench / "layer_metrics" / "toy_fetches.py").write_text(TOY_METRIC_PY)
    for check in checks:
        check(m)


def test_a_four_chip_cell_is_a_file_and_shards_over_four_devices(
        tmp_path, copy):
    """The queued data-parallel cell, rehearsed: `chips: 4` and a mesh in
    a workload file put the parameters on all four (virtual) devices."""
    (copy / "benchmark" / "workloads" / "bert_base.dp4_b512_s128.json"
     ).write_text(json.dumps({
         "name": "bert_base.dp4_b512_s128", "mesh": {"dp": 4},
         "traffic": {"batch": 512, "seq_len": 128, "predictions": 19,
                     "dropout": 0.0, "input_mask": False, "pool": 8}}))
    m = json.loads((copy / "BENCHMARK.json").read_text())
    m["workloads"].append({"name": "bert_base.dp4_b512_s128",
                           "config": "bert_base", "traffic": "dp4_b512_s128",
                           "chips": 4, "why": "t"})
    (copy / "BENCHMARK.json").write_text(json.dumps(m))
    args = ("--workload", "bert_base.dp4_b512_s128", "--seconds", "0.5",
            "--tiny")
    res, rows = run_cell(tmp_path, str(copy), *args, devices=2)
    assert res.returncode != 0 and rows == []
    assert "needs 4 chips" in res.stderr
    res, rows = run_cell(tmp_path, str(copy), *args, devices=4)
    phase = check_rehearsal(res, rows, "end_to_end",
                            "bert_base.dp4_b512_s128")
    assert phase["start"]["device"]["count"] == 4
    assert phase["setup"]["devices_holding_parameters"] == 4
    assert phase["checks"]["parameters_on_the_chips"] is True


def test_the_benchmark_alone_does_not_run(tmp_path, copy):
    """BENCHMARK.json and the files under `paths`, with no program beside
    them: a non-zero exit and no result."""
    res, rows = run_cell(tmp_path, str(copy), "--workload",
                         "bert_base.b128_s128", "--tiny", pythonpath=None)
    assert res.returncode != 0 and rows == []
    assert "paddle_tpu" in res.stderr


def test_exe_run_has_one_call_site():
    """A Pallas kernel carries the call stack it was traced under into the
    executable's cache key: were the traced run to call `exe.run` from
    another line than the plain run, it would compile the step anew."""
    text = open(os.path.join(BENCH, "run.py")).read()
    assert len(re.findall(r"\bexe\.run\(program\b", text)) == 1


# ------------------------------------------------------------ the manifest
def check_manifest_keys_names_and_units(m):
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert m["paths"] == ["benchmark", "tests/benchmark"]
    assert 1 <= m["run_seconds"] <= 51
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
    for e in m["end_to_end"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= e["bound"] <= 0.1
        assert e["source"] in ("host_clock", "device_trace")
    for p in m["per_layer"]:
        assert set(p) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert p["source"] in SOURCES
        assert len(p["layer"]) <= 200 and "\n" not in p["layer"]
    names = [x["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for x in m[key]]
    assert all(NAME.match(n) for n in names), names
    metrics = [x["name"] for x in m["end_to_end"] + m["per_layer"]]
    assert len(set(metrics)) == len(metrics)
    assert all(UNIT.match(x["unit"]) and x["better"] in ("lower", "higher")
               for x in m["end_to_end"] + m["per_layer"])
    assert "setup_s" in metrics
    assert len(json.dumps(m)) < 64 * 1024
    four = sum(w["chips"] == 4 for w in m["workloads"])
    assert four <= max(1, len(m["workloads"]) // 4)


def test_manifest_keys_names_and_units():
    check_manifest_keys_names_and_units(manifest())


def check_manifest_names_files_that_exist(m, root=REPO):
    """`root` holds `benchmark/` and what the manifest's `file`s name."""
    bench = os.path.join(root, "benchmark")
    configs = {c["name"]: c for c in m["configs"]}
    assert {w["config"] for w in m["workloads"]} == set(configs)
    assert len({(w["config"], w["traffic"]) for w in m["workloads"]}) \
        == len(m["workloads"])
    for c in m["configs"]:
        assert c["file"].startswith("benchmark/configs/")
        with open(os.path.join(root, c["file"])) as f:
            body = json.load(f)
        assert body["name"] == c["name"] and body["reduced"] == c["reduced"]
        assert body["correct"]["first_loss_rel_tol"] <= 0.2
        model = load(os.path.join(root, c["file"][:-5] + ".py"))
        for fn in ("build", "make_batches", "flops_per_sample", "tiny"):
            assert callable(getattr(model, fn)), (c["name"], fn)
    for w in m["workloads"]:
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        with open(os.path.join(bench, "workloads", w["name"] + ".json")) as f:
            body = json.load(f)
        assert body["name"] == w["name"]
        assert body["traffic"]["batch"] > 0 and body["traffic"]["pool"] >= 2
        assert (body["mesh"] is not None) == (w["chips"] > 1)
    end_to_end = {e["name"] for e in m["end_to_end"]}
    for e in m["end_to_end"]:
        assert callable(load(os.path.join(
            bench, "end_to_end", e["name"] + ".py")).compute)
    for p in m["per_layer"]:
        assert p["moves"] in end_to_end
        assert callable(load(os.path.join(
            bench, "layer_metrics", p["name"] + ".py")).compute)
    for directory, _, files in os.walk(bench):
        for f in files:
            if "__pycache__" not in directory:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", f), f


def test_manifest_names_files_that_exist():
    check_manifest_names_files_that_exist(manifest())


@pytest.mark.parametrize("entry", manifest()["per_layer"],
                         ids=lambda entry: entry["name"])
def test_every_per_layer_entry_has_a_reader_that_says_none_or_a_number(
        entry, monkeypatch):
    """Each entry of the manifest, whoever listed it: a reader file whose
    `compute`, on a run with no trace, no span and no counter, returns
    None or a number and does not raise; a unit and a `source` the
    contract allows; cells the manifest has."""
    for shared in ("_benchmark_trace_scopes", "_benchmark_scope_union"):
        monkeypatch.delitem(sys.modules, shared, raising=False)
    compute = load(os.path.join(BENCH, "layer_metrics",
                                entry["name"] + ".py")).compute
    assert callable(compute)
    stats = load(os.path.join(BENCH, "stats.py"))
    got = compute(types.SimpleNamespace(
        trace=None, spans={}, counters={}, step_s=[], losses=[], chips=1,
        device_kind="cpu", samples_per_step=0, flops_per_sample=0.0,
        median=stats.median, percentile=stats.percentile,
        peak=load(os.path.join(BENCH, "peaks.py")).peak))
    assert got is None or (isinstance(got, (int, float))
                           and math.isfinite(got)), got
    assert UNIT.match(entry["unit"]) and entry["source"] in SOURCES
    assert entry["better"] in ("lower", "higher")
    cells = {w["name"] for w in manifest()["workloads"]}
    listed = entry.get("workloads")
    assert listed is None or (listed and set(listed) <= cells)


# ------------------------------------------------------------ the yardstick
def test_bert_flops_per_sample_is_the_closed_form():
    config = json.load(open(os.path.join(BENCH, "configs", "bert_base.json")))
    model = load(os.path.join(BENCH, "configs", "bert_base.py"))
    got = model.flops_per_sample(config, {"seq_len": 128, "predictions": 19})
    weights = 12 * (4 * 768 * 768 + 2 * 768 * 3072)
    want = (6 * weights * 128 + 12 * 12 * 128 * 128 * 768
            + 6 * 19 * 768 * 30522)
    assert got == want and abs(got - 69.7e9) < 0.05e9
    # the head is the 3.8% that bench.py's formula leaves out
    assert abs(6 * 19 * 768 * 30522 / got - 0.038) < 0.001
    assert math.isclose(math.log(config["classes"]), 10.326, abs_tol=1e-3)


def test_bert_attn_required_is_the_expectation_over_the_padded_lengths():
    """What `attn_roofline_pct` divides by on the padded s512 cell: every
    query row against the keys the input mask keeps, 384 of 512 in
    expectation (lengths uniform in 256-512), forward x 3."""
    config = json.load(open(os.path.join(BENCH, "configs", "bert_base.json")))
    model = load(os.path.join(BENCH, "configs", "bert_base.py"))
    padded = {"batch": 32, "seq_len": 512, "input_mask": True,
              "lengths": [256, 512]}
    got = model.attn_required(config, padded)
    # 12 layers x 12 heads x 64: 4 x 64 FLOP a head, query and kept key
    assert got["flop"] == 3 * 32 * 12 * 512 * 384 * 12 * 4 * 64
    # bf16, 768 wide: Q, O, dO, dQ + Q, O over all 512 rows (6), K, V
    # read forward and backward and their gradients over 384 (6)
    assert got["bytes"] == 32 * 12 * 2 * 768 * (6 * 512 + 6 * 384)
    assert abs(got["flop"] / 197e12 / 3.532e-3 - 1) < 1e-3
    assert abs(got["bytes"] / 819e9 / 3.872e-3 - 1) < 1e-3   # the bound
    # without a mask every key is kept; twice the batch, twice the work
    whole = model.attn_required(config, dict(padded, input_mask=False,
                                             batch=64))
    assert whole["flop"] == 2 * got["flop"] * 512 / 384
    assert whole["bytes"] == 64 * 12 * 2 * 768 * 12 * 512
    # where nothing is padded this is `flops_per_sample`'s attention term
    assert whole["flop"] / 64 == 12 * 12 * 512 * 512 * 768
    # at s128 the count is there, and the reader, finding no kernel in
    # the dense path's trace, says nothing
    with open(os.path.join(BENCH, "workloads",
                           "bert_base.b128_s128.json")) as f:
        short = model.attn_required(config, json.load(f)["traffic"])
    assert short["flop"] == 128 * 12 * 12 * 128 * 128 * 768


def test_resnet_flops_per_sample_is_the_count_of_its_shapes():
    config = json.load(open(os.path.join(BENCH, "configs", "resnet50.json")))
    model = load(os.path.join(BENCH, "configs", "resnet50.py"))
    macs = model.forward_multiply_adds(config, 224)
    # the stem alone, by hand: 112 x 112 outputs x 64 filters x 3 x 7 x 7
    assert macs > 112 * 112 * 64 * 3 * 49 == 118013952
    assert abs(macs / 4.1e9 - 1) < 0.02
    got = model.flops_per_sample(config, {"image_size": 224})
    assert got == 6 * macs and abs(got / (3 * 2 * 4.1e9) - 1) < 0.02
    # convolutions scale with the image's area, the classifier does not
    big = model.forward_multiply_adds(config, 448)
    assert big - 2048 * 1000 == 4 * (macs - 2048 * 1000)


def test_peaks_table_knows_the_v5e_and_refuses_the_rest():
    peaks = load(os.path.join(BENCH, "peaks.py"))
    assert peaks.peak("TPU v5 lite", "bf16_flops_per_s") == 197e12
    assert peaks.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(SystemExit):
        peaks.peak("cpu", "bf16_flops_per_s")


@pytest.mark.parametrize("values,q,want", [
    (list(range(1, 21)), 95, 19), (list(range(1, 201)), 95, 190),
    (list(range(1, 201)), 100, 200), ([3.0, 1.0, 2.0], 50, 2.0),
    ([5.0], 95, 5.0)])
def test_percentile_is_the_nearest_rank(values, q, want):
    stats = load(os.path.join(BENCH, "stats.py"))
    assert stats.percentile(values, q) == want


def test_percentile_and_median_refuse_nonsense():
    stats = load(os.path.join(BENCH, "stats.py"))
    assert stats.median([4, 1, 3, 2]) == 2.5 and stats.median([3, 1, 2]) == 2
    for bad in ([], [1.0]):
        with pytest.raises(ValueError):
            stats.percentile(bad, 0 if bad else 95)
    with pytest.raises(ValueError):
        stats.median([])


def test_trace_reduce_on_a_trace_made_by_hand():
    tr = load(os.path.join(BENCH, "trace_reduce.py"))
    device = {"/device:TPU:0": [(1.0, 2.0, "a"), (1.5, 2.5, "b"),
                                (4.0, 5.0, "a")]}
    host = [(0.5, 3.0, "exe.run"), (3.0, 5.5, "fetch")]
    got = tr.reduce(device, host, steps=2)
    # window 0.5..5.5; busy [1, 2.5] + [4, 5]; gaps [0.5, 1] and [2.5, 3]
    # under exe.run, [3, 4] and [5, 5.5] under fetch
    assert got["window_s"] == 5.0 and got["busy_s"] == 2.5
    assert got["idle_s"] == 2.5 and got["chips"] == 1 and got["steps"] == 2
    assert got["device_ops"] == [["a", 2.0], ["b", 1.0]]
    assert got["idle_gaps"] == [["fetch", 1.5], ["exe.run", 1.0]]

    # a second chip, busy [1, 3]: every number is the mean of the two
    device["/device:TPU:1"] = [(1.0, 3.0, "a")]
    two = tr.reduce(device, host, steps=2)
    assert two["chips"] == 2 and two["busy_s"] == (2.5 + 2.0) / 2
    assert two["device_ops"] == [["a", 2.0], ["b", 0.5]]
    assert dict(two["idle_gaps"]) == {"fetch": (1.5 + 2.5) / 2,
                                      "exe.run": (1.0 + 0.5) / 2}

    # what no span covers is named so; events are clipped to the window
    got = tr.reduce({"/device:TPU:0": [(-1.0, 0.5, "a")]},
                    [(0.0, 1.0, "exe.run"), (2.0, 3.0, "fetch")], steps=1)
    assert got["busy_s"] == 0.5 and got["window_s"] == 3.0
    assert dict(got["idle_gaps"]) == {"exe.run": 0.5, "fetch": 1.0,
                                      tr.NO_SPAN: 1.0}
    # no host span: the window is the device's own first to last
    got = tr.reduce({"/device:TPU:0": [(1.0, 2.0, "a"), (3.0, 4.0, "a")]},
                    [], steps=1)
    assert (got["window_s"], got["busy_s"]) == (3.0, 2.0)
    assert got["idle_gaps"] == [[tr.NO_SPAN, 1.0]]
    # no device plane with events: nothing to report, not a zero
    assert tr.reduce({}, host, steps=1) is None
    assert tr.reduce({"/device:TPU:0": []}, host, steps=1) is None
    assert tr.union([(3, 4), (1, 2), (1.5, 2.5), (5, 5)]) == [(1, 2.5),
                                                             (3, 4)]


def test_trace_reduce_reads_a_real_profile_and_finds_no_chip_in_it(tmp_path):
    """A profile of this CPU: `read` finds the benchmark's spans in it and
    no chip's plane, so `reduce` reports nothing."""
    import jax
    import jax.numpy as jnp
    tr = load(os.path.join(BENCH, "trace_reduce.py"))
    run = load(os.path.join(BENCH, "run.py"))
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path), profiler_options=run.trace_options())
    try:
        for _ in range(3):
            with jax.profiler.TraceAnnotation("exe.run"):
                y = f(x)
            with jax.profiler.TraceAnnotation("fetch"):
                y.block_until_ready()
    finally:
        jax.profiler.stop_trace()
    device, host = tr.read(str(tmp_path), {"exe.run", "fetch"})
    assert device == {}
    assert sorted(n for _, _, n in host) == ["exe.run"] * 3 + ["fetch"] * 3
    assert all(e >= s for s, e, _ in host)
    assert tr.reduce(device, host, steps=3) is None
    assert any(p["plane"].startswith("/host:") for p in tr.describe(
        str(tmp_path)))


def test_short_name_keeps_what_names_an_operation():
    tr = load(os.path.join(BENCH, "trace_reduce.py"))
    assert tr.short_name(
        "%fusion.38 = (f32[64]{0:T(128)S(1)}, f32[64]) fusion(bf16[2] %x), "
        "kind=kLoop, calls=%fused_computation.79") == "%fusion"
    assert tr.short_name(
        '%jvp__.21 = (bf16[1536,128,64]) custom-call(s32[1] %c), '
        'custom_call_target="tpu_custom_call", operand_layout_constraints={}'
    ) == "%jvp__ [tpu_custom_call]"
    assert tr.short_name("%divide_subtract_fusion.1 = f32[2] fusion()") \
        == "%divide_subtract_fusion"
    assert tr.short_name("%copy-done = f32[2] copy-done()") == "%copy-done"
    assert tr.short_name("plain") == "plain"
