"""`phi4_mini_flash.b1_s4096`, off the chip: the cell's `--tiny` rehearsal
through `run.py`, its configuration file against the catalog's row, its
yardstick (`flops_per_sample` and the two roofline counters) against
counts written out here, and its readers on a run without a chip's
plane. No test here describes a TPU topology.
"""
import importlib.util
import json
import math
import os
import re
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
CELL = "phi4_mini_flash.b1_s4096"
READERS = ["device_ms.ssm", "ssm_roofline_pct", "attn_roofline_pct",
           "attn_kv_blocks_per_step"]


def load(path):
    spec = importlib.util.spec_from_file_location(
        "_p4c_" + re.sub(r"\W", "_", os.path.relpath(path, REPO)), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def config():
    with open(os.path.join(BENCH, "configs", "phi4_mini_flash.json")) as f:
        return json.load(f)


MODEL = load(os.path.join(BENCH, "configs", "phi4_mini_flash.py"))
TRAFFIC = {"batch": 1, "seq_len": 4096, "pool": 4}


# ---------------------------------------------------------------- the runs
@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_rehearsal_runs_the_cell_and_never_reports_correct(tmp_path,
                                                                trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla_cache"),
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    res = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2 ** 31 + 32), "--seconds", "1", "--trace",
         str(trace), "--tiny"],
        capture_output=True, text=True, env=env, timeout=600, cwd=REPO)
    assert res.returncode != 0 and "rehearsal" in res.stderr, \
        res.stderr[-2000:]
    rows = [json.loads(line) for line in res.stdout.splitlines()]
    phase = {r["phase"]: r for r in rows if "phase" in r}
    last = rows[-1]
    assert last["correct"] is False and last["failed"] == 0
    assert last["attempted"] >= 1
    assert all(v["value"] is None for v in last["metrics"].values())
    # untraced: the end-to-end names and no other; traced: what the host
    # reads, and none of the cell's readers that need a chip's plane
    # (`test_benchmark.py::rehearsal_names`, a function of the manifest)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        must, may = load(os.path.join(
            REPO, "tests", "benchmark", "test_benchmark.py")
        ).rehearsal_names(json.load(f), CELL, trace)
    assert must <= set(last["metrics"]) <= may, sorted(last["metrics"])
    assert not set(READERS) & set(last["metrics"])
    checks = phase["checks"]
    assert checks["losses_finite"] and checks["no_compile_in_window"]
    assert checks["first_loss_near_ln_classes"]
    assert checks["loss_falls"] or last["attempted"] < 4
    assert phase["setup"]["parameters"] == 86


def test_same_seed_same_documents_and_labels_are_the_next_ids():
    cfg = config()
    a, b, c = (MODEL.make_batches(cfg, dict(TRAFFIC, seq_len=33), seed, 2)
               for seed in (2 ** 31 + 5, 2 ** 31 + 5, 7))
    assert all((a[i][k] == b[i][k]).all() for i in range(2) for k in a[i])
    assert not (a[0]["ids"] == c[0]["ids"]).all()
    assert a[0]["ids"].shape == (1, 33) and a[0]["labels"].shape == (1, 33, 1)
    assert (a[0]["labels"][0, :-1, 0] == a[0]["ids"][0, 1:]).all()
    assert 0 <= a[0]["ids"].min() and a[0]["ids"].max() < cfg["vocab_size"]


# ------------------------------------------------------------ the yardstick
# the language model's settings as the catalog's row gives them
PUBLISHED = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064}
SOURCE = ("https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/"
          "blob/main/config.json")


def test_config_file_holds_the_published_widths_and_states_the_cut():
    cfg = config()
    assert cfg["source"] == SOURCE
    differs = {k for k, v in PUBLISHED.items() if cfg.get(k) != v}
    assert differs == set(cfg["reduced"]) == {"num_hidden_layers",
                                              "vocab_size"}
    assert cfg["published"] == {k: PUBLISHED[k] for k in cfg["reduced"]}
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]  # the floor
    assert cfg["num_hidden_layers"] == len(cfg["layer_kinds"]) == 6 >= 4
    assert cfg["deployment"]["chips_that_share_a_layer"] == 8
    assert cfg["classes"] == cfg["vocab_size"]
    # every kind of the release's 32 layers, in its order
    sys.path.insert(0, REPO)
    from paddle_tpu.models import phi4_flash
    full = phi4_flash.layer_kinds(PUBLISHED["num_hidden_layers"])
    assert [full[i] for i in cfg["published_index"]] == cfg["layer_kinds"]
    assert set(cfg["layer_kinds"]) == set(full)
    program = MODEL.model_cfg(cfg)
    assert (program["head_dim"], program["d_inner"], program["dt_rank"]) \
        == (64, 5120, 160)
    published = phi4_flash.phi4_flash_config()
    assert {k: program[k] for k in program
            if k not in ("vocab_size", "layer_kinds", "published_index")} \
        == {k: published[k] for k in program
            if k not in ("vocab_size", "layer_kinds", "published_index")}


def test_config_file_agrees_with_the_catalog_s_row_where_it_is_at_hand():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guides here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Phi-4-mini-flash-reasoning")
    assert row["source_url"] == SOURCE and row["config"] == PUBLISHED


def test_first_loss_is_the_analytic_value_and_the_tolerance_comes_from_it():
    want = config()["correct"]
    v = 2560 * 0.02 ** 2
    first = math.log(25008) + v / 2
    assert abs(first - 10.639) < 1e-3 and "10.639" in want["first_loss_is"]
    over = first / math.log(25008) - 1
    assert over < want["first_loss_rel_tol"] < over + 0.015
    # 1% either side of the value stays inside the harness's check
    assert abs(first * 1.01 - math.log(25008)) \
        <= want["first_loss_rel_tol"] * math.log(25008)


def test_flops_per_sample_is_the_closed_form():
    got = MODEL.flops_per_sample(config(), TRAFFIC)
    mlp = 3 * 2560 * 10240
    mamba = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    attention = 2560 * 5120 + 2560 * 2560
    weights = 2 * mamba + 2 * attention + 2 * 2560 * 5120 + 2 * 2560 * 2560 \
        + 6 * mlp + 2560 * 25008
    assert weights == 696770560
    causal = 4096 * 4097 // 2
    window = 512 * 513 // 2 + (4096 - 512) * 512     # min(t + 1, 512)
    assert MODEL.kept_keys(4096, 512) == window == 1966336
    assert MODEL.kept_keys(4096, 0) == causal
    # a query head: 2 x 64 a key for the score, 2 x 128 for the values
    maps = 3 * (2 * causal + window) * 40 * (2 * 64 + 2 * 128)
    scans = 3 * 2 * 4096 * 5120 * 16 * 7
    assert got == pytest.approx(6 * weights * 4096 + maps + scans, rel=1e-12)
    assert abs(got / 18.00e12 - 1) < 1e-3
    # the window layer is counted at its 512 keys a query, not the causal
    # half a kernel without the skip would visit: 4.3 times less
    assert causal / window == pytest.approx(4.267, abs=1e-3)


def test_flops_per_sample_against_a_hand_count_at_the_tiny_size():
    cfg, traffic = MODEL.tiny(config(), TRAFFIC)
    s = traffic["seq_len"]
    assert (cfg["hidden_size"], s) == (32, 80)
    h, inner, q, kv = 32, 64, 8 * 4, 4 * 4
    weights = (2 * (h * 2 * inner + inner * (2 + 2 * 4) + 2 * inner
                    + inner * h)
               + 2 * (h * (q + 2 * kv) + q * h) + 2 * h * inner + 2 * h * q
               + 6 * 3 * h * 48 + h * 96)
    kept = 2 * (s * (s + 1) // 2) + 24 * 25 // 2 + (s - 24) * 24
    want = 6 * weights * s + 3 * kept * 8 * 6 * 4 + 3 * 2 * s * inner * 4 * 7
    assert MODEL.flops_per_sample(cfg, traffic) == pytest.approx(want,
                                                                 rel=1e-12)


@pytest.mark.parametrize("counter,want", [
    # 2 scans, 2 x 64 tokens, 64 channels x 4 states: 7 FLOP a (position,
    # channel, state) forward, x 3; bytes 4 x (tokens x (3 x (2 x 64 + 2 x
    # 4) read + 2 x 64 written) + 3 x 64 x 4 of A_log) a layer
    ("ssm_required", {"flop": 3 * 2 * 2 * 64 * 64 * 4 * 7,
                      "bytes": 2 * 4 * (128 * (3 * 136 + 128) + 3 * 256)}),
    # window 24 + full + cross at 64 tokens, 8 heads of 4 (values 8): kept
    # keys 300 + 40 x 24 and 2 x 2080, 6 d = 24 FLOP a head and key; bytes
    # 2 x tokens x ((q + 2 kv + o) + (q + 2 kv + 2 o) + (q + 2 kv)) a layer
    ("attn_required", {"flop": 3 * 2 * (300 + 960 + 2 * 2080) * 8 * 24,
                       "bytes": 3 * 128 * 2 * (3 * (32 + 32) + 3 * 64)})])
def test_roofline_counters_against_hand_counts(counter, want):
    toy, _ = MODEL.tiny(config(), TRAFFIC)
    got = getattr(MODEL, counter)(toy, {"batch": 2, "seq_len": 64})
    assert got["flop"] == pytest.approx(want["flop"], rel=1e-12)
    assert got["bytes"] == want["bytes"]


def test_the_cell_s_required_work_bounds_what_the_readers_divide_by():
    """At the cell's sizes: the scans are bound by bytes (1.35 GB, 1.6 ms
    at the HBM peak), the attention maps by FLOP (0.864e12, 4.4 ms at the
    bf16 peak); both far under the step's FLOP."""
    ssm = MODEL.ssm_required(config(), TRAFFIC)
    attn = MODEL.attn_required(config(), TRAFFIC)
    assert ssm["bytes"] / 819e9 > ssm["flop"] / 197e12
    assert attn["flop"] / 197e12 > attn["bytes"] / 819e9
    assert abs(ssm["bytes"] / 1.347e9 - 1) < 1e-3
    assert abs(attn["flop"] / 0.8639e12 - 1) < 1e-3
    assert ssm["flop"] + attn["flop"] < 0.05 * MODEL.flops_per_sample(
        config(), TRAFFIC)


# ----------------------------------------------------------- the readers
@pytest.mark.parametrize("name", READERS)
def test_new_readers_find_nothing_without_a_chips_plane(name):
    run = types.SimpleNamespace(trace=None, spans={}, counters={}, chips=1,
                                device_kind="cpu")
    assert load(os.path.join(BENCH, "layer_metrics",
                             name + ".py")).compute(run) is None


def check_manifest(m):
    """The four readers are per-layer entries that list the cell (since
    PR 38; a later PR may append cells to their lists); the cell and the
    configuration by name."""
    by_name = {p["name"]: p for p in m["per_layer"]}
    for name in READERS + ["device_ms.attn", "attn_grid_steps_per_step",
                           "flash_ms_per_step"]:
        entry = by_name[name]
        assert CELL in entry["workloads"], name
        assert (entry["layer"], entry["moves"]) == ("Op kernels",
                                                    "samples_per_s")
        assert entry["better"] == ("higher" if name.endswith("_pct")
                                   else "lower")
    cell = {w["name"]: w for w in m["workloads"]}[CELL]
    assert cell["chips"] == 1 and cell["config"] == "phi4_mini_flash"
    entry = {c["name"]: c for c in m["configs"]}["phi4_mini_flash"]
    assert entry["source"] == SOURCE
    assert entry["reduced"] == config()["reduced"]
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        assert json.load(f)["traffic"] == TRAFFIC


def test_the_manifest_lists_the_four_readers_with_the_cell():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        check_manifest(json.load(f))


def test_block_counter_is_read_from_the_programs_registry(monkeypatch):
    from paddle_tpu.fluid import telemetry
    monkeypatch.setattr(telemetry, "REGISTRY", telemetry.MetricsRegistry())
    reader = load(os.path.join(BENCH, "layer_metrics",
                               "attn_kv_blocks_per_step.py"))
    traced = types.SimpleNamespace(trace={"busy_s": 1.0})
    assert reader.compute(traced) is None       # a program without it
    gauge = telemetry.REGISTRY.gauge("attn_kv_blocks_per_step", "",
                                     labelnames=("site",))
    for site, pairs in (("w", 40 * 150), ("full", 40 * 528),
                        ("cross", 40 * 528), ("w", 40 * 150)):  # traced again
        gauge.labels(site=site).set(pairs)
    assert reader.compute(traced) == 40 * (150 + 2 * 528)
    assert reader.compute(types.SimpleNamespace(trace=None)) is None


@pytest.mark.parametrize("name", ["ssm_roofline_pct", "attn_roofline_pct"])
def test_roofline_readers_say_nothing_of_a_configuration_without_the_count(
        name, monkeypatch):
    """On a cell whose configuration counts no scan or no attention map
    (the Qwen cell, read with the same listing) the share is left out."""
    reader = load(os.path.join(BENCH, "layer_metrics", name + ".py"))
    helper = reader.helper()
    monkeypatch.setattr(helper, "ms_per_step", lambda *a, **k: 12.0)
    monkeypatch.setattr(helper.shared(), "last", lambda: {
        "chips": 1, "steps": 10, "kernels": {"flash_fwd": {"s": 0.1}}})
    monkeypatch.setattr(helper, "cell_files",
                        lambda: ({}, {}, types.SimpleNamespace()))
    run = types.SimpleNamespace(trace={"busy_s": 1.0}, chips=1,
                                device_kind="TPU v5 lite",
                                peak=lambda kind, what: 1.0)
    assert reader.compute(run) is None
    required = {"flop": 0.005, "bytes": 0.002}
    monkeypatch.setattr(helper, "cell_files", lambda: (
        {}, {}, types.SimpleNamespace(ssm_required=lambda c, t: required,
                                      attn_required=lambda c, t: required)))
    ms = 12.0 if name == "ssm_roofline_pct" else 10.0
    assert reader.compute(run) == pytest.approx(100 * 0.005 / (ms * 1e-3))
