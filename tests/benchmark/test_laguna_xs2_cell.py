"""`laguna_xs2.b1_s8192`, off the chip: the cell's `--tiny` rehearsal
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
CELL = "laguna_xs2.b1_s8192"
READERS = ["device_ms.rope", "attn_grid_steps_per_step"]


def load(path):
    spec = importlib.util.spec_from_file_location(
        "_lxc_" + re.sub(r"\W", "_", os.path.relpath(path, REPO)), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def config():
    with open(os.path.join(BENCH, "configs", "laguna_xs2.json")) as f:
        return json.load(f)


MODEL = load(os.path.join(BENCH, "configs", "laguna_xs2.py"))
TRAFFIC = {"batch": 1, "seq_len": 8192, "pool": 4}
SOURCE = "https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json"
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]


# ---------------------------------------------------------------- the runs
@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_rehearsal_runs_the_cell_and_never_reports_correct(tmp_path,
                                                                trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla_cache"),
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    res = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2 ** 31 + 34), "--seconds", "1", "--trace",
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
    # embedding, head, final norm; 9 in the dense layer, 12 a sparse one
    assert phase["setup"]["parameters"] == 3 + 9 + 4 * 12


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
def test_config_file_holds_the_published_widths_and_states_the_cut():
    cfg = config()
    assert cfg["source"] == SOURCE and cfg["reduced"] == REDUCED
    widths = {"hidden_size": 2048, "head_dim": 128, "num_key_value_heads": 8,
              "num_attention_heads": 48, "sliding_window": 512,
              "intermediate_size": 8192, "moe_intermediate_size": 512,
              "shared_expert_intermediate_size": 512,
              "num_experts_per_tok": 8, "moe_routed_scaling_factor": 2.5,
              "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-06}
    assert {k: cfg[k] for k in widths} == widths
    assert cfg["router_width"] == cfg["published"]["num_experts"] == 256
    assert cfg["published"] == {"num_hidden_layers": 40, "num_experts": 256,
                                "vocab_size": 100352}
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (5, 32, 12544)
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]  # floor
    assert cfg["num_experts"] * 8 == cfg["router_width"]
    assert cfg["deployment"]["chips_that_share_a_layer"] == 8
    assert cfg["expert_start"] == 0 and cfg["classes"] == cfg["vocab_size"]
    full, window = (cfg["rope_parameters"][k]
                    for k in ("full_attention", "sliding_attention"))
    assert (full["rope_type"], full["rope_theta"], full["factor"],
            full["original_max_position_embeddings"], full["beta_fast"],
            full["beta_slow"], full["attention_factor"],
            full["partial_rotary_factor"]) == (
        "yarn", 500000, 64, 4096, 64, 1, 1.4158883083359672, 0.5)
    assert (window["rope_type"], window["rope_theta"],
            window["partial_rotary_factor"]) == ("default", 10000, 1)
    # the leading dense layer and one whole period after it
    kinds, heads, mlps = MODEL.layer_lists(cfg)
    assert kinds == ["full", "sliding", "sliding", "sliding", "full"]
    assert heads == [48, 64, 64, 64, 48]
    assert mlps == ["dense", "sparse", "sparse", "sparse", "sparse"]
    assert len(cfg["layer_types"]) == 40  # the lists stay whole, as published
    assert {"gate", "router", "yarn", "weights", "token ids"} \
        <= set(cfg["assumed"])
    assert "691,623,936" in cfg["deployment"]["parameters"]
    # the builder's own published sizes are the file's
    sys.path.insert(0, REPO)
    from paddle_tpu.models import laguna
    published, program = laguna.laguna_config(), MODEL.model_cfg(cfg)
    cut = ("vocab_size", "layer_types", "heads_per_layer", "mlp_types",
           "experts_held")
    assert {k: v for k, v in program.items() if k not in cut} \
        == {k: v for k, v in published.items() if k not in cut}
    for name in ("layer_types", "heads_per_layer", "mlp_types"):
        assert program[name] == published[name][:5]
    assert program["rope"]["full"]["rotary_dim"] == 64
    assert program["rope"]["sliding"] == {"theta": 10000.0, "rotary_dim": 128}


def test_config_file_agrees_with_the_catalog_s_row_where_it_is_at_hand():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guides here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Laguna-XS.2")
    cfg = config()
    assert row["source_url"] == SOURCE
    differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differs == set(REDUCED)
    assert cfg["published"] == {k: row["config"][k] for k in REDUCED}


def test_first_loss_is_the_analytic_value_and_the_tolerance_comes_from_it():
    want = config()["correct"]
    v = 2048 * 0.02 ** 2
    first = math.log(12544) + v / 2
    assert abs(first - 9.847) < 1e-3 and "9.847" in want["first_loss_is"]
    over = first / math.log(12544) - 1
    assert over < want["first_loss_rel_tol"] < over + 0.015
    # 1% either side of the value stays inside the harness's check
    assert abs(first * 1.01 - math.log(12544)) \
        <= want["first_loss_rel_tol"] * math.log(12544)
    assert want["falling_n"] == 10


def test_flops_per_sample_is_the_closed_form():
    got = MODEL.flops_per_sample(config(), TRAFFIC)
    h = 2048
    full = 2 * h * 48 * 128 + 2 * h * 1024 + h * 48      # q, o; k, v; gate
    window = 2 * h * 64 * 128 + 2 * h * 1024 + h * 64
    assert (full, window) == (29458432, 37879808)
    # router, shared expert, 8 x 32 / 256 = 1 routed expert a token
    sparse = h * 256 + 3 * h * 512 + 3 * h * 512
    weights = 2 * full + 3 * window + 3 * h * 8192 + 4 * sparse + h * 12544
    assert weights == 275841024
    assert weights == sum(MODEL.matmul_weights_per_token(config()).values())
    causal = 8192 * 8193 // 2
    under = 512 * 513 // 2 + (8192 - 512) * 512          # min(t + 1, 512)
    assert MODEL.kept_keys(8192, 0) == causal == 33558528
    assert MODEL.kept_keys(8192, 512) == under == 4063488
    # a query head: 2 x 128 a key for the score, 2 x 128 for the values
    maps = causal * 4 * 48 * 128 * 2 + under * 4 * 64 * 128 * 3
    assert got == pytest.approx(6 * weights * 8192 + 3 * maps, rel=1e-12)
    assert abs(got / 19.70e12 - 1) < 1e-3
    assert causal / under == pytest.approx(8.259, abs=1e-3)


def test_flops_per_sample_against_a_hand_count_at_the_tiny_size():
    cfg, traffic = MODEL.tiny(config(), TRAFFIC)
    s = traffic["seq_len"]
    assert (cfg["hidden_size"], s) == (32, 80)
    h, kv = 32, 2 * 8
    attention = sum(2 * h * n * 8 + 2 * h * kv + h * n
                    for n in (4, 8, 8, 8, 4))
    # router 16 wide, shared 12 wide, 3 x 4 / 16 routed experts a token
    sparse = h * 16 + 3 * h * 12 + 3 * h * 12 * 3 * 4 / 16
    weights = attention + 3 * h * 48 + 4 * sparse + h * 96
    kept = 2 * (s * (s + 1) // 2) * 4 + 3 * (24 * 25 // 2 + (s - 24) * 24) * 8
    want = 6 * weights * s + 3 * kept * 4 * 8
    assert MODEL.flops_per_sample(cfg, traffic) == pytest.approx(want,
                                                                 rel=1e-12)


@pytest.mark.parametrize("counter,want", [
    # 2 full layers of 4 heads + 3 window (24) layers of 8, 64 tokens x 2,
    # d 8: kept keys 2080 and 300 + 40 x 24, 4 d = 32 FLOP a head and key;
    # bytes 2 x tokens x ((2 q + 2 kv) + (3 q + 2 kv) + (q + 2 kv)) a layer,
    # q = heads x 8, kv = 16
    ("attn_required", {"flop": 3 * 2 * (2 * 2080 * 4 + 3 * 1260 * 8) * 32,
                       "bytes": 128 * 2 * (2 * (6 * 32 + 6 * 16)
                                           + 3 * (6 * 64 + 6 * 16))}),
    # 4 sparse layers, 128 tokens: a 16-wide router and 128 x 3 x 4 / 16 =
    # 96 expected rows through an expert's 3 x 32 x 12; bytes 4 x (3 x (4
    # experts + router) + 5 x tokens x 32) a layer
    ("moe_required", {"flop": 4 * 6 * (128 * 512 + 96 * 1152),
                      "bytes": 4 * 4 * (3 * (4 * 1152 + 512)
                                        + 5 * 128 * 32)})])
def test_roofline_counters_against_hand_counts(counter, want):
    toy, _ = MODEL.tiny(config(), TRAFFIC)
    got = getattr(MODEL, counter)(toy, {"batch": 2, "seq_len": 64})
    assert got["flop"] == pytest.approx(want["flop"], rel=1e-12)
    assert got["bytes"] == want["bytes"]


def test_the_cell_s_required_work_bounds_what_the_readers_divide_by():
    """At the cell's sizes: the attention maps are bound by FLOP (6.15e12,
    31.2 ms at the bf16 peak), the expert layers by bytes (6.2 GB, 7.6 ms
    at the HBM peak: the weights of 32 experts a layer read twice and
    their gradient written); 65,536 rows a step at the bound."""
    attn = MODEL.attn_required(config(), TRAFFIC)
    moe = MODEL.moe_required(config(), TRAFFIC)
    assert attn["flop"] / 197e12 > attn["bytes"] / 819e9
    assert moe["bytes"] / 819e9 > moe["flop"] / 197e12
    assert abs(attn["flop"] / 6.1468e12 - 1) < 1e-3
    assert abs(moe["bytes"] / 6.199e9 - 1) < 1e-3
    assert attn["flop"] == pytest.approx(
        0.312 * MODEL.flops_per_sample(config(), TRAFFIC), rel=1e-2)
    sys.path.insert(0, REPO)
    from paddle_tpu.ops import decoder_ops
    assert 4 * decoder_ops.row_bound(8192, 8, 32, 256) == 65536


# ----------------------------------------------------------- the readers
@pytest.mark.parametrize("name", READERS + [
    "device_ms.attn", "device_ms.moe", "attn_roofline_pct",
    "moe_roofline_pct", "moe_rows_per_step", "attn_kv_blocks_per_step"])
def test_the_cell_s_readers_find_nothing_without_a_chips_plane(name):
    """The two this PR adds and the six accepted files that read the
    cell as they stand: on a run with no trace each returns None and
    does not raise, as on a program that lacks the span or the gauge."""
    run = types.SimpleNamespace(trace=None, spans={}, counters={}, chips=1,
                                device_kind="cpu")
    assert load(os.path.join(BENCH, "layer_metrics",
                             name + ".py")).compute(run) is None


def check_manifest(m):
    """The two readers this cell brought and the six accepted files that
    read it as they stand are per-layer entries that list the cell (since
    PR 38; a later PR may append cells to their lists); the cell and the
    configuration are found by NAME, wherever later entries put them."""
    by_name = {p["name"]: p for p in m["per_layer"]}
    for name in READERS + ["device_ms.attn", "device_ms.moe",
                           "attn_roofline_pct", "moe_roofline_pct",
                           "moe_rows_per_step", "attn_kv_blocks_per_step",
                           "flash_ms_per_step"]:
        entry = by_name[name]
        assert CELL in entry["workloads"], name
        assert (entry["layer"], entry["moves"]) == ("Op kernels",
                                                    "samples_per_s")
        assert entry["better"] == ("higher" if name.endswith("_pct")
                                   else "lower")
    cell = {w["name"]: w for w in m["workloads"]}[CELL]
    assert cell["chips"] == 1 and cell["config"] == "laguna_xs2"
    entry = {c["name"]: c for c in m["configs"]}["laguna_xs2"]
    assert entry["source"] == SOURCE and entry["reduced"] == REDUCED
    assert entry["file"] == "benchmark/configs/laguna_xs2.json"
    assert all(len(e["why"]) <= 200 for e in (cell, entry))
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        assert json.load(f)["traffic"] == TRAFFIC


def test_the_manifest_lists_the_cell_s_readers_with_the_cell():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        check_manifest(json.load(f))


def test_grid_step_counter_is_read_from_the_programs_registry(monkeypatch):
    from paddle_tpu.fluid import telemetry
    monkeypatch.setattr(telemetry, "REGISTRY", telemetry.MetricsRegistry())
    reader = load(os.path.join(BENCH, "layer_metrics",
                               "attn_grid_steps_per_step.py"))
    traced = types.SimpleNamespace(trace={"busy_s": 1.0})
    assert reader.compute(traced) is None       # a program without it
    gauge = telemetry.REGISTRY.gauge("attn_grid_steps_per_step", "",
                                     labelnames=("site",))
    # the cell's five layers: 48 heads x 8 x 8 blocks of 1024 causal, 64
    # heads x 16 row blocks x 2 of 512 under the window; one traced again
    for site, steps in (("l0", 3072), ("l1", 2048), ("l2", 2048),
                        ("l3", 2048), ("l4", 3072), ("l1", 2048)):
        gauge.labels(site=site).set(steps)
    assert reader.compute(traced) == 12288
    assert reader.compute(types.SimpleNamespace(trace=None)) is None


def test_rope_reader_takes_the_union_under_the_rotary_scopes(monkeypatch):
    reader = load(os.path.join(BENCH, "layer_metrics", "device_ms.rope.py"))
    helper = reader.helper()
    assert reader.OPS == ("rotary_embedding",)
    found = {"steps": 2, "planes": [[
        (0.000, 0.004, "fwd/rotary_embedding", "fusion.1"),
        (0.002, 0.006, "fwd/rotary_embedding", "fusion.2"),   # overlaps
        (0.010, 0.012, "bwd/rotary_embedding_grad", "fusion.3"),
        (0.020, 0.050, "fwd/mul", "fusion.4")]]}
    assert helper.union_ms_per_step(found, reader.OPS) == pytest.approx(4.0)
    monkeypatch.setattr(helper, "_state", dict(helper._state, last=found))
    assert reader.compute(None) == pytest.approx(4.0)
    monkeypatch.setattr(helper, "_state", dict(helper._state, last=None))
    assert reader.compute(None) is None
