"""`smallthinker_21b_a3b.b1_s16384`, off the chip: the cell's `--tiny`
rehearsal through `run.py`, its configuration file against the catalog's
row, its parameter count from the builder's own program, its yardstick
(`flops_per_sample` and the two roofline counters) against counts
written out here, and its readers on a run without a chip's plane. No
test here describes a TPU topology.
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
CONFIG = "smallthinker_21b_a3b"
CELL = CONFIG + ".b1_s16384"
# the one reader this cell brought, and the nine accepted files that
# read it as they stand
NEW_READERS = ["device_ms.router"]
ACCEPTED_READERS = [
    "flash_ms_per_step", "device_ms.moe", "device_ms.attn",
    "moe_roofline_pct", "moe_rows_per_step", "attn_roofline_pct",
    "attn_kv_blocks_per_step", "attn_grid_steps_per_step", "device_ms.rope"]


def load(path):
    spec = importlib.util.spec_from_file_location(
        "_stc_" + re.sub(r"\W", "_", os.path.relpath(path, REPO)), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def config():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


MODEL = load(os.path.join(BENCH, "configs", CONFIG + ".py"))
TRAFFIC = {"batch": 1, "seq_len": 16384, "pool": 4}
SOURCE = ("https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/"
          "blob/main/config.json")
REDUCED = ["num_hidden_layers", "moe_num_primary_experts", "vocab_size"]


# ---------------------------------------------------------------- the runs
@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_rehearsal_runs_the_cell_and_never_reports_correct(tmp_path,
                                                                trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla_cache"),
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    res = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2 ** 31 + 39), "--seconds", "1", "--trace",
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
    assert not set(NEW_READERS + ACCEPTED_READERS) & set(last["metrics"])
    checks = phase["checks"]
    assert checks["losses_finite"] and checks["no_compile_in_window"]
    assert checks["first_loss_near_ln_classes"]
    assert checks["loss_falls"] or last["attempted"] < 4
    # embedding, head, final norm; 9 a layer
    assert phase["setup"]["parameters"] == 3 + 4 * 9


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
    widths = {"hidden_size": 2560, "num_attention_heads": 28,
              "num_key_value_heads": 4, "head_dim": 128,
              "moe_ffn_hidden_size": 768,
              "moe_num_active_primary_experts": 6,
              "moe_primary_router_apply_softmax": True,
              "norm_topk_prob": True, "sliding_window_size": 4096,
              "rope_theta": 1500000, "rope_scaling": None,
              "rms_norm_eps": 1e-06, "max_position_embeddings": 16384,
              "tie_word_embeddings": False}
    assert {k: cfg[k] for k in widths} == widths
    assert cfg["router_width"] \
        == cfg["published"]["moe_num_primary_experts"] == 64
    assert cfg["published"] == {"num_hidden_layers": 52,
                                "moe_num_primary_experts": 64,
                                "vocab_size": 151936}
    assert (cfg["num_hidden_layers"], cfg["moe_num_primary_experts"],
            cfg["vocab_size"]) == (4, 16, 18992)
    # the guide's floors: an eighth of the vocabulary, 8 experts a layer,
    # a whole period of four layers
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["moe_num_primary_experts"] >= 8
    assert cfg["moe_num_primary_experts"] \
        * cfg["deployment"]["chips_that_share_a_layer"] \
        == cfg["router_width"]
    assert cfg["expert_start"] == 0 and cfg["classes"] == cfg["vocab_size"]
    # both lists whole, as published; the builder reads the first four:
    # one whole period, full without rotary, then window with it x 3
    assert cfg["rope_layout"] == cfg["sliding_window_layout"] \
        == [0, 1, 1, 1] * 13
    assert MODEL.layer_lists(cfg) == ([0, 1, 1, 1], [0, 1, 1, 1])
    assert {"router input", "router", "experts", "attention", "weights",
            "packing", "token ids"} <= set(cfg["assumed"])
    assert "BEFORE input_layernorm" in cfg["assumed"]["router input"]
    # the embedding's own scale, stated with its reason; every other
    # matrix at 0.02, on which the first loss rests
    assert cfg["embedding_init_std"] == 1.0
    assert "embedding_init_std = 1.0" in cfg["assumed"]["weights"] \
        and "UN-NORMED" in cfg["assumed"]["weights"]
    assert MODEL.model_cfg(cfg)["embed_init_std"] == 1.0 \
        and MODEL.model_cfg(cfg)["init_std"] == 0.02
    assert "559,290,880" in cfg["deployment"]["parameters"]
    assert "11,526,179,840" in cfg["deployment"]["moe_num_primary_experts"]
    assert cfg["flags"] == {"FLAGS_use_bf16_matmul": True}
    assert cfg["optimizer"]["name"] == "adam" \
        and cfg["optimizer"]["lr"] == 1e-4
    # the builder's own published sizes are the file's
    sys.path.insert(0, REPO)
    from paddle_tpu.models import smallthinker
    published, program = smallthinker.smallthinker_config(), \
        MODEL.model_cfg(cfg)
    cut = ("vocab_size", "rope_layout", "window_layout", "experts_held")
    assert {k: v for k, v in program.items() if k not in cut} \
        == {k: v for k, v in published.items() if k not in cut}
    for name in ("rope_layout", "window_layout"):
        assert program[name] == published[name][:4]
    assert program["experts_held"] == 16 and program["num_experts"] == 64


def test_config_file_agrees_with_the_catalog_s_row_where_it_is_at_hand():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guides here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SmallThinker-21BA3B-Instruct")
    cfg = config()
    assert row["source_url"] == SOURCE
    differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differs == set(REDUCED)
    assert cfg["published"] == {k: row["config"][k] for k in REDUCED}


def test_the_builder_s_program_counts_the_cell_s_parameters():
    """559,290,880 from the shapes of the program `build` makes of the
    file (not run), part by part as `deployment.parameters` says."""
    import numpy as np
    sys.path.insert(0, REPO)
    main, _, _ = MODEL.build(config(), TRAFFIC)
    sizes = {p.name: int(np.prod(p.shape))
             for p in main.global_block().all_parameters()}
    assert sum(sizes.values()) == 559290880
    assert sizes["embed_tokens"] + sizes["lm_head"] == 97239040
    per_layer = [sum(v for n, v in sizes.items()
                     if n.startswith(f"layers.{i}.")) for i in range(4)]
    assert per_layer == [115512320] * 4
    assert sizes["layers.3.moe.w_gate_up"] == 16 * 2560 * 1536
    assert 16 * sum(sizes.values()) == pytest.approx(8.95e9, rel=1e-3)
    assert 12 * sum(sizes.values()) == pytest.approx(6.71e9, rel=1e-3)


def test_first_loss_is_the_analytic_value_and_the_tolerance_comes_from_it():
    want = config()["correct"]
    v = 2560 * 0.02 ** 2
    first = math.log(18992) + v / 2
    assert abs(first - 10.364) < 1e-3 and "10.364" in want["first_loss_is"]
    over = first / math.log(18992) - 1
    assert over < want["first_loss_rel_tol"] < over + 0.015
    # 1% of ln(classes) either side of the value stays inside the
    # harness's check (sampling over 16,384 positions moves it by ~0.008)
    for sign in (1, -1):
        assert abs(first + sign * 0.01 * math.log(18992) - math.log(18992)) \
            <= want["first_loss_rel_tol"] * math.log(18992)
    assert want["first_loss_rel_tol"] == 0.062 and want["falling_n"] == 10


def test_flops_per_sample_is_the_closed_form():
    got = MODEL.flops_per_sample(config(), TRAFFIC)
    h = 2560
    attention = 2 * h * 28 * 128 + 2 * h * 4 * 128        # q, o; k, v
    assert attention == 20971520
    # router, and 6 x 16 / 64 = 1.5 routed experts a token
    sparse = h * 64 + 1.5 * 3 * h * 768
    weights = 4 * (attention + sparse) + h * 18992
    assert weights == 168550400
    assert weights == sum(MODEL.matmul_weights_per_token(config()).values())
    causal = 16384 * 16385 // 2
    under = 4096 * 4097 // 2 + (16384 - 4096) * 4096     # min(t + 1, 4096)
    assert MODEL.kept_keys(16384, 0) == causal == 134225920
    assert MODEL.kept_keys(16384, 4096) == under == 58722304
    assert MODEL.kept_keys(4096, 4096) == 4096 * 4097 // 2
    # a query head: 2 x 128 a key for the score, 2 x 128 for the values
    maps = (causal + 3 * under) * 4 * 28 * 128
    assert MODEL.attention_flop_per_sample(config(), TRAFFIC) == maps
    assert abs(maps / 4.45e12 - 1) < 1e-3
    assert got == pytest.approx(6 * weights * 16384 + 3 * maps, rel=1e-12)
    assert abs(got / 29.92e12 - 1) < 1e-3
    assert causal / under == pytest.approx(2.286, abs=1e-3)
    # at 8192 the window would keep three quarters: the mask does little
    assert MODEL.kept_keys(8192, 4096) / MODEL.kept_keys(8192, 0) \
        == pytest.approx(0.75, abs=1e-3)


def test_flops_per_sample_against_a_hand_count_at_the_tiny_size():
    cfg, traffic = MODEL.tiny(config(), TRAFFIC)
    s = traffic["seq_len"]
    assert (cfg["hidden_size"], s) == (32, 80)
    h, q, kv = 32, 14 * 8, 2 * 8
    # router 16 wide, 3 x 4 / 16 routed experts of 3 x 32 x 12 a token
    weights = 4 * (2 * h * q + 2 * h * kv + h * 16
                   + 3 * h * 12 * 3 * 4 / 16) + h * 96
    kept = s * (s + 1) // 2 + 3 * (24 * 25 // 2 + (s - 24) * 24)
    want = 6 * weights * s + 3 * kept * 14 * 4 * 8
    assert MODEL.flops_per_sample(cfg, traffic) == pytest.approx(want,
                                                                 rel=1e-12)


@pytest.mark.parametrize("counter,want", [
    # 1 full + 3 window (24) layers of 14 heads, 64 tokens x 2, d 8: kept
    # keys 2080 and 300 + 40 x 24, 4 d = 32 FLOP a head and key; bytes
    # 2 x tokens x ((2 q + 2 kv) + (3 q + 2 kv) + (q + 2 kv)) a layer,
    # q = 14 x 8, kv = 16
    ("attn_required", {"flop": 3 * 2 * (2080 + 3 * 1260) * 14 * 32,
                       "bytes": 128 * 2 * 4 * (6 * 112 + 6 * 16)}),
    # 4 layers, 128 tokens: a 16-wide router and 128 x 3 x 4 / 16 = 96
    # expected rows through an expert's 3 x 32 x 12; bytes 4 x (3 x (4
    # experts + router) + 8 x tokens x 32) a layer: the router's input is
    # a tensor of its own
    ("moe_required", {"flop": 4 * 6 * (128 * 512 + 96 * 1152),
                      "bytes": 4 * 4 * (3 * (4 * 1152 + 512)
                                        + 8 * 128 * 32)})])
def test_roofline_counters_against_hand_counts(counter, want):
    toy, _ = MODEL.tiny(config(), TRAFFIC)
    got = getattr(MODEL, counter)(toy, {"batch": 2, "seq_len": 64})
    assert got["flop"] == pytest.approx(want["flop"], rel=1e-12)
    assert got["bytes"] == want["bytes"]


def test_the_cell_s_required_work_bounds_what_the_readers_divide_by():
    """At the cell's sizes BOTH shares are bound by FLOP: the attention
    maps 13.35e12 (67.8 ms at the bf16 peak), the expert layers 3.54e12
    (18.0 ms) against 9.9 GB (12.1 ms at the HBM peak): the first expert
    layer in the benchmark that its weights' bytes do not bound, at
    1,536 rows an expert; 196,608 rows a step and pass: four layers of
    `row_bound`'s 49,152, twice the expected rows."""
    attn = MODEL.attn_required(config(), TRAFFIC)
    moe = MODEL.moe_required(config(), TRAFFIC)
    assert attn["flop"] / 197e12 > attn["bytes"] / 819e9
    assert moe["flop"] / 197e12 > moe["bytes"] / 819e9
    assert attn["flop"] / 197e12 == pytest.approx(67.8e-3, rel=2e-3)
    assert moe["flop"] / 197e12 == pytest.approx(18.0e-3, rel=2e-3)
    assert moe["flop"] == pytest.approx(
        4 * 6 * (16384 * 163840 + 24576 * 5898240), rel=1e-12)
    assert moe["bytes"] == 16 * (3 * (94371840 + 163840) + 8 * 16384 * 2560)
    assert attn["flop"] == pytest.approx(
        0.446 * MODEL.flops_per_sample(config(), TRAFFIC), rel=1e-2)
    sys.path.insert(0, REPO)
    from paddle_tpu.ops import decoder_ops
    assert 4 * decoder_ops.row_bound(16384, 6, 16, 64) == 196608
    assert 16384 * 6 * 16 / 64 / 16 == 1536


# ----------------------------------------------------------- the readers
@pytest.mark.parametrize("name", NEW_READERS + ACCEPTED_READERS)
def test_the_cell_s_readers_find_nothing_without_a_chips_plane(name):
    """The one this PR adds and the nine accepted files that read the
    cell as they stand: on a run with no trace each returns None and
    does not raise, as on a program that lacks the span or the gauge."""
    run = types.SimpleNamespace(trace=None, spans={}, counters={}, chips=1,
                                device_kind="cpu")
    assert load(os.path.join(BENCH, "layer_metrics",
                             name + ".py")).compute(run) is None


def test_router_reader_takes_the_union_under_the_router_s_scopes_alone(
        monkeypatch):
    reader = load(os.path.join(BENCH, "layer_metrics",
                               "device_ms.router.py"))
    helper = reader.helper()
    assert reader.OPS == ("moe_router",)
    found = {"steps": 2, "planes": [[
        (0.000, 0.004, "fwd/moe_router", "fusion.1"),
        (0.002, 0.006, "fwd/moe_router", "fusion.2"),          # overlaps
        (0.010, 0.012, "bwd/moe_router_grad", "fusion.3"),
        (0.020, 0.050, "fwd/moe_expert_ffn", "fusion.4"),      # not its
        (0.060, 0.070, None, "ragged-dot-none.5")]]}
    assert helper.union_ms_per_step(found, reader.OPS) == pytest.approx(4.0)
    monkeypatch.setattr(helper, "_state", dict(helper._state, last=found))
    assert reader.compute(None) == pytest.approx(4.0)
    # `device_ms.moe` folds it in with the experts and XLA's own kernels
    moe = load(os.path.join(BENCH, "layer_metrics", "device_ms.moe.py"))
    assert moe.compute(None) == pytest.approx(4.0 + 15.0 + 5.0)
    monkeypatch.setattr(helper, "_state", dict(helper._state, last=None))
    assert reader.compute(None) is None


def check_manifest(m):
    """The reader this cell brought and the nine accepted files that read
    it as they stand are per-layer entries that list the cell (a later PR
    may append cells to their lists); the cell and the configuration are
    found by NAME, wherever later entries put them."""
    by_name = {p["name"]: p for p in m["per_layer"]}
    for name in NEW_READERS + ACCEPTED_READERS:
        entry = by_name[name]
        assert CELL in entry["workloads"], name
        assert (entry["layer"], entry["moves"]) == ("Op kernels",
                                                    "samples_per_s")
        assert entry["better"] == ("higher" if name.endswith("_pct")
                                   else "lower")
    router = by_name["device_ms.router"]
    assert (router["unit"], router["source"]) == ("ms", "device_trace")
    cell = {w["name"]: w for w in m["workloads"]}[CELL]
    assert cell["chips"] == 1 and cell["config"] == CONFIG
    assert cell["traffic"] == "b1_s16384"
    entry = {c["name"]: c for c in m["configs"]}[CONFIG]
    assert entry["source"] == SOURCE and entry["reduced"] == REDUCED
    assert entry["file"] == "benchmark/configs/" + CONFIG + ".json"
    assert all(len(e["why"]) <= 200 for e in (cell, entry))
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        body = json.load(f)
    assert body["traffic"] == TRAFFIC and body["mesh"] is None


def test_the_manifest_lists_the_cell_s_readers_with_the_cell():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        m = json.load(f)
    check_manifest(m)
    # every per-layer metric WITHOUT a list is one the cell reports too
    # (it reports `samples_per_s`, which each of them moves, or `setup_s`)
    assert len(m["configs"]) >= 6 and len(m["workloads"]) >= 8
    assert sum(w["chips"] == 4 for w in m["workloads"]) \
        <= max(1, len(m["workloads"]) // 4)


def test_the_real_manifest_passes_every_manifest_check_of_this_directory():
    """`check_manifest*` of `test_benchmark.py` and of each sibling cell's
    file, on the manifest as this PR leaves it: appending this cell to
    nine lists, one configuration, one cell and one reader broke none."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        m = json.load(f)
    bench = load(os.path.join(REPO, "tests", "benchmark",
                              "test_benchmark.py"))
    bench.check_manifest_keys_names_and_units(m)
    bench.check_manifest_names_files_that_exist(m)
    for sibling in ("test_trace_scopes", "test_step_records",
                    "test_qwen3_next_cell", "test_phi4_flash_cell",
                    "test_laguna_xs2_cell"):
        load(os.path.join(REPO, "tests", "benchmark",
                          sibling + ".py")).check_manifest(m)
