"""`ling3_flash_vl.b1_s4096`, off the chip: the cell's `--tiny` rehearsal
through `run.py`, its configuration file against the catalog's row, its
parameter count from the builder's own program, its yardstick
(`flops_per_sample` and the four roofline counters) against counts
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
CONFIG = "ling3_flash_vl"
CELL = CONFIG + ".b1_s4096"
# the two readers this cell brought, and the eleven accepted files that
# read it as they stand
NEW_READERS = ["device_ms.kda", "kda_roofline_pct"]
ACCEPTED_READERS = [
    "device_ms.moe", "moe_roofline_pct", "moe_rows_per_step",
    "device_ms.router", "device_ms.attn", "flash_ms_per_step",
    "attn_roofline_pct", "attn_kv_blocks_per_step",
    "attn_grid_steps_per_step", "device_ms.mla", "mla_roofline_pct"]


def load(path):
    spec = importlib.util.spec_from_file_location(
        "_lfc_" + re.sub(r"\W", "_", os.path.relpath(path, REPO)), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def config():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


MODEL = load(os.path.join(BENCH, "configs", CONFIG + ".py"))
TRAFFIC = {"batch": 1, "seq_len": 4096, "pool": 4}
SOURCE = ("https://huggingface.co/inclusionAI/Ling-3.0-flash-VL/blob/main/"
          "config.json")
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
         "--seed", str(2 ** 31 + 46), "--seconds", "1", "--trace",
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
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        must, may = load(os.path.join(
            REPO, "tests", "benchmark", "test_benchmark.py")
        ).rehearsal_names(json.load(f), CELL, trace)
    assert must <= set(last["metrics"]) <= may, sorted(last["metrics"])
    assert not set(NEW_READERS + ACCEPTED_READERS) & set(last["metrics"])
    checks = phase["checks"]
    assert checks["losses_finite"] and checks["no_compile_in_window"]
    assert checks["first_loss_near_ln_classes"]
    # embedding, head, final norm; KDA 8 + 2 norms a layer (five), MLA 6
    # + 2 norms; dense 2 (two), sparse 5 (four)
    assert phase["setup"]["parameters"] == 3 + 5 * 10 + 8 + 2 * 2 + 4 * 5


def test_same_seed_same_documents_and_the_label_shift():
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
    widths = {"hidden_size": 2560, "num_attention_heads": 32,
              "num_key_value_heads": 32, "head_dim": 128,
              "q_lora_rank": None, "kv_lora_rank": 512,
              "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
              "v_head_dim": 128, "rotary_dim": 64,
              "intermediate_size": 6144, "moe_intermediate_size": 768,
              "moe_shared_expert_intermediate_size": 768,
              "num_experts_per_tok": 8, "n_group": 8, "topk_group": 4,
              "routed_scaling_factor": 2.5, "score_function": "sigmoid",
              "norm_topk_prob": True, "moe_router_enable_expert_bias": True,
              "layer_group_size": 6, "first_k_dense_replace": 2,
              "short_conv_kernel_size": 4, "kda_lower_bound": -5,
              "kda_safe_gate": True, "no_kda_lora": True,
              "group_norm_size": 1, "num_kv_heads_for_linear_attn": 0,
              "rope_theta": 6000000, "rms_norm_eps": 1e-06}
    assert {k: cfg[k] for k in widths} == widths
    assert cfg["router_width"] == cfg["published"]["num_experts"] == 512
    assert cfg["published"] == {"num_hidden_layers": 42,
                                "num_experts": 512, "vocab_size": 157184}
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (6, 8, 19648)
    # the guide's floors: an eighth of the vocabulary, 8 experts a layer,
    # a whole period, four layers after the leading dense ones
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["num_experts"] >= 8
    assert MODEL.layer_kinds(cfg) == ["dense"] * 2 + ["sparse"] * 4
    assert MODEL.mixer_kinds(cfg) == ["kda"] * 5 + ["mla"]
    assert cfg["num_experts"] \
        * cfg["deployment"]["chips_that_share_a_layer"] \
        == cfg["router_width"]
    assert cfg["expert_start"] == 0 and cfg["classes"] == cfg["vocab_size"]
    assert {"kda gate", "kda output", "kda convolution", "qk norm",
            "attention", "rope", "router", "bias update", "shared expert",
            "weights", "packing", "chunk of the delta rule", "left out",
            "token ids"} <= set(cfg["assumed"])
    assert (cfg["router_bias_update_rate"], cfg["kda_chunk_size"]) \
        == (0.001, 64)
    assert "707,798,176" in cfg["deployment"]["parameters"]
    assert "EP64 x 7 stages" in cfg["deployment"]["is"]
    assert cfg["flags"] == {"FLAGS_use_bf16_matmul": True}
    assert cfg["optimizer"]["name"] == "adam" \
        and cfg["optimizer"]["lr"] == 1e-4
    # the builder's own published sizes are the file's
    sys.path.insert(0, REPO)
    from paddle_tpu.models import ling3_flash
    published, program = ling3_flash.ling3_flash_config(), \
        MODEL.model_cfg(cfg)
    cut = ("vocab_size", "layer_types", "mlp_types", "experts_held")
    assert {k: v for k, v in program.items() if k not in cut} \
        == {k: v for k, v in published.items() if k not in cut}
    for key in ("layer_types", "mlp_types"):
        assert program[key] == published[key][:6]
    assert program["experts_held"] == 8 and program["num_experts"] == 512


@pytest.mark.parametrize("key,value", [
    ("q_lora_rank", 1536), ("use_kda_lora", True), ("group_norm_size", 4),
    ("expert_swiglu_limit_list", [7] + [0] * 41)])
def test_a_configuration_the_program_does_not_build_stops_the_build(key,
                                                                   value):
    with pytest.raises(ValueError, match="not built"):
        MODEL.model_cfg(dict(config(), **{key: value}))


def test_the_builder_s_program_counts_the_cell_s_parameters():
    """707,798,176 from the shapes of the program `build` makes of the
    file (not run), part by part as `deployment.parameters` says; the
    routers' biases are persistables, not parameters."""
    import numpy as np
    sys.path.insert(0, REPO)
    main, _, fetches = MODEL.build(config(), TRAFFIC)
    sizes = {p.name: int(np.prod(p.shape))
             for p in main.global_block().all_parameters()}
    assert sum(sizes.values()) == 707798176
    assert sizes["embed_tokens"] + sizes["lm_head"] == 100597760
    assert 16 * sum(sizes.values()) == pytest.approx(11.32e9, rel=1e-3)
    biases = [v for v in main.global_block().vars.values()
              if v.name.endswith("router_bias")]
    assert len(biases) == 4 and all(v.persistable for v in biases)
    assert len(fetches) == 1


def test_first_loss_is_the_analytic_value_and_the_tolerance_comes_from_it():
    want = config()["correct"]
    v = 2560 * 0.02 ** 2
    first = math.log(19648) + v / 2
    assert abs(first - 10.398) < 1e-3 and "10.398" in want["first_loss_is"]
    over = first / math.log(19648) - 1
    assert over < want["first_loss_rel_tol"] < over + 0.015
    for sign in (1, -1):
        assert abs(first + sign * 0.009 * math.log(19648)
                   - math.log(19648)) \
            <= want["first_loss_rel_tol"] * math.log(19648)
    assert want["first_loss_rel_tol"] == 0.062 and want["falling_n"] == 10


def test_flops_per_sample_is_the_closed_form():
    """12.46e12: 6 x 471,384,064 matmul weights a token x 4096 (the
    routed experts at the expected 1/8 held assignment a token) + 3 x
    the one MLA layer's causal maps at 192 + 128 + 3 x the five KDA
    layers' chunk products."""
    got = MODEL.flops_per_sample(config(), TRAFFIC)
    h = 2560
    kda = h * 4 * 4096 + 2 * h * 32 + 4096 * h
    mla = h * 576 + h * 6144 + 512 * 8192 + h * 32 + 4096 * h
    assert (kda, mla) == (52592640, 31965184)
    sparse = h * 512 + 3 * h * 768 + 8 * 8 / 512 * 3 * h * 768
    weights = 5 * kda + mla + 2 * 3 * h * 6144 + 4 * sparse + h * 19648
    assert weights == 471384064
    assert weights == sum(MODEL.matmul_weights_per_token(config()).values())
    maps = (4096 * 4097 // 2) * 32 * 2 * (192 + 128)
    assert MODEL.attention_flop_per_sample(config(), TRAFFIC) == maps
    chunk = 4 * 64 * 128 + 2 * 64 * 64 / 3 + 3 * 2 * 64 * 128 \
        + 6 * 128 * 128
    assert MODEL.kda_flop_per_token_head(config()) == pytest.approx(chunk)
    delta = 5 * 3 * 4096 * 32 * chunk
    assert got == pytest.approx(6 * weights * 4096 + 3 * maps + delta,
                                rel=1e-12)
    assert abs(got / 12.46e12 - 1) < 2e-3


@pytest.mark.parametrize("counter,want", [
    # the first layer alone, KDA and dense: 4 heads of 8, 64 tokens x 2:
    # the chunk products a token and head at C 64; f32 bytes: read q, k,
    # v, a (4 x 32) and b (4), written o (32)
    ("kda_required", {
        "flop": 3 * 128 * 4 * (4 * 64 * 8 + 2 * 64 * 64 / 3 + 6 * 64 * 8
                               + 6 * 8 * 8),
        "bytes": 128 * 4 * (3 * (4 * 32 + 4) + 2 * 32)}),
    # no latent attention and no sparse layer in it: nothing required
    ("attn_required", {"flop": 0, "bytes": 0}),
    ("mla_required", {"flop": 0, "bytes": 0}),
    ("moe_required", {"flop": 0, "bytes": 0})])
def test_roofline_counters_against_hand_counts_one_layer(counter, want):
    toy, _ = MODEL.tiny(config(), TRAFFIC)
    toy = dict(toy, num_hidden_layers=1)
    got = getattr(MODEL, counter)(toy, {"batch": 2, "seq_len": 64})
    assert got["flop"] == pytest.approx(want["flop"], rel=1e-12)
    assert got["bytes"] == want["bytes"]


@pytest.mark.parametrize("counter,want", [
    # the sixth layer: latent attention, 4 heads, 128 tokens; kept keys
    # 2080 a sequence, 2 x (12 + 8) = 40 FLOP a head and key
    ("attn_required", {"flop": 3 * 2 * 2080 * 4 * 40,
                       "bytes": 128 * 2 * (6 * 48 + 6 * 32)}),
    # up-projections from the hidden state 32 x 48 and the latent 16 x
    # 64 = 2560 weights; reading 32 + 16 + 4, writing 48 + 48 + 32
    ("mla_required", {"flop": 6 * 128 * 2560,
                      "bytes": 4 * (3 * 2560 + 128 * (3 * 52 + 2 * 128))}),
    # 4 sparse layers: a 16-wide router, 128 x 4 x 4 / 16 = 128 expected
    # rows through 3 x 32 x 12
    ("moe_required", {"flop": 4 * 6 * (128 * 512 + 128 * 1152),
                      "bytes": 4 * 4 * (3 * (4 * 1152 + 512)
                                        + 5 * 128 * 32)})])
def test_roofline_counters_against_hand_counts_six_layers(counter, want):
    toy, _ = MODEL.tiny(config(), TRAFFIC)
    got = getattr(MODEL, counter)(toy, {"batch": 2, "seq_len": 64})
    assert got["flop"] == pytest.approx(want["flop"], rel=1e-12)
    assert got["bytes"] == want["bytes"]


def test_the_cell_s_required_work_bounds_what_the_readers_divide_by():
    """At the cell's sizes KDA is bound by its bytes (5.74 ms at the HBM
    peak against 1.83 of FLOP), the attention maps and the latent op by
    FLOP, the expert layers by their bytes: 64 rows an expert, 1/64 of
    a rank's load."""
    kda = MODEL.kda_required(config(), TRAFFIC)
    attn = MODEL.attn_required(config(), TRAFFIC)
    moe = MODEL.moe_required(config(), TRAFFIC)
    mla = MODEL.mla_required(config(), TRAFFIC)
    assert kda["bytes"] / 819e9 == pytest.approx(5.74e-3, rel=2e-3)
    assert kda["flop"] / 197e12 == pytest.approx(1.83e-3, rel=3e-3)
    assert attn["flop"] / 197e12 > attn["bytes"] / 819e9
    assert mla["flop"] / 197e12 > mla["bytes"] / 819e9
    assert moe["bytes"] / 819e9 > moe["flop"] / 197e12
    sys.path.insert(0, REPO)
    from paddle_tpu.ops import decoder_ops
    assert decoder_ops.row_bound(4096, 8, 8, 512) == 1024
    assert 4096 * 8 * 8 / 512 / 8 == 64
    assert decoder_ops.kda_block(-5.0, 64) == 16


# ----------------------------------------------------------- the readers
@pytest.mark.parametrize("name", NEW_READERS + ACCEPTED_READERS)
def test_the_cell_s_readers_find_nothing_without_a_chips_plane(name):
    """The two readers of the KDA op and the eleven accepted files that
    read the cell: on a run with no trace each returns None and does not
    raise, as on a program that lacks the op (the parent's)."""
    run = types.SimpleNamespace(trace=None, spans={}, counters={}, chips=1,
                                device_kind="cpu")
    assert load(os.path.join(BENCH, "layer_metrics",
                             name + ".py")).compute(run) is None


def test_kda_readers_take_the_union_under_the_kda_op_s_scopes_alone(
        monkeypatch):
    reader = load(os.path.join(BENCH, "layer_metrics", "device_ms.kda.py"))
    share = load(os.path.join(BENCH, "layer_metrics", "kda_roofline_pct.py"))
    helper = reader.helper()
    assert reader.OPS == share.OPS == ("kda",)
    found = {"steps": 2, "planes": [[
        (0.000, 0.030, "fwd/kda", "fusion.1"),
        (0.010, 0.040, "fwd/kda", "while.2"),                   # overlaps
        (0.050, 0.060, "bwd/kda_grad", "fusion.3"),
        (0.070, 0.090, "fwd/gated_delta_rule", "while.4"),      # not its
        (0.100, 0.110, "fwd/causal_conv1d", "fusion.5")]]}
    assert helper.union_ms_per_step(found, reader.OPS) == pytest.approx(25.0)
    monkeypatch.setattr(helper, "_state", dict(helper._state, last=found))
    assert reader.compute(None) == pytest.approx(25.0)
    monkeypatch.setattr(helper, "cell_files", lambda: (
        config(), TRAFFIC, MODEL))
    run = types.SimpleNamespace(chips=1, device_kind="TPU v5 lite",
                                peak=load(os.path.join(BENCH, "peaks.py"))
                                .peak)
    need = MODEL.kda_required(config(), TRAFFIC)["bytes"] / 819e9
    assert share.compute(run) == pytest.approx(100 * need / 25e-3,
                                               rel=1e-9)
    monkeypatch.setattr(helper, "cell_files", lambda: (
        config(), TRAFFIC, types.SimpleNamespace()))
    assert share.compute(run) is None  # a configuration without KDA
    monkeypatch.setattr(helper, "_state", dict(helper._state, last=None))
    assert reader.compute(None) is None and share.compute(run) is None


def check_manifest(m):
    """The readers this cell brought and the eleven accepted files that
    read it are per-layer entries that list the cell (a later change may
    append cells to their lists); the cell and the configuration are
    found by NAME, wherever later entries put them."""
    by_name = {p["name"]: p for p in m["per_layer"]}
    for name in NEW_READERS + ACCEPTED_READERS:
        entry = by_name[name]
        assert CELL in entry["workloads"], name
        assert (entry["layer"], entry["moves"]) == ("Op kernels",
                                                    "samples_per_s")
        assert entry["better"] == ("higher" if name.endswith("_pct")
                                   else "lower")
        assert entry["source"] == "device_trace" or name.endswith("_step")
    for name in NEW_READERS:
        assert by_name[name]["workloads"][0] == CELL
    assert by_name["kda_roofline_pct"]["unit"] == "%"
    assert by_name["device_ms.kda"]["unit"] == "ms"
    cell = {w["name"]: w for w in m["workloads"]}[CELL]
    assert cell["chips"] == 1 and cell["config"] == CONFIG
    assert cell["traffic"] == "b1_s4096"
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
    assert len(m["configs"]) >= 8 and len(m["workloads"]) >= 10
    assert sum(w["chips"] == 4 for w in m["workloads"]) \
        <= max(1, len(m["workloads"]) // 4)


def test_the_real_manifest_passes_every_manifest_check_of_this_directory():
    """`check_manifest*` of `test_benchmark.py` and of each sibling cell's
    file, on the manifest as it stands: appending this cell to
    eleven lists, one configuration, one cell and two readers broke
    none."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        m = json.load(f)
    bench = load(os.path.join(REPO, "tests", "benchmark",
                              "test_benchmark.py"))
    bench.check_manifest_keys_names_and_units(m)
    bench.check_manifest_names_files_that_exist(m)
    for sibling in ("test_trace_scopes", "test_step_records",
                    "test_qwen3_next_cell", "test_phi4_flash_cell",
                    "test_laguna_xs2_cell", "test_smallthinker_cell",
                    "test_joyai_llm_flash_cell"):
        load(os.path.join(REPO, "tests", "benchmark",
                          sibling + ".py")).check_manifest(m)
