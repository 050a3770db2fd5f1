"""`qwen3_next_80b_a3b.b1_s4096`, off the chip: the cell's `--tiny`
rehearsal through `run.py`, its yardstick (`flops_per_sample` and the two
roofline counters) against counts written out here, its readers on a
run without a chip's plane, and the interval-union helper on a trace
made by hand. No test here describes a TPU topology.
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
CELL = "qwen3_next_80b_a3b.b1_s4096"
READERS = ["device_ms.gdn", "device_ms.moe", "device_ms.attn",
           "gdn_roofline_pct", "moe_roofline_pct", "moe_rows_per_step"]


def load(path):
    spec = importlib.util.spec_from_file_location(
        "_q3c_" + re.sub(r"\W", "_", os.path.relpath(path, REPO)), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def config():
    with open(os.path.join(BENCH, "configs", "qwen3_next_80b_a3b.json")) as f:
        return json.load(f)


MODEL = load(os.path.join(BENCH, "configs", "qwen3_next_80b_a3b.py"))
TRAFFIC = {"batch": 1, "seq_len": 4096, "pool": 4}


@pytest.fixture()
def union(monkeypatch):
    """`scope_union.py` as the readers share it, fresh."""
    monkeypatch.delitem(sys.modules, "_benchmark_scope_union", raising=False)
    monkeypatch.delitem(sys.modules, "_benchmark_trace_scopes", raising=False)
    return load(os.path.join(BENCH, "layer_metrics",
                             "device_ms.gdn.py")).helper()


# ---------------------------------------------------------------- the runs
@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_rehearsal_runs_the_cell_and_never_reports_correct(tmp_path,
                                                                trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla_cache"),
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    res = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2 ** 31 + 28), "--seconds", "1", "--trace",
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
    if trace:
        assert "scope_union" not in phase  # the helper raised nothing
    checks = phase["checks"]
    assert checks["losses_finite"] and checks["no_compile_in_window"]
    # the toy's first loss is near ln(96); a cycled pool is memorised (a
    # busy machine may fit too few steps into the second to show it)
    assert checks["first_loss_near_ln_classes"]
    assert checks["loss_falls"] or last["attempted"] < 4
    assert phase["setup"]["parameters"] == 66


def test_same_seed_same_documents_and_labels_are_the_next_ids():
    cfg = config()
    a, b, c = (MODEL.make_batches(cfg, dict(TRAFFIC, seq_len=33), seed, 2)
               for seed in (2 ** 31 + 5, 2 ** 31 + 5, 7))
    assert all((a[i][k] == b[i][k]).all() for i in range(2) for k in a[i])
    assert not (a[0]["ids"] == c[0]["ids"]).all()
    assert not (a[0]["ids"] == a[1]["ids"]).all()
    assert a[0]["ids"].shape == (1, 33) and a[0]["labels"].shape == (1, 33, 1)
    assert (a[0]["labels"][0, :-1, 0] == a[0]["ids"][0, 1:]).all()
    assert 0 <= a[0]["ids"].min() and a[0]["ids"].max() < cfg["vocab_size"]


# ------------------------------------------------------------ the yardstick
# the language model's settings as the source's config.json gives them
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


def test_config_file_holds_the_published_widths_and_states_the_cut():
    cfg = config()
    assert cfg["source"] == ("https://huggingface.co/Qwen/"
                             "Qwen3-Next-80B-A3B-Instruct/blob/main/"
                             "config.json")
    differs = {k for k, v in PUBLISHED.items() if cfg.get(k) != v}
    assert differs == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert cfg["published"] == {k: PUBLISHED[k] for k in cfg["reduced"]}
    assert cfg["router_width"] == PUBLISHED["num_experts"]
    assert cfg["num_experts"] == 32 >= 8            # the guide's floors
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert cfg["num_hidden_layers"] == cfg["full_attention_interval"] == 4
    assert cfg["deployment"]["chips_that_share_a_layer"] \
        * cfg["num_experts"] == cfg["router_width"]
    assert cfg["classes"] == cfg["vocab_size"]
    program = MODEL.model_cfg(cfg)
    assert program["num_experts"] == 512 and program["experts_held"] == 32


def test_flops_per_sample_is_the_closed_form():
    got = MODEL.flops_per_sample(config(), TRAFFIC)
    gdn = 2048 * (2048 + 2048 + 4096 + 4096) + 2048 * 64 + 4096 * 2048
    attention = 2048 * 16 * 2 * 256 + 2 * 2048 * 2 * 256 + 4096 * 2048
    dense_moe = 2048 * 512 + 3 * 2048 * 512 + 2048
    routed = 10 * 32 / 512 * 3 * 2048 * 512
    weights = 3 * gdn + attention + 4 * (dense_moe + routed) + 2048 * 18992
    assert weights == 191864832
    scores = 6 * 4096 * 4096 * 16 * 256          # causal half, fwd + bwd
    chunk = (4 * 64 * 128 + 2 * 64 * 64 / 3 + 2 * 64 * 128 + 2 * 64 * 128
             + 2 * 64 * 128 + 6 * 128 * 128)
    delta = 3 * 3 * 4096 * 32 * chunk
    assert got == pytest.approx(6 * weights * 4096 + scores + delta,
                                rel=1e-12)
    assert abs(6 * weights * 4096 / 4.715e12 - 1) < 1e-3
    assert abs(scores / 0.412e12 - 1) < 2e-3
    assert abs(got / 5.343e12 - 1) < 1e-3
    # a step is one sequence here, and tokens scale everything but the
    # scores linearly
    half = MODEL.flops_per_sample(config(), dict(TRAFFIC, seq_len=2048))
    assert got - 2 * half == pytest.approx(scores / 2, rel=1e-9)


@pytest.mark.parametrize("counter,want", [
    # 1 GDN layer at a toy shape, 128 tokens, 2 value heads of 8 x 8 over
    # 1 key head: chunk products 4*64*8 + 2*64*64/3 + 3*(2*64*8) +
    # 6*8*8 = 6698.67 a token-head, forward x 3; bytes 4 x tokens x
    # (3 x (8 + 8 + 16 + 4) read + 2 x 16 written)
    ("gdn_required", {"flop": 3 * 128 * 2 * (4 * 64 * 8 + 2 * 64 * 64 / 3
                                             + 3 * 2 * 64 * 8 + 6 * 8 * 8),
                      "bytes": 128 * 4 * (3 * 36 + 2 * 16)}),
    # 2 layers, 128 tokens, top-2 of 8 with 4 held, hidden 16, width 4:
    # rows 128 x 2 x 4 / 8 = 128 through 3 x 16 x 4 = 192 weights, the
    # router 16 x 8; bytes 4 x (3 x (4 x 192 + 128) + 5 x 128 x 16)
    ("moe_required", {"flop": 2 * 6 * (128 * 128 + 128 * 192),
                      "bytes": 2 * 4 * (3 * (4 * 192 + 128)
                                        + 5 * 128 * 16)})])
def test_roofline_counters_against_hand_counts(counter, want):
    toy = dict(config(), hidden_size=16, num_hidden_layers=2,
               full_attention_interval=2, linear_num_key_heads=1,
               linear_num_value_heads=2, linear_key_head_dim=8,
               linear_value_head_dim=8, router_width=8, num_experts=4,
               num_experts_per_tok=2, moe_intermediate_size=4)
    got = getattr(MODEL, counter)(toy, {"batch": 2, "seq_len": 64})
    assert got["flop"] == pytest.approx(want["flop"], rel=1e-12)
    assert got["bytes"] == want["bytes"]


def test_the_cell_s_required_work_bounds_what_the_readers_divide_by():
    """At the cell's sizes: the delta rule is bound by bytes, the expert
    layer by its weights' bytes; both far under the step's FLOP."""
    gdn = MODEL.gdn_required(config(), TRAFFIC)
    moe = MODEL.moe_required(config(), TRAFFIC)
    assert gdn["bytes"] / 819e9 > gdn["flop"] / 197e12
    assert moe["bytes"] / 819e9 > moe["flop"] / 197e12
    assert abs(moe["bytes"] / 5.55e9 - 1) < 0.01
    assert gdn["flop"] + moe["flop"] < 0.12 * MODEL.flops_per_sample(
        config(), TRAFFIC)


# ----------------------------------------------------------- the readers
@pytest.mark.parametrize("name", READERS)
def test_new_readers_find_nothing_without_a_chips_plane(name):
    run = types.SimpleNamespace(trace=None, spans={}, counters={}, chips=1,
                                device_kind="cpu")
    assert load(os.path.join(BENCH, "layer_metrics",
                             name + ".py")).compute(run) is None


def check_manifest(m):
    """The six readers are per-layer entries that list the cell (since PR
    38; a later PR may append cells to their lists), `Op kernels` moving
    `samples_per_s`; the cell and the four-chip cell by name."""
    by_name = {p["name"]: p for p in m["per_layer"]}
    for name in READERS:
        entry = by_name[name]
        assert CELL in entry["workloads"], name
        assert (entry["layer"], entry["moves"]) == ("Op kernels",
                                                    "samples_per_s")
        assert entry["better"] == ("higher" if name.endswith("_pct")
                                   else "lower")
    # the flash kernels' share of their roofline reads here too, through
    # this configuration's `attn_required`
    assert CELL in by_name["attn_roofline_pct"]["workloads"]
    cells = {w["name"]: w for w in m["workloads"]}
    assert cells[CELL]["chips"] == 1
    assert cells["bert_base.dp4_b512_s128"]["chips"] == 4
    # what every cell must report is still there for the new ones
    everywhere = [p for p in m["per_layer"] if "workloads" not in p]
    assert {p["moves"] for p in everywhere} >= {"samples_per_s", "setup_s"}


def test_the_manifest_lists_the_six_readers_with_the_cell():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        check_manifest(json.load(f))


def test_attn_required_is_the_causal_half_at_the_published_heads():
    """16 query heads x 256 over 2 key/value heads, one gated-attention
    layer of four: FLOP over the causal half with its diagonal, forward
    x 3; bytes of Q, K, V, O and their gradients in bf16."""
    got = MODEL.attn_required(config(), TRAFFIC)
    causal = 4096 * 4097 // 2
    assert got["flop"] == 3 * causal * 16 * (2 * 256 + 2 * 256)
    q, kv = 16 * 256, 2 * 256
    assert got["bytes"] == 4096 * 2 * ((2 * q + 2 * kv) + (3 * q + 2 * kv)
                                       + (q + 2 * kv))
    # bound by FLOP: 2.09 ms at the bf16 peak against 0.28 ms of bytes
    assert got["flop"] / 197e12 > 7 * got["bytes"] / 819e9
    assert abs(got["flop"] / 0.4124e12 - 1) < 1e-3
    # two sequences, two such layers of eight: four times the maps
    twice = MODEL.attn_required(dict(config(), num_hidden_layers=8),
                                dict(TRAFFIC, batch=2))
    assert twice == {"flop": 4 * got["flop"], "bytes": 4 * got["bytes"]}


def test_rows_counter_is_read_from_the_programs_registry(monkeypatch):
    from paddle_tpu.fluid import telemetry
    monkeypatch.setattr(telemetry, "REGISTRY", telemetry.MetricsRegistry())
    reader = load(os.path.join(BENCH, "layer_metrics",
                               "moe_rows_per_step.py"))
    traced = types.SimpleNamespace(trace={"busy_s": 1.0})
    assert reader.compute(traced) is None       # a program without it
    gauge = telemetry.REGISTRY.gauge("moe_rows_per_step", "",
                                     labelnames=("site",))
    for layer in range(4):
        gauge.labels(site=f"moe_expert_ffn_{layer}").set(40960)
    gauge.labels(site="moe_expert_ffn_0").set(40960)  # traced again
    assert reader.compute(traced) == 4 * 40960
    assert reader.compute(types.SimpleNamespace(trace=None)) is None


# ----------------------------------------------------- the interval union
def test_union_counts_a_while_s_body_once_on_a_trace_made_by_hand(union):
    # two steps, window 0..20. Chip 0: a `while` of the delta rule over
    # [2, 8] whose body's two fusions [3, 4] and [5, 7] lie inside it; the
    # backward's `while` [12, 15] with a body event [13, 14]; an expert
    # product [8, 9.5]; a flash kernel [9.5, 10.5]; an unscoped copy
    step = "jit__step(9)"
    device = {"/device:TPU:0": [
        (2.0, 8.0, "%while.3 = (f32[2]) while()", step),
        (3.0, 4.0, "%fusion.11 = f32[2] fusion()", step),
        (5.0, 7.0, "%fusion.12 = f32[2] fusion()", step),
        (8.0, 9.0, "%fusion.20 = f32[2] fusion()", step),
        (9.0, 9.5, "%ragged-dot-none.1 = f32[2] custom-call()", step),
        (9.5, 10.5, "%flash_fwd.4 = bf16[2] custom-call()", step),
        (10.5, 11.0, "%copy.2 = f32[2] copy()", step),
        (12.0, 15.0, "%while.5 = (f32[2]) while()", step),
        (13.0, 14.0, "%fusion.13 = f32[2] fusion()", step),
        (30.0, 31.0, "%fusion.11 = f32[2] fusion()", step),  # past the window
    ]}
    gdn, gdn_bwd = "jit(_step)/fwd/gated_delta_rule/", \
        "jit(_step)/bwd/gated_delta_rule_grad/transpose(jvp())/"
    modules = {step: ({
        "while.3": gdn + "while", "fusion.11": gdn + "while/body/dot_general",
        "fusion.12": gdn + "while/body/add",
        "fusion.20": "jit(_step)/checkpoint/fwd/moe_expert_ffn/gather",
        # XLA's own grouped-product kernel: no Fluid scope in its op_name
        "ragged-dot-none.1": "ragged-dot-none",
        "flash_fwd.4": "jit(_step)/fwd/fused_attention_qkv/flash_fwd/"
                       "pallas_call",
        "copy.2": "", "while.5": gdn_bwd + "while",
        "fusion.13": gdn_bwd + "while/body/dot_general"}, {})}
    host = [(0.0, 9.0, "exe.run"), (9.0, 10.0, "fetch"),
            (10.0, 19.0, "exe.run"), (19.0, 20.0, "fetch")]
    found = union.intervals(device, host, modules)
    assert found["steps"] == 2 and len(found["planes"][0]) == 9
    # the union: [2, 8] + [12, 15] = 9 s over 2 steps; a sum of durations
    # would read 6 + 1 + 2 + 3 + 1 = 13
    assert union.union_ms_per_step(found, ("gated_delta_rule",)) == 4500.0
    moe = ("moe_router", "moe_expert_ffn")
    assert union.union_ms_per_step(found, moe) == 500.0
    assert union.union_ms_per_step(found, moe, ("ragged-dot",)) == 750.0
    assert union.union_ms_per_step(found, ("fused_attention_qkv",)) == 500.0
    assert union.union_ms_per_step(found, ("no_such_op",)) == 0.0
    # a second chip, idle under these scopes: the mean of the planes
    device["/device:TPU:1"] = [(2.0, 3.0, "%copy.2 = f32[2] copy()", step)]
    two = union.intervals(device, host, modules)
    assert union.union_ms_per_step(two, ("gated_delta_rule",)) == 2250.0
    # nothing to read: no benchmark span, or no chip's plane
    assert union.intervals(device, [], modules) is None
    assert union.union_ms_per_step(
        union.intervals({}, host, modules), ("gated_delta_rule",)) is None
    assert union.ms_per_step(("gated_delta_rule",)) is None  # no trace taken


def test_roofline_share_is_the_larger_bound_over_the_measured_time(union):
    run = types.SimpleNamespace(
        chips=1, device_kind="TPU v5 lite",
        peak=load(os.path.join(BENCH, "peaks.py")).peak)
    # bound by bytes: 819e9 bytes are a second, measured two
    assert union.roofline_pct(run, {"flop": 1e12, "bytes": 819e9}, 2000.0) \
        == pytest.approx(50.0)
    # bound by FLOP: 197e12 are a second, measured four
    assert union.roofline_pct(run, {"flop": 197e12, "bytes": 1e9}, 4000.0) \
        == pytest.approx(25.0)
    assert union.roofline_pct(run, {"flop": 1.0, "bytes": 1.0}, None) is None


def test_watch_does_nothing_outside_the_harness(union):
    import jax.profiler
    before = (jax.profiler.start_trace, jax.profiler.stop_trace)
    union.watch()
    assert (jax.profiler.start_trace, jax.profiler.stop_trace) == before
    assert union.last() is None
