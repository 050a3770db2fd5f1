"""chip_smoke.py off the chip. The script is the quickest proof that the
system still starts on a TPU, so what the suite can hold it to here is
the other half of its contract: with no TPU it refuses — no training, no
result — and its rehearsal argument runs every phase at a toy size on
the CPU without ever reporting ok."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(tmp_path, *args, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla_cache"),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        capture_output=True, text=True, env=env, timeout=600, cwd=REPO)
    assert '"ok": true' not in res.stdout, res.stdout
    rows = [json.loads(line) for line in res.stdout.splitlines()]
    return res, {r["phase"]: r for r in rows if "phase" in r}, rows


@pytest.mark.parametrize("args", [(), ("--multichip",)],
                         ids=["one_chip", "multichip"])
def test_without_a_tpu_it_refuses_before_any_work(tmp_path, args):
    res, _, rows = _run(tmp_path, *args, devices=4)
    assert res.returncode != 0
    assert rows == [] and "needs a TPU" in res.stderr
    assert not (tmp_path / "xla_cache").exists()  # not even the cache dir


def test_tiny_rehearsal_runs_both_phases_and_never_reports_ok(tmp_path):
    res, phase, rows = _run(tmp_path, "--tiny")
    assert res.returncode != 0, res.stderr[-2000:]
    assert rows[-1] == {"ok": False, "rehearsal": True,
                        "device": {"platform": "cpu", "kind": "cpu",
                                   "count": 1}}
    single, window = phase["steps"]["losses"], phase["window"]["losses"]
    assert len(single) == 5 and len(window) == 2 * 4
    assert np.isfinite(single + window).all()
    assert window[-1] < single[-1] < single[0]
    assert phase["flash_parity"]["status"] == "ok"
    # the compile cache is where the variable says, and nowhere else
    assert phase["start"]["compile_cache_dir"] == str(tmp_path / "xla_cache")
    assert phase["end"]["cache_entries_after"] > 0


def test_multichip_rehearsal_passes_its_placement_assertions(tmp_path):
    """Every leg on four virtual devices: shards on four distinct
    devices, the leg's collective in its executable, loss parity."""
    res, phase, rows = _run(tmp_path, "--multichip", "--tiny", devices=4)
    assert res.returncode != 0 and rows[-1]["rehearsal"], res.stderr[-2000:]
    assert rows[-1]["device"]["count"] == 4
    legs = {r["leg"]: r for r in rows if "leg" in r}
    assert {n: r["collective"] for n, r in legs.items()} == {
        "bert_dp4xmp1": "all-reduce", "bert_dp2xmp2": "all-reduce",
        "gpipe": "collective-permute", "gpipe_het": "collective-permute",
        "ring_attention": "collective-permute", "moe": "all-to-all"}
    assert all(r["devices"] == 4 for r in legs.values())
    assert "steps" not in phase and "window" not in phase  # no one-chip phase


def test_laguna_rehearsal_holds_the_program_to_its_reference(tmp_path):
    """`--laguna --tiny`: both programs (bf16 operands are float32 ones
    on a CPU) within the float32 limits of the reference, the reference
    with bf16 ACTIVATIONS outside them, a pass an expert layer, a head
    count a layer kind."""
    res, phase, rows = _run(tmp_path, "--laguna", "--tiny")
    assert res.returncode != 0 and rows[-1]["rehearsal"], res.stderr[-2000:]
    parity = phase["laguna_parity"]
    limits = parity["limits"]["float32"]
    for errors in parity["errors"].values():
        assert all(errors[n] <= limits[n] for n in limits)
    assert any(parity["bf16_activations"][n] > limits[n] for n in limits)
    step = phase["laguna_step"]
    assert step["passes"] == [1, 1, 1, 1]
    assert step["moe_combine_kernel"] == [0] * 4  # lax.ragged_dot's path
    assert step["attn_query_heads"] == [4, 8, 8, 8, 4]
    assert step["attention_heads_and_windows"] == [
        [4, 0], [8, 24], [8, 24], [8, 24], [4, 0]]


def test_smallthinker_rehearsal_holds_the_program_to_its_reference(tmp_path):
    """`--smallthinker --tiny`: both programs (bf16 operands are float32
    ones on a CPU) within the float32 limits of the reference, the
    reference with bf16 ACTIVATIONS outside them, a pass an expert
    layer, and what the ops say of the model's mechanisms: 14 query
    heads over 2 (a repeat of 7), window 24 on three layers of four,
    ReLU-gated experts, XLA's scatter-add (no kernel off the chip)."""
    res, phase, rows = _run(tmp_path, "--smallthinker", "--tiny")
    assert res.returncode != 0 and rows[-1]["rehearsal"], res.stderr[-2000:]
    parity = phase["smallthinker_parity"]
    limits = parity["limits"]["float32"]
    assert {"loss", "layers.0.attn.w_q@GRAD", "layers.1.attn.w_q@GRAD",
            "layers.0.moe.w_router@GRAD", "layers.1.moe.w_gate_up@GRAD",
            "layers.1.moe.w_down@GRAD", "lm_head@GRAD",
            "embed_tokens@GRAD"} <= set(limits)
    for errors in parity["errors"].values():
        assert all(errors[n] <= limits[n] for n in limits)
    assert any(parity["bf16_activations"][n] > limits[n] for n in limits)
    step = phase["smallthinker_step"]
    assert step["passes"] == [1, 1, 1, 1]
    assert step["attention_heads_and_windows"] == [
        [14, 0], [14, 24], [14, 24], [14, 24]]
    assert step["attn_window"] == [0, 24, 24, 24]
    assert step["attn_kv_repeat"] == [7] * 4
    assert step["moe_activation_relu"] == [1] * 4
    assert step["moe_combine_kernel"] == [0] * 4  # lax.ragged_dot's path


def test_joyai_rehearsal_holds_the_program_to_its_reference(tmp_path):
    """`--joyai --tiny`: both programs (bf16 operands are float32 ones on
    a CPU) within the float32 limits of the reference, both losses, a
    gradient of every kind and each router's bias after the step; the
    reference with bf16 ACTIVATIONS outside them; a pass an expert layer
    (the MTP module's too), and the held experts' share of each router's
    assignments (4 of 16 held at the toy size)."""
    res, phase, rows = _run(tmp_path, "--joyai", "--tiny")
    assert res.returncode != 0 and rows[-1]["rehearsal"], res.stderr[-2000:]
    parity = phase["joyai_parity"]
    limits = parity["limits"]["float32"]
    assert {"loss", "mtp_loss", "embed_tokens@GRAD", "lm_head@GRAD",
            "layers.0.attn.w_kv_a@GRAD", "layers.0.attn.w_q_b@GRAD",
            "mtp.eh_proj@GRAD", "layers.1.moe.router_bias",
            "mtp.layer.moe.router_bias"} <= set(limits)
    for errors in parity["errors"].values():
        assert all(errors[n] <= limits[n] for n in limits)
    assert any(parity["bf16_activations"][n] > limits[n] for n in limits)
    step = phase["joyai_step"]
    assert step["passes"] == [1] * 5
    assert step["moe_combine_kernel"] == [0] * 5  # lax.ragged_dot's path
    assert len(step["held_share"]) == 5
    assert all(0 < share < 1 for share in step["held_share"])


def test_ling3_rehearsal_holds_the_program_to_its_reference(tmp_path):
    """`--ling3 --tiny`: both programs (bf16 operands are float32 ones on
    a CPU) within the float32 limits of the reference: the loss, a
    block of logits, a gradient of every kind and each router's bias
    after the step; the reference with bf16 ACTIVATIONS and under each
    of its two KDA controls outside them; a pass an expert layer, the
    chunks each KDA layer scans, the groups each router keeps, and the
    held experts' share of each router's assignments (4 of 16 held at
    the toy size)."""
    res, phase, rows = _run(tmp_path, "--ling3", "--tiny")
    assert res.returncode != 0 and rows[-1]["rehearsal"], res.stderr[-2000:]
    parity = phase["ling3_parity"]
    limits = parity["limits"]["float32"]
    assert {"loss", "logits", "layers.0.attn.a_log@GRAD",
            "layers.0.attn.dt_bias@GRAD", "layers.5.attn.w_q@GRAD",
            "layers.2.moe.w_router@GRAD",
            "layers.2.moe.router_bias"} <= set(limits)
    for errors in parity["errors"].values():
        assert all(errors[n] <= limits[n] for n in limits)
    assert any(parity["bf16_activations"][n] > limits[n] for n in limits)
    assert set(parity["controls"]) == {"kda_state_bf16",
                                       "kda_gate_head_mean"}
    for errors in parity["controls"].values():
        assert any(errors[n] > limits[n] for n in limits)
    step = phase["ling3_step"]
    assert step["passes"] == [1] * 4
    assert step["kda_chunks_per_step"] == [2 * 2] * 5  # 2 x ceil(80 / 64)
    assert step["moe_router_groups_kept"] == [2] * 4
    assert all(0 < share < 1 for share in step["held_share"])
