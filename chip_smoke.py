#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py               one chip: the main path
    python chip_smoke.py --multichip   four chips: the mesh paths, and
                                       what they are compared with
    python chip_smoke.py --qwen3-next  one chip: Qwen3-Next-80B-A3B's step
                                       at its cell's sizes against the
                                       plain float32 reference
    python chip_smoke.py --phi4-flash  one chip: Phi-4-mini-flash-reasoning's
                                       step at its cell's sizes (6 layers,
                                       published widths, 1 x 4096) against
                                       its plain float32 reference
    python chip_smoke.py --laguna      one chip: Laguna-XS.2's step at its
                                       cell's sizes (5 layers, published
                                       widths, 1 x 8192) against its plain
                                       float32 reference
    python chip_smoke.py --smallthinker  one chip: SmallThinker-21BA3B's
                                       step at its cell's sizes (4 layers,
                                       published widths, 1 x 16384) against
                                       its plain float32 reference
    python chip_smoke.py --joyai       one chip: JoyAI-LLM-Flash's step at
                                       its cell's sizes (5 layers and MTP,
                                       published widths, 1 x 8192) against
                                       its plain float32 reference: both
                                       losses, a gradient of every kind,
                                       the routers' biases after the step
    python chip_smoke.py --ling3       one chip: Ling-3.0-flash-VL's step at
                                       its cell's sizes (6 layers,
                                       published widths, 1 x 4096) against
                                       its plain float32 reference: the
                                       loss, a block of logits, a gradient
                                       of every kind, the routers' biases;
                                       and the reference's two KDA controls

Main path: BERT-base MLM pretraining at full width (12 x 768 x 12 heads x
3072, vocab 30522) at b128 x s128 with bf16 matmuls, built by
`bert.build_bert_pretrain_program` and run by `fluid.Executor` under
`TPUPlace` — the README's quick-start shape. Five optimizer steps, one
`exe.run` each, on one batch made from a seed (loss finite at every
step, lower at the last than at the first; parameters and the fetched
loss on the chip; NO Pallas kernel in the compiled step: at s128 a
head's score tile is one kernel block and the attention op takes XLA's
dense path, `attention_ops.DENSE_MAX_SEQ`), then the `exe.run(...,
n_steps=4)` window of the same program (twice: the first call compiles
the scan), then the flash kernels, which longer sequences run, against
the einsum reference on a small input. No OOM ladder and no shrink: a
size that does not fit is an error to read.

Everything runs in this one process — a chip belongs to one process at
a time. Without a TPU the script exits non-zero before any work and
prints nothing on stdout. Every stdout line is one JSON object; the last
one is the verdict, `{"ok": true, "device": {...}}`. Times are host-clock
smoke observations ended by block_until_ready, not benchmark results.

`--tiny` is the rehearsal: the same phases at a toy size on whatever
backend JAX has (the CPU, in the sandbox). It proves paths and control
flow, never the chip: it does not report ok, and exits non-zero.
"""
import argparse
import importlib.metadata
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
# where JAX_COMPILATION_CACHE_DIR is unset, the fixed in-checkout cache:
# the directory is part of the cache key, so it never moves
CACHE_DIR = os.path.join(ROOT, ".xla_cache")

FULL = dict(batch=128, seq_len=128, lr=1e-4)
TINY = dict(batch=8, seq_len=16, lr=1e-3,
            cfg=dict(vocab_size=128, hidden=32, layers=2, heads=4, ffn=64,
                     max_len=32, type_vocab=2))
SINGLE_STEPS, WINDOW_STEPS, MESH_STEPS = 5, 4, 3


def emit(**row):
    print(json.dumps(row), flush=True)


def cache_entries(cache_dir):
    return len([f for f in os.listdir(cache_dir) if not f.startswith(".")])


class CompileClock:
    """Seconds JAX spent in backend compiles (a load from the persistent
    cache counts as a short one), summed between two `take()`s."""

    def __init__(self):
        import jax.monitoring
        self.seconds, self.count = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event, duration, **kwargs):
        if event.endswith("backend_compile_duration"):
            self.seconds += duration
            self.count += 1

    def take(self):
        out = dict(compile_seconds=round(self.seconds, 2),
                   compiles=self.count)
        self.seconds, self.count = 0.0, 0
        return out


def build(size):
    from paddle_tpu.fluid import core
    from paddle_tpu.models import bert
    core.set_flag("FLAGS_use_bf16_matmul", True)
    cfg = size.get("cfg") or bert.bert_base_config()
    main, startup, _, fetches = bert.build_bert_pretrain_program(
        cfg, seq_len=size["seq_len"], dropout=0.0, lr=size["lr"])
    feed = bert.synthetic_pretrain_batch(cfg, size["batch"],
                                         size["seq_len"], seed=0)
    return cfg, (main, startup, fetches), feed


def train_one_chip(size, dev, clock):
    """Startup, SINGLE_STEPS runs of one step, the n_steps window."""
    import jax
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import core

    cfg, (main, startup, fetches), feed = build(size)
    exe = fluid.Executor(fluid.TPUPlace(0))
    scope = core.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        params = [p.name for p in main.global_block().all_parameters()]
        for name in params:
            where = scope.find_var(name).get_tensor().array.devices()
            assert where == {dev}, f"parameter {name} on {where}, not {dev}"

        losses, seconds = [], []
        for _ in range(SINGLE_STEPS):
            t0 = time.perf_counter()
            (loss,) = exe.run(main, feed=feed, fetch_list=fetches,
                              return_numpy=False)
            jax.block_until_ready(loss.array)
            seconds.append(time.perf_counter() - t0)
            assert loss.array.devices() == {dev}, loss.array.devices()
            losses.append(float(np.asarray(loss.array).ravel()[0]))
        assert np.isfinite(losses).all(), losses
        assert losses[-1] < losses[0], losses
        emit(phase="steps", batch=size["batch"], seq_len=size["seq_len"],
             layers=cfg["layers"], hidden=cfg["hidden"],
             n_parameters_on_device=len(params), losses=losses,
             # step 1 traces and compiles; the median leaves out the
             # first two all the same
             step_seconds=[round(s, 4) for s in seconds],
             smoke_observation_step_ms=round(
                 float(np.median(seconds[2:])) * 1e3, 2),
             **clock.take())

        # the executor's own compiled step, asked for its text: which
        # attention path is in what ran?
        from tools.mfu_report import compiled_step_of
        cb = compiled_step_of(exe)
        compiled = cb.lowered(
            scope, {n: scope.find_var(n).get_tensor().array
                    for n in cb.feed_names}, jax.random.key(0)).compile()
        n_kernels = compiled.as_text().count(
            'custom_call_target="tpu_custom_call"')
        mem = compiled.memory_analysis()
        emit(phase="executable", tpu_custom_calls=n_kernels,
             argument_bytes=mem.argument_size_in_bytes,
             temp_bytes=mem.temp_size_in_bytes, **clock.take())
        if dev.platform == "tpu":
            # s128 is at attention_ops.DENSE_MAX_SEQ: dense attention in
            # every layer, no kernel (above it: 4 a layer, pinned by
            # tests/test_chip_compile.py; on the chip by flash_parity)
            assert n_kernels == 0, n_kernels

        # the window twice: the first traces and compiles the scan, the
        # second runs the compiled one
        wl, window_seconds = [], []
        for _ in range(2):
            t0 = time.perf_counter()
            (window,) = exe.run(main, feed=feed, fetch_list=fetches,
                                return_numpy=False, n_steps=WINDOW_STEPS)
            jax.block_until_ready(window.array)
            window_seconds.append(time.perf_counter() - t0)
            got = np.asarray(window.array).ravel().tolist()
            assert len(got) == WINDOW_STEPS and np.isfinite(got).all(), got
            wl += got
        assert wl[-1] < wl[0] < losses[0], (wl, losses)
        emit(phase="window", n_steps=WINDOW_STEPS, losses=wl,
             window_seconds=[round(s, 4) for s in window_seconds],
             smoke_observation_window_step_ms=round(
                 window_seconds[1] / WINDOW_STEPS * 1e3, 2),
             **clock.take())
    stats = dev.memory_stats() or {}
    emit(phase="memory", peak_bytes_in_use=stats.get("peak_bytes_in_use"),
         bytes_limit=stats.get("bytes_limit"))


def flash_parity(tiny):
    """The flash kernels against the einsum reference, on this backend:
    Mosaic-compiled on a TPU, through the interpreter in the rehearsal."""
    from tools.flash_smoke import run_config
    row = run_config(128, 128, 128, B=2, H=12, steps=2, interpret=tiny)
    emit(phase="flash_parity",
         **{k: row.get(k) for k in
            ("status", "error", "traceback_tail", "max_err_fwd",
             "max_err_dq", "max_err_dk", "max_err_dv", "seq_len", "heads",
             "head_dim", "dtype")})
    assert row["status"] == "ok", row


# `reference_parity`: the largest error allowed, of each compared tensor,
# as max |program - reference| over max |reference| (the loss: absolute).
# Two programs are held to the one float32 reference (PERF.md §6, PR 28,
# has the chip's readings each limit lies between):
# "float32": the program with FLAGS_use_bf16_matmul off, traced under
#   jax.default_matmul_precision("highest"): the same arithmetic as the
#   reference in another order (chunks for tokens, a grouped product for
#   a loop over experts, flash kernels for S x S scores). These limits
#   are the tight ones: the reference with every activation rounded to
#   bf16 (`round_to`), the nearest precision below the configuration's,
#   has to fail at least one of them, and that is asserted.
# "bf16_operands": the cell's own precision. bf16 matmul operands move a
#   router's input by ~1e-3, which swaps a token's tenth expert for its
#   eleventh wherever the two lie closer than that, a token in twenty a
#   layer: a discrete change no rounding bound covers, so these limits
#   only fence the readings (PR 27 measured 9.5e-4 of a tensor's scale
#   for ONE attention op; here four layers and the routing lie between).
QWEN3_NEXT_LIMITS = {
    # readings (my chip runs, PR 28): the float32 program 9.5e-7 and
    # 4.0e-6 .. 3.1e-5; bf16 activations 7.1e-5 and 3.2e-2 .. 2.1e-1
    "float32": {
        "loss": 1e-5,
        "layers.0.gdn.w_qkvz@GRAD": 1e-3,
        "layers.0.gdn.a_log@GRAD": 1e-3,
        "layers.3.attn.w_q@GRAD": 1e-3,
        "layers.0.moe.w_router@GRAD": 1e-3,
        "layers.0.moe.w_down@GRAD": 1e-3,
        "layers.0.moe.shared_gate@GRAD": 1e-3,
        "embed_tokens@GRAD": 1e-3,
    },
    # readings: 1.8e-4 / 2.2e-4 and, in this order, 0.042, 0.050, 0.059,
    # 0.19, 0.23, 0.046, 0.040 (two runs within 7% of each other)
    "bf16_operands": {
        "loss": 1e-3,
        "layers.0.gdn.w_qkvz@GRAD": 0.1,
        "layers.0.gdn.a_log@GRAD": 0.1,
        "layers.3.attn.w_q@GRAD": 0.12,
        "layers.0.moe.w_router@GRAD": 0.4,
        "layers.0.moe.w_down@GRAD": 0.5,
        "layers.0.moe.shared_gate@GRAD": 0.1,
        "embed_tokens@GRAD": 0.1,
    },
}


# Phi-4-mini-flash: the same two programs against its reference. No
# router here, so bf16 operands change nothing discrete: their fences
# are rounding's (six layers of ~1e-2 a tensor), set above the readings.
PHI4_FLASH_LIMITS = {
    # readings (my chip runs, PR 32): in PERF.md §6
    "float32": {
        "loss": 1e-5,
        "layers.0.mamba.w_in@GRAD": 1e-3,
        "layers.0.mamba.a_log@GRAD": 1e-3,
        "layers.1.attn.w_qkv@GRAD": 1e-3,
        "layers.2.mamba.w_x@GRAD": 1e-3,      # the memory layer: GMU's part
        "layers.3.attn.w_qkv@GRAD": 1e-3,     # K, V: cross-attention's part
        "layers.4.gmu.w_in@GRAD": 1e-3,
        "layers.5.cross.w_q@GRAD": 1e-3,
        "layers.5.cross.lambda_q1@GRAD": 1e-3,
        "layers.0.mlp.w_down@GRAD": 1e-3,
        "embed_tokens@GRAD": 1e-3,            # tied: the lookup's + the head's
    },
    "bf16_operands": {
        "loss": 1e-3,
        "layers.0.mamba.w_in@GRAD": 0.1,
        "layers.0.mamba.a_log@GRAD": 0.1,
        "layers.1.attn.w_qkv@GRAD": 0.1,
        "layers.2.mamba.w_x@GRAD": 0.1,
        "layers.3.attn.w_qkv@GRAD": 0.1,
        "layers.4.gmu.w_in@GRAD": 0.1,
        "layers.5.cross.w_q@GRAD": 0.1,
        "layers.5.cross.lambda_q1@GRAD": 0.1,
        "layers.0.mlp.w_down@GRAD": 0.1,
        "embed_tokens@GRAD": 0.1,
    },
}


# Laguna-XS.2: the same two programs against its reference. A sigmoid
# router over 256 experts lies between, so bf16 operands move a discrete
# choice as in Qwen's: those limits only fence the readings.
LAGUNA_LIMITS = {
    # readings (my chip run, PR 34): the float32 program 0.0 and
    # 2.5e-6 .. 3.1e-5; bf16 activations 6.9e-5 and 3.0e-2 .. 3.7e-1
    "float32": {
        "loss": 1e-5,
        "layers.0.attn.w_q@GRAD": 1e-3,       # full: 48 heads, YaRN
        "layers.0.attn.w_g@GRAD": 1e-3,
        "layers.0.mlp.w_down@GRAD": 1e-3,
        "layers.1.attn.w_k@GRAD": 1e-3,       # window: 64 heads over 8
        "layers.1.moe.w_router@GRAD": 1e-3,
        "layers.1.moe.w_down@GRAD": 1e-3,
        "layers.1.moe.shared_w_gate_up@GRAD": 1e-3,
        "layers.4.attn.w_q@GRAD": 1e-3,
        "layers.4.moe.w_gate_up@GRAD": 1e-3,
        "embed_tokens@GRAD": 1e-3,
    },
    # readings: 4.9e-4 and, in this order, 0.034, 0.040, 0.031, 0.040,
    # 0.25, 0.45, 0.038, 0.039, 0.31, 0.041: where an expert's rows
    # changed hands its gradient moves by a third of its scale
    "bf16_operands": {
        "loss": 1e-3,
        "layers.0.attn.w_q@GRAD": 0.12,
        "layers.0.attn.w_g@GRAD": 0.12,
        "layers.0.mlp.w_down@GRAD": 0.12,
        "layers.1.attn.w_k@GRAD": 0.12,
        "layers.1.moe.w_router@GRAD": 0.7,
        "layers.1.moe.w_down@GRAD": 0.7,
        "layers.1.moe.shared_w_gate_up@GRAD": 0.12,
        "layers.4.attn.w_q@GRAD": 0.12,
        "layers.4.moe.w_gate_up@GRAD": 0.7,
        "embed_tokens@GRAD": 0.12,
    },
}


# SmallThinker-21BA3B: the same two programs against its reference, at
# the cell's init (the embedding from normal(0, 1), so that the stream
# the routers read un-normed holds tokens that differ: PERF.md §6).
# Readings: my chip run, PR 39's second session, seed 28. The float32
# program: loss 0.0, gradients 3.0e-7 .. 1.5e-4; the reference with bf16
# ACTIVATIONS reads 1.1e-4 and 1.5e-2 .. 0.17 and is over every limit.
# - `w_gate_up` keeps a limit of its own. ReLU's derivative is a step:
#   a routed token whose gate pre-activation lies within float32
#   rounding of zero gives its WHOLE rank-one term to one side and not
#   to the other (measured under the first init, when the read was
#   1.5e-3: every difference lay in single columns of the gate half,
#   one token's input row each, cosine 0.9999999; in the expert layer
#   alone the pre-activation there was ±4e-8 and float64 sided with
#   either; the op's backward has no defect). At this seed no term
#   flipped (6.6e-6); one is up to ~6e-3 of scale, bf16 activations
#   read 0.17: the limit 1.5e-2 lies between.
# bf16 operands move the router's discrete choice as in Qwen's and
# Laguna's, and with token states that differ more tokens sit near a tie
# than in a collapsed stream: those limits only fence the readings (in
# this order 5.7e-6, 0.0140, 0.0111, 0.0152, 0.0485, 0.246, 0.0998,
# 0.0283, 0.0180), ~3 x each.
SMALLTHINKER_LIMITS = {
    "float32": {
        "loss": 1e-5,
        "layers.0.attn.w_q@GRAD": 1e-3,       # full: no rotary at all
        "layers.0.moe.w_router@GRAD": 1e-3,   # fed the layer's input
        "layers.1.attn.w_q@GRAD": 1e-3,       # window 4096, rotary
        "layers.1.moe.w_router@GRAD": 1e-3,
        "layers.1.moe.w_gate_up@GRAD": 1.5e-2,  # ReLU-gated
        "layers.1.moe.w_down@GRAD": 1e-3,
        "lm_head@GRAD": 1e-3,
        "embed_tokens@GRAD": 1e-3,
    },
    "bf16_operands": {
        "loss": 1e-3,
        "layers.0.attn.w_q@GRAD": 0.045,
        "layers.0.moe.w_router@GRAD": 0.035,
        "layers.1.attn.w_q@GRAD": 0.045,
        "layers.1.moe.w_router@GRAD": 0.15,
        "layers.1.moe.w_gate_up@GRAD": 0.7,
        "layers.1.moe.w_down@GRAD": 0.3,
        "lm_head@GRAD": 0.09,
        "embed_tokens@GRAD": 0.055,
    },
}


# JoyAI-LLM-Flash: the same two programs against its reference, and
# also the MTP loss and each router's bias after the step.
# - A gradient of every kind of parameter (every role of a layer, the
#   MTP module's own, the shared embedding and head), float32 against
#   float32 at 1e-3 of its scale, a loss at 1e-5: the other decoders'
#   limits, whose readings at the same depth and router lie 30 x below
#   (Laguna: 2.5e-6 .. 3.1e-5, PR 34); bf16 activations move every
#   gradient by ~1e-2 and fail them.
# - A bias after the step is compared by the SHARE of its 256 entries
#   that differ by more than half a step gamma. A router's input moves
#   by float32 rounding between the program and the reference, so a
#   token within that of a tie between its 8th and 9th expert may be
#   sent elsewhere (a few of 8192 a layer), which moves two loads by one;
#   an expert whose load sat at the mean (256) or one off it then turns
#   the sign of its step: ~1 entry a router. 0.05 is 12 entries.
# - bf16 operands move the router's discrete choice as in the other
#   expert cells: those limits only fence the readings.
# Readings (my chip run, PR 41, seed 28): the float32 program, loss 9.5e-7
# and 0.0, gradients 3.9e-7 .. 3.4e-5, biases equal; bf16 operands, loss
# 5.6e-5 / 1.0e-4, gradients 0.008 .. 0.062 outside the experts and 0.12
# .. 0.31 on the router's and experts' weights, biases 0.4 .. 1.2% of
# entries; bf16 ACTIVATIONS over 29 of the 33 float32 limits.
JOYAI_LIMITS = {
    "float32": {
        "loss": 1e-5,
        "mtp_loss": 1e-5,
        "embed_tokens@GRAD": 1e-3,            # both lookups' parts
        "lm_head@GRAD": 1e-3,                 # both losses' parts
        "final_norm@GRAD": 1e-3,
        "layers.0.attn.w_q_a@GRAD": 1e-3,
        "layers.0.attn.q_a_norm@GRAD": 1e-3,
        "layers.0.attn.w_q_b@GRAD": 1e-3,     # latent_qkv's up-projections
        "layers.0.attn.w_kv_a@GRAD": 1e-3,    # c_KV and the shared k_R
        "layers.0.attn.kv_a_norm@GRAD": 1e-3,
        "layers.0.attn.w_kv_b@GRAD": 1e-3,
        "layers.0.attn.w_o@GRAD": 1e-3,
        "layers.0.input_norm@GRAD": 1e-3,
        "layers.0.post_norm@GRAD": 1e-3,
        "layers.0.mlp.w_gate_up@GRAD": 1e-3,
        "layers.0.mlp.w_down@GRAD": 1e-3,
        "layers.1.moe.w_router@GRAD": 1e-3,
        "layers.1.moe.w_gate_up@GRAD": 1e-3,
        "layers.1.moe.w_down@GRAD": 1e-3,
        "layers.1.moe.shared_w_gate_up@GRAD": 1e-3,
        "layers.1.moe.shared_w_down@GRAD": 1e-3,
        "layers.4.attn.w_kv_b@GRAD": 1e-3,
        "layers.4.moe.w_router@GRAD": 1e-3,
        "mtp.hnorm@GRAD": 1e-3,
        "mtp.enorm@GRAD": 1e-3,
        "mtp.eh_proj@GRAD": 1e-3,
        "mtp.norm@GRAD": 1e-3,
        "mtp.layer.attn.w_q_b@GRAD": 1e-3,
        "mtp.layer.moe.w_router@GRAD": 1e-3,
        "mtp.layer.moe.w_down@GRAD": 1e-3,
        "layers.1.moe.router_bias": 0.05,
        "layers.4.moe.router_bias": 0.05,
        "mtp.layer.moe.router_bias": 0.05,
    },
}
JOYAI_LIMITS["bf16_operands"] = {
    n: (1e-3 if "loss" in n else 0.04 if n.endswith("router_bias")
        else 0.7 if ".moe." in n else 0.12)
    for n in JOYAI_LIMITS["float32"]}


# Ling-3.0-flash-VL: the same two programs against its reference, and
# also the logits of the first LOGIT_ROWS positions and each router's
# bias after the step (by the share of entries, as JoyAI's: a bias here
# is 512 wide and a held expert expects 64 rows, so 0.05 is 25 entries).
# - Float32 against float32: a loss at 1e-5, logits and a gradient of
#   every kind (each KDA parameter, each latent-attention one, the dense
#   FFN, the grouped router, the experts, the shared embedding's and
#   head's) at 1e-3 of their scale, the other decoders' limits. The
#   reference with bf16 ACTIVATIONS must fail them, and so must each of
#   its two controls (`CONTROLS`): the KDA state kept in bf16 between
#   tokens, and each head's 128 decays replaced by their mean (a decay a
#   head); that is asserted.
# - bf16 operands move the router's discrete choice, in its group stage
#   too, as in the other expert cells: those limits only fence the
#   readings, ~1.4 x the largest of each kind. Readings (TPU v5 lite,
#   seed 28): the float32 program, loss 0.0, logits 1.2e-5,
#   gradients 5.6e-6 .. 6.0e-5, biases equal; bf16 operands, loss 9.0e-5,
#   logits 0.056, gradients 0.012 .. 0.22 outside the experts (the
#   largest KDA layer 4's W_qkva: the bf16-activation reference reads
#   0.17 there too) and 0.30 .. 0.62 on the routers and experts, biases
#   3.5-4.9% of entries; over the float32 limits, bf16 ACTIVATIONS
#   (loss 1.8e-4, up to 0.58) and the KDA state in bf16 (2.1e-4, up to
#   0.53) at 31 of 33, a decay a head (0.0105, up to 1.29) at all 33.
LING3_LIMITS = {
    "float32": {
        "loss": 1e-5,
        "logits": 1e-3,
        "embed_tokens@GRAD": 1e-3,
        "lm_head@GRAD": 1e-3,
        "final_norm@GRAD": 1e-3,
        "layers.0.input_norm@GRAD": 1e-3,
        "layers.0.attn.w_qkva@GRAD": 1e-3,    # q, k, v and the decay's W_a
        "layers.0.attn.conv_w@GRAD": 1e-3,
        "layers.0.attn.w_b@GRAD": 1e-3,
        "layers.0.attn.a_log@GRAD": 1e-3,
        "layers.0.attn.dt_bias@GRAD": 1e-3,
        "layers.0.attn.norm@GRAD": 1e-3,
        "layers.0.attn.w_g@GRAD": 1e-3,
        "layers.0.attn.w_o@GRAD": 1e-3,
        "layers.0.post_norm@GRAD": 1e-3,
        "layers.0.mlp.w_gate_up@GRAD": 1e-3,
        "layers.0.mlp.w_down@GRAD": 1e-3,
        "layers.2.moe.w_router@GRAD": 1e-3,   # the grouped router
        "layers.2.moe.w_gate_up@GRAD": 1e-3,
        "layers.2.moe.w_down@GRAD": 1e-3,
        "layers.2.moe.shared_w_gate_up@GRAD": 1e-3,
        "layers.2.moe.shared_w_down@GRAD": 1e-3,
        "layers.4.attn.w_qkva@GRAD": 1e-3,
        "layers.4.attn.a_log@GRAD": 1e-3,
        "layers.5.attn.w_q@GRAD": 1e-3,       # latent_qkv, no query latent
        "layers.5.attn.w_kv_a@GRAD": 1e-3,
        "layers.5.attn.kv_a_norm@GRAD": 1e-3,
        "layers.5.attn.w_kv_b@GRAD": 1e-3,
        "layers.5.attn.w_g@GRAD": 1e-3,
        "layers.5.attn.w_o@GRAD": 1e-3,
        "layers.5.moe.w_router@GRAD": 1e-3,
        "layers.2.moe.router_bias": 0.05,
        "layers.5.moe.router_bias": 0.05,
    },
}
LING3_LIMITS["bf16_operands"] = {
    n: (1e-3 if n == "loss" else 0.08 if n == "logits"
        else 0.07 if n.endswith("router_bias")
        else 0.85 if ".moe." in n else 0.3)
    for n in LING3_LIMITS["float32"]}


def _by_path(path):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "_smoke_" + os.path.basename(path)[:-3], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _gauge_by_site(name, sites):
    from paddle_tpu.fluid import telemetry
    family = telemetry.REGISTRY.get(name)
    return [int(family.value(site=site)) for site in sites] if family else None


def _qwen3_next_counters(main):
    """(further names to fetch, what to say of them and of the gauges):
    each expert layer's passes, the rows a step's products run, whether
    the combine kernel adds a layer's rows back, and the steps of each
    Gated DeltaNet layer's forward kernel's grid (None where the
    `lax.scan` lowering ran)."""
    from paddle_tpu.models import qwen3_next
    passes = qwen3_next.expert_passes(main)
    gdns = [op.attr("site") for op in main.global_block().ops
            if op.type == "gated_delta_rule"]

    def say(fetched):
        import numpy as np
        return dict(
            passes=[int(np.asarray(g)[0]) for g in fetched],
            moe_rows_per_step=sum(_gauge_by_site("moe_rows_per_step",
                                                 passes)),
            moe_combine_kernel=_gauge_by_site("moe_combine_kernel", passes),
            delta_grid_steps_per_step=_gauge_by_site(
                "delta_grid_steps_per_step", gdns))
    return list(passes.values()), say


def _phi4_flash_counters(main):
    """Nothing further to fetch; the gauges the new ops set: the block
    pairs each attention layer's forward kernel visits (window, full,
    cross) and the steps of its grid, at the blocks chosen for the call,
    the chunks each scan steps over, and the steps of its forward
    kernel's grid (None where the `lax.scan` lowering ran)."""
    from paddle_tpu.models import phi4_flash
    sites = phi4_flash.attention_sites(main)
    scans = [op.attr("site") for op in main.global_block().ops
             if op.type == "selective_scan"]

    def say(fetched):
        return dict(
            attention_windows=[window for _, window in sites.values()],
            attn_kv_blocks_per_step=_gauge_by_site(
                "attn_kv_blocks_per_step", sites),
            attn_grid_steps_per_step=_gauge_by_site(
                "attn_grid_steps_per_step", sites),
            ssm_chunks_per_step=_gauge_by_site("ssm_chunks_per_step", scans),
            ssm_grid_steps_per_step=_gauge_by_site(
                "ssm_grid_steps_per_step", scans))
    return [], say


def _routed_decoder_counters(attention_gauges, expert_gauges=()):
    """The counters function of a decoder with expert and attention
    layers: (each expert layer's passes to fetch, what to say of them
    and of the gauges): the rows a step's grouped products run, a
    layer's query heads and window, and the named gauges a layer, by
    the ops' sites."""
    def counters(main):
        from paddle_tpu.models._decoder_parts import (attention_sites,
                                                      expert_passes)
        passes = expert_passes(main)
        sites = attention_sites(main)

        def say(fetched):
            import numpy as np
            return dict(
                passes=[int(np.asarray(g)[0]) for g in fetched],
                moe_rows_per_step=sum(_gauge_by_site("moe_rows_per_step",
                                                     passes)),
                attention_heads_and_windows=list(sites.values()),
                **{name: _gauge_by_site(name, passes)
                   for name in expert_gauges},
                **{name: _gauge_by_site(name, sites)
                   for name in attention_gauges})
        return list(passes.values()), say
    return counters


_laguna_counters = _routed_decoder_counters(
    ("attn_query_heads", "attn_kv_blocks_per_step",
     "attn_grid_steps_per_step"), ("moe_combine_kernel",))
# also what the ops say of the mechanisms the model brought: the window
# and the K/V repeat a layer, the experts' gate
_smallthinker_counters = _routed_decoder_counters(
    ("attn_window", "attn_kv_repeat", "attn_kv_blocks_per_step",
     "attn_grid_steps_per_step"),
    ("moe_activation_relu", "moe_combine_kernel"))


def _joyai_counters(main):
    """(each expert layer's passes and each router's ids to fetch, what
    to say of them and of the gauges): the passes, the rows a step's
    products run, whether the combine kernel adds a layer's rows back,
    the held experts' share of each router's assignments (1/16 at a
    uniform router; experts from 0 held), a layer's block pairs and grid
    steps."""
    from paddle_tpu.models import joyai_flash
    passes = joyai_flash.expert_passes(main)
    sites = joyai_flash.attention_sites(main)
    ops = main.global_block().ops
    ids = [op.output("TopkIdx")[0] for op in ops if op.type == "moe_router"]
    held = next(main.global_block().var(op.input("WGateUp")[0]).shape[0]
                + op.attr("expert_start") for op in ops
                if op.type == "moe_expert_ffn")

    def say(fetched):
        import numpy as np
        return dict(
            passes=[int(np.asarray(g)[0]) for g in fetched[:len(passes)]],
            moe_rows_per_step=sum(_gauge_by_site("moe_rows_per_step",
                                                 passes)),
            moe_combine_kernel=_gauge_by_site("moe_combine_kernel", passes),
            held_share=[float(np.mean(np.asarray(g) < held))
                        for g in fetched[len(passes):]],
            **{name: _gauge_by_site(name, sites)
               for name in ("attn_kv_blocks_per_step",
                            "attn_grid_steps_per_step")})
    return list(passes.values()) + ids, say


def _ling3_counters(main):
    """JoyAI's, and the chunks each KDA layer's scan steps over, the
    steps of its forward kernel's grid (None where the `lax.scan`
    lowering ran) and the groups each router keeps."""
    further, say_routed = _joyai_counters(main)
    ops = main.global_block().ops
    kdas = [op.attr("site") for op in ops if op.type == "kda"]
    routers = [op.attr("site") for op in ops if op.type == "moe_router"]

    def say(fetched):
        return dict(say_routed(fetched),
                    kda_chunks_per_step=_gauge_by_site(
                        "kda_chunks_per_step", kdas),
                    delta_grid_steps_per_step=_gauge_by_site(
                        "delta_grid_steps_per_step", kdas),
                    moe_router_groups_kept=_gauge_by_site(
                        "moe_router_groups_kept", routers))
    return further, say


PARITY = {
    "qwen3_next": dict(config="qwen3_next_80b_a3b",
                       cell="qwen3_next_80b_a3b.b1_s4096",
                       limits=QWEN3_NEXT_LIMITS,
                       counters=_qwen3_next_counters),
    "phi4_flash": dict(config="phi4_mini_flash",
                       cell="phi4_mini_flash.b1_s4096",
                       limits=PHI4_FLASH_LIMITS,
                       counters=_phi4_flash_counters),
    "laguna": dict(config="laguna_xs2", cell="laguna_xs2.b1_s8192",
                   limits=LAGUNA_LIMITS, counters=_laguna_counters),
    "smallthinker": dict(config="smallthinker_21b_a3b",
                         cell="smallthinker_21b_a3b.b1_s16384",
                         limits=SMALLTHINKER_LIMITS,
                         counters=_smallthinker_counters),
    "joyai": dict(config="joyai_llm_flash", cell="joyai_llm_flash.b1_s8192",
                  limits=JOYAI_LIMITS, counters=_joyai_counters),
    "ling3": dict(config="ling3_flash_vl", cell="ling3_flash_vl.b1_s4096",
                  limits=LING3_LIMITS, counters=_ling3_counters),
}


def reference_parity(which, tiny, clock):
    """One step of a decoder cell's program (its sizes, its traffic,
    built by its configuration's files) against the plain reference at
    the same weights and batch: the fetched loss and the gradient of one
    parameter of each kind, fetched as `@GRAD`, once at the cell's
    precision and once with float32 operands. The weights are pulled
    from the scope after start-up (the seed makes them the same in both
    programs); a program's state leaves the chip before the next thing
    runs, so that each fits."""
    import contextlib
    import gc
    import jax
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import core, telemetry
    from paddle_tpu.models._decoder_parts import head_logits

    spec = PARITY[which]
    limits = spec["limits"]
    configs = os.path.join(ROOT, "benchmark", "configs")
    model = _by_path(os.path.join(configs, spec["config"] + ".py"))
    reference = _by_path(
        os.path.join(configs, spec["config"] + "_reference.py"))
    with open(os.path.join(configs, spec["config"] + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           spec["cell"] + ".json")) as f:
        traffic = json.load(f)["traffic"]
    if tiny:
        config, traffic = model.tiny(config, traffic)
    cfg = model.model_cfg(config)
    feed = model.make_batches(config, traffic, 28, 1)[0]
    names = list(limits["float32"])
    wanted = [n for n in names if n != "loss"]

    def program_step(bf16_operands):
        """(weights after start-up, {name: fetched}) of a fresh program."""
        core.set_flag("FLAGS_use_bf16_matmul", bf16_operands)
        main, startup, fetches = model.build(config, traffic)
        main.random_seed = startup.random_seed = 28
        exe = fluid.Executor(fluid.TPUPlace(0))
        scope = core.Scope()
        exe.run(startup, scope=scope)
        weights = {
            p.name: np.asarray(scope.find_var(p.name).get_tensor().array)
            for p in main.global_block().all_parameters()}
        further, say = spec["counters"](main)
        t0 = time.perf_counter()
        # "mtp_loss" is the second fetch, "logits" the head's output; any
        # other name a variable's (a gradient, or a bias after the step)
        by_name = {"mtp_loss": fetches[-1].name}
        if "logits" in wanted:
            by_name["logits"] = head_logits(main)
        with (contextlib.nullcontext() if bf16_operands
              else jax.default_matmul_precision("highest")):
            got = exe.run(main, feed=feed, scope=scope,
                          fetch_list=[fetches[0].name]
                          + [by_name.get(n, n) for n in wanted] + further)
        said = say(got[len(names):])
        got = dict(zip(names, (np.asarray(g) for g in got)))
        if "logits" in got:  # the positions the reference gives
            got["logits"] = got["logits"][:, :reference.LOGIT_ROWS]
        stats = jax.devices()[0].memory_stats() or {}
        kept = telemetry.REGISTRY.get("remat_kept_bytes")
        emit(phase=which + "_step", bf16_operands=bf16_operands,
             loss=float(got["loss"].ravel()[0]), **said,
             remat_kept_bytes=kept and int(kept.value()),
             parameters=int(sum(w.size for w in weights.values())),
             seconds=round(time.perf_counter() - t0, 1),
             peak_bytes_in_use=stats.get("peak_bytes_in_use"),
             **clock.take())
        return weights, got

    try:
        programs = {}
        for kind, bf16_operands in (("bf16_operands", True),
                                    ("float32", False)):
            weights, programs[kind] = program_step(bf16_operands)
            gc.collect()
    finally:
        core.set_flag("FLAGS_use_bf16_matmul", True)

    params = {n: jnp.asarray(w) for n, w in weights.items()}
    ids, labels = jnp.asarray(feed["ids"]), jnp.asarray(feed["labels"][..., 0])
    controls = getattr(reference, "CONTROLS", ())

    @jax.jit
    def read(params, rounded, flags):
        """The reference's loss and gradients (and what else it reads, by
        name, where it has `readings`); `rounded` rounds every activation
        to bf16 and `flags` turn on the reference's CONTROLS, one each
        (traced flags, so that all readings share one compile)."""
        # not a pair of converts: XLA may drop those as excess precision
        round_to = lambda x: jnp.where(  # noqa: E731
            rounded, jax.lax.reduce_precision(x, 8, 7), x)
        if controls:
            return reference.readings(params, feed, cfg, names, round_to,
                                      dict(zip(controls, flags)))
        if hasattr(reference, "readings"):
            return reference.readings(params, feed, cfg, names, round_to)
        loss, grads = reference.loss_and_grads(
            params, ids, labels, cfg, [n[:-len("@GRAD")] for n in wanted],
            round_to)
        return {"loss": loss, **{n + "@GRAD": g for n, g in grads.items()}}

    def error(name, a, b):
        a, b = np.asarray(a), np.asarray(b)
        if name.endswith("_bias"):  # a router's bias after the step: the
            # share of its entries more than half a step apart
            step = cfg["bias_update_rate"]
            return float(np.mean(np.abs(a - b) > step / 2))
        return float(np.abs(a - b).max()
                     / (1.0 if name.endswith("loss") else np.abs(b).max()))

    def errors(a, b):
        return {n: error(n, a[n], b[n]) for n in names}

    t0 = time.perf_counter()
    off = (False,) * len(controls)
    ref = {n: np.asarray(v) for n, v in read(params, False, off).items()}
    got = {kind: errors(programs[kind], ref) for kind in programs}
    below = errors(read(params, True, off), ref)
    control = {c: errors(read(params, False, tuple(n == c for n in controls)),
                         ref) for c in controls}
    emit(phase=which + "_parity", reference_loss=float(ref["loss"]),
         errors=got, bf16_activations=below, controls=control,
         limits=limits, seconds=round(time.perf_counter() - t0, 1),
         **clock.take())
    over = {f"{kind}: {n}": e for kind in got for n, e in got[kind].items()
            if e > limits[kind][n]}
    assert not over, f"over their limits: {over}"
    for name, errs in [("bf16 activations", below)] + list(control.items()):
        assert any(e > limits["float32"][n] for n, e in errs.items()), \
            f"{name} pass every float32 limit: the limits tell nothing"


def multichip(size, devices):
    """The mesh paths on ``devices``, and what each is compared with."""
    import __graft_entry__ as legs
    cfg, program, feed = build(size)
    solo = legs.bert_losses(program, feed, MESH_STEPS)
    emit(phase="bert_one_device", losses=solo, batch=size["batch"],
         seq_len=size["seq_len"], layers=cfg["layers"],
         hidden=cfg["hidden"])
    for model_parallel in (1, 2):
        emit(phase="bert_n_vs_1", **legs.bert_n_vs_1(
            devices, program, cfg, feed, model_parallel, solo))
    for row in legs.placement_legs(devices):
        emit(phase="placement", **row)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--multichip", action="store_true",
                    help="the four-chip mesh paths and nothing else")
    ap.add_argument("--qwen3-next", action="store_true",
                    help="Qwen3-Next's step against its float32 reference "
                         "and nothing else")
    ap.add_argument("--phi4-flash", action="store_true",
                    help="Phi-4-mini-flash's step against its float32 "
                         "reference and nothing else")
    ap.add_argument("--laguna", action="store_true",
                    help="Laguna-XS.2's step against its float32 "
                         "reference and nothing else")
    ap.add_argument("--smallthinker", action="store_true",
                    help="SmallThinker-21BA3B's step against its float32 "
                         "reference and nothing else")
    ap.add_argument("--joyai", action="store_true",
                    help="JoyAI-LLM-Flash's step against its float32 "
                         "reference and nothing else")
    ap.add_argument("--ling3", action="store_true",
                    help="Ling-3.0-flash-VL's step against its float32 "
                         "reference and nothing else")
    ap.add_argument("--tiny", action="store_true",
                    help="rehearsal at a toy size on any backend; never ok")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    want = 4 if args.multichip else 1
    if not args.tiny and dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, jax.devices()[0] is "
                 f"{dev.platform!r} ({dev.device_kind})")
    if len(devices) < want:
        sys.exit(f"chip_smoke: needs {want} devices, have {len(devices)}")

    from paddle_tpu.inference import enable_compile_cache
    cache_dir = enable_compile_cache(CACHE_DIR)
    emit(phase="start", device=device, jax=jax.__version__,
         jaxlib=importlib.metadata.version("jaxlib"),
         libtpu=importlib.metadata.version("libtpu"),
         compile_cache_dir=cache_dir,
         cache_entries_before=cache_entries(cache_dir))
    size = TINY if args.tiny else FULL
    parity = next((which for which in PARITY if getattr(args, which)), None)
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.multichip:
        multichip(size, devices[:4])
    elif parity:
        reference_parity(parity, args.tiny, clock)
    else:
        train_one_chip(size, dev, clock)
        flash_parity(args.tiny)
    emit(phase="end", seconds=round(time.perf_counter() - t0, 1),
         cache_entries_after=cache_entries(cache_dir), **clock.take())
    if args.tiny:
        emit(ok=False, rehearsal=True, device=device)
        sys.exit("chip_smoke: --tiny is a rehearsal, not a chip run")
    emit(ok=True, device=device)


if __name__ == "__main__":
    main()
