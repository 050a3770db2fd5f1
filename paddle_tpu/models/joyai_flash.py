"""JoyAI-LLM-Flash as a Fluid program: a DeepSeek-V3-style decoder LM
whose attention is latent (MLA, DeepSeek-V2 §2.1): the query through a
1536-wide latent, keys and values through one 512-wide latent, one
64-wide rotary key shared by all 32 heads, a 192-wide Q.K (128 without
rotary + 64 with, pairs of dims interleaved) over a 128-wide V; a
leading dense gated FFN, then sparse experts under a sigmoid router
that chooses by scores + a bias it keeps out of the gradient and moves
by the step's loads (DeepSeek-V3 §2.1.2), with one ungated shared
expert; and one multi-token-prediction module (DeepSeek-V3 §2.2) on the
shared embedding and head, whose loss is added to the next-token loss.
RMSNorm, untied embedding and head. Source:
https://huggingface.co/jdopensource/JoyAI-LLM-Flash (config.json); the
layer equations and each departure from the release are written out in
benchmark/configs/joyai_llm_flash_reference.py, the plain float32
reference the tests and ``chip_smoke.py`` hold this program to.

The expert layer is one expert-parallel rank's: it routes over all
``num_experts``, holds ``experts_held`` of them from ``expert_start`` and
adds their part alone (ops/decoder_ops.moe_expert_ffn); on one chip it
runs without the exchange. Parameter names are the reference's:
``layers.<i>.attn.*``, ``layers.<i>.mlp.*`` (dense) or
``layers.<i>.moe.*`` (sparse), ``layers.<i>.input_norm`` / ``post_norm``,
the MTP module's ``mtp.*``; a router's bias is the persistable
``<layer>.moe.router_bias``.
"""
from __future__ import annotations

from .. import fluid
from ..fluid import layers, telemetry
from ._decoder_parts import (attention_sites, attr, expert_passes, gated_ffn,
                             linear, minimize, rms_norm, router_biases)
from .bert import fused_multihead_attention

__all__ = ["joyai_flash_config", "build_joyai_flash_pretrain_program",
           "attention_sites", "expert_passes", "router_biases",
           "synthetic_pretrain_batch"]


def joyai_flash_config():
    """The published sizes (config.json), under this program's names."""
    return dict(
        vocab_size=129280, hidden=2048, heads=32, q_latent=1536,
        kv_latent=512, nope_dim=128, rope_dim=64, v_dim=128,
        rope_theta=32000000.0, mlp_types=["dense"] + ["sparse"] * 39,
        mlp_width=7168, num_experts=256, experts_per_tok=8,
        expert_width=768, shared_width=768, routed_scale=2.5, mtp_depth=1,
        eps=1e-6,
        # one rank's share and the training assumptions (not in the
        # source): DeepSeek-V3 §4.2's bias step and MTP weight
        experts_held=256, expert_start=0, init_std=0.02,
        bias_update_rate=0.001, mtp_weight=0.3)


def latent_attention(x, prefix, cfg):
    """c_Q = N(x W_q_a), [c_KV ; k_R] = x W_kv_a, c_KV = N(c_KV); the
    heads from ``layers.latent_qkv``; causal attention at D = nope +
    rope over V of v_dim, then W_o."""
    heads, rope = cfg["heads"], cfg["rope_dim"]
    c_q = rms_norm(linear(x, cfg["q_latent"], prefix + "w_q_a", cfg),
                   prefix + "q_a_norm", cfg)
    c_kv, k_rope = layers.split(
        linear(x, cfg["kv_latent"] + rope, prefix + "w_kv_a", cfg),
        [cfg["kv_latent"], rope], dim=-1)
    c_kv = rms_norm(c_kv, prefix + "kv_a_norm", cfg)
    q, k, v = layers.latent_qkv(
        c_q, c_kv, k_rope, heads, cfg["nope_dim"], rope, cfg["v_dim"],
        cfg["rope_theta"], q_attr=attr(prefix + "w_q_b", cfg),
        kv_attr=attr(prefix + "w_kv_b", cfg))
    o = fused_multihead_attention(q, k, v, heads, causal=True,
                                  n_v_head=heads)
    return linear(o, cfg["hidden"], prefix + "w_o", cfg)


def sparse_moe(x, prefix, cfg):
    """The held experts' part under the biased sigmoid router + the
    shared expert (no gate on it, no auxiliary loss: the source has a
    key for neither)."""
    idx, weight, _ = layers.moe_router(
        x, cfg["num_experts"], cfg["experts_per_tok"], scoring="sigmoid",
        scale=cfg["routed_scale"], param_attr=attr(prefix + "w_router", cfg),
        bias_update_rate=cfg["bias_update_rate"],
        bias_name=prefix + "router_bias")
    routed = layers.moe_expert_ffn(
        x, idx, weight, cfg["experts_held"], cfg["expert_width"],
        expert_start=cfg["expert_start"], num_experts=cfg["num_experts"],
        gate_up_attr=attr(prefix + "w_gate_up", cfg),
        down_attr=attr(prefix + "w_down", cfg))
    return layers.elementwise_add(
        routed, gated_ffn(x, cfg["shared_width"], prefix + "shared_", cfg))


def decoder_layer(x, prefix, cfg, dense):
    h = rms_norm(x, prefix + "input_norm", cfg)
    x = layers.elementwise_add(x, latent_attention(h, prefix + "attn.", cfg))
    h = rms_norm(x, prefix + "post_norm", cfg)
    if dense:
        y = gated_ffn(h, cfg["mlp_width"], prefix + "mlp.", cfg)
    else:
        y = sparse_moe(h, prefix + "moe.", cfg)
    return layers.elementwise_add(x, y)


def mtp_block(h, next_ids, cfg):
    """DeepSeek-V3 §2.2, depth 1: h' = W_eh [N_h(h_i) ; N_e(Emb(t_{i+1}))]
    (h first: the paper's order), then one sparse decoder layer. ``h`` is
    the trunk's last state before its final norm; the embedding is the
    trunk's."""
    e = layers.embedding(next_ids, [cfg["vocab_size"], cfg["hidden"]],
                         param_attr=attr("embed_tokens", cfg))
    joined = layers.concat([rms_norm(h, "mtp.hnorm", cfg),
                            rms_norm(e, "mtp.enorm", cfg)], axis=-1)
    return decoder_layer(linear(joined, cfg["hidden"], "mtp.eh_proj", cfg),
                         "mtp.layer.", cfg, dense=False)


def _cross_entropy(x, norm, labels, cfg):
    logits = linear(rms_norm(x, norm, cfg), cfg["vocab_size"], "lm_head",
                    cfg)
    return layers.mean(layers.softmax_with_cross_entropy(logits, labels))


def build_joyai_flash_pretrain_program(cfg=None, seq_len=8192, lr=1e-4,
                                       recompute=True):
    """Pretraining step over ``len(cfg["mlp_types"])`` layers and the MTP
    module (``mtp_depth`` 1: a deeper one raises; the depth and the
    loss's weight are the gauges ``mtp_depth`` / ``mtp_loss_weight``,
    site "mtp", set here). Feeds: ``ids`` [B, S], ``labels`` [B, S, 1]
    (the ids shifted by one) and ``mtp_labels`` [B, S, 1] (shifted by
    two), int64. Trained:
    CE_main + ``mtp_weight`` CE_mtp, the cross entropies averaged over
    the positions; fetched: CE_main first, CE_mtp second. ``recompute``:
    one RecomputeOptimizer checkpoint at the embedding's output, at every
    decoder layer's and at the MTP block's, so that a layer's internals
    live only while its backward runs. -> (main, startup, feeds,
    fetches)."""
    cfg = cfg or joyai_flash_config()
    if cfg["mtp_depth"] != 1:
        raise ValueError(f"one MTP module is built, not {cfg['mtp_depth']}")
    telemetry.set_site_gauge(
        "mtp_depth", "multi-token-prediction modules the program trains",
        "mtp", cfg["mtp_depth"])
    telemetry.set_site_gauge(
        "mtp_loss_weight", "the MTP loss's weight in the trained loss",
        "mtp", cfg["mtp_weight"])
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = fluid.data("ids", shape=[seq_len], dtype="int64")
        labels = fluid.data("labels", shape=[seq_len, 1], dtype="int64")
        mtp_labels = fluid.data("mtp_labels", shape=[seq_len, 1],
                                dtype="int64")
        x = layers.embedding(ids, [cfg["vocab_size"], cfg["hidden"]],
                             param_attr=attr("embed_tokens", cfg))
        checkpoints = [x]
        for i, kind in enumerate(cfg["mlp_types"]):
            x = decoder_layer(x, f"layers.{i}.", cfg, kind == "dense")
            checkpoints.append(x)
        mtp = mtp_block(x, labels, cfg)
        checkpoints.append(mtp)
        loss_main = _cross_entropy(x, "final_norm", labels, cfg)
        loss_mtp = _cross_entropy(mtp, "mtp.norm", mtp_labels, cfg)
        loss = layers.elementwise_add(
            loss_main, layers.scale(loss_mtp, scale=cfg["mtp_weight"]))
        minimize(loss, lr, recompute, checkpoints)
    return main, startup, [ids, labels, mtp_labels], [loss_main, loss_mtp]


def synthetic_pretrain_batch(cfg, batch, seq_len, seed=0):
    """One feed dict: documents of seq_len + 2 ids uniform over the
    vocabulary from ``seed``, one a sequence; labels are the next ids,
    ``mtp_labels`` the ids after those."""
    import numpy as np
    doc = np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (batch, seq_len + 2), dtype=np.int64)
    return {"ids": doc[:, :-2].copy(), "labels": doc[:, 1:-1, None].copy(),
            "mtp_labels": doc[:, 2:, None].copy()}
