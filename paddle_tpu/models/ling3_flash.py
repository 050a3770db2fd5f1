"""Ling-3.0-flash-VL's language model as a Fluid program: a decoder LM
whose mixers are Kimi delta attention (KDA, arXiv:2510.26692: the delta
rule with a decay a key channel, bounded below, after a short causal
convolution) in five layers of each six and latent attention (MLA,
DeepSeek-V2 §2.1, its query projected from the layer's input with no
latent, rotate-half rotary on the one shared 64-wide key) in the sixth,
a head-wise sigmoid gate on both mixers' output; two leading dense gated
FFNs, then sparse experts under a sigmoid router with a group stage and
a choice-only bias (DeepSeek-V3 §2.1.2: 8 groups of 64, the 4 best kept,
the top 8 inside them) and one ungated shared expert. RMSNorm, untied
embedding and head, one loss. Source:
https://huggingface.co/inclusionAI/Ling-3.0-flash-VL (config.json); the
layer equations and each departure from the release are written out in
benchmark/configs/ling3_flash_vl_reference.py, the plain float32
reference the tests and ``chip_smoke.py`` hold this program to.

The expert layer is one expert-parallel rank's: it routes over all
``num_experts``, holds ``experts_held`` of them from ``expert_start`` and
adds their part alone (ops/decoder_ops.moe_expert_ffn); on one chip it
runs without the exchange. Parameter names are the reference's:
``layers.<i>.attn.*`` (either mixer), ``layers.<i>.mlp.*`` (dense) or
``layers.<i>.moe.*`` (sparse), ``layers.<i>.input_norm`` /
``post_norm``; a router's bias is the persistable
``<layer>.moe.router_bias``.
"""
from __future__ import annotations

import math

from .. import fluid
from ..fluid import layers
from ..fluid.initializer import Constant, Uniform
from ..fluid.param_attr import ParamAttr
from ._decoder_parts import (attention_sites, attr, expert_passes, gated_ffn,
                             head_logits, linear, minimize, rms_norm,
                             router_biases, synthetic_pretrain_batch)
from .bert import fused_multihead_attention

__all__ = ["ling3_flash_config", "build_ling3_flash_pretrain_program",
           "attention_sites", "expert_passes", "router_biases",
           "head_logits", "synthetic_pretrain_batch"]


def layer_types(layers_, group_size):
    """Layer i is latent attention where (i + 1) % ``group_size`` is 0,
    Kimi delta attention otherwise (``layer_group_size``)."""
    return ["mla" if (i + 1) % group_size == 0 else "kda"
            for i in range(layers_)]


def ling3_flash_config():
    """The published sizes (config.json), under this program's names."""
    return dict(
        vocab_size=157184, hidden=2560, heads=32, kv_latent=512,
        nope_dim=128, rope_dim=64, v_dim=128, rope_theta=6000000.0,
        layer_types=layer_types(42, 6), mlp_types=["dense"] * 2
        + ["sparse"] * 40, mlp_width=6144, kda_head_dim=128, conv_kernel=4,
        kda_lower_bound=-5.0, num_experts=512, experts_per_tok=8,
        n_group=8, topk_group=4, expert_width=768, shared_width=768,
        routed_scale=2.5, eps=1e-6,
        # one rank's share and the training assumptions (not in the
        # source): DeepSeek-V3 §4.2's bias step, the delta rule's chunk
        experts_held=512, expert_start=0, init_std=0.02,
        bias_update_rate=0.001, kda_chunk=64)


def head_gate(o, x, heads, d, prefix, cfg):
    """o [B, S, heads * d] times sigmoid(x W_g), one gate a token and head
    (``gated_attention_proj_granularity_type`` head_wise)."""
    gate = layers.sigmoid(linear(x, heads, prefix + "w_g", cfg))
    o = layers.elementwise_mul(layers.reshape(o, [0, 0, heads, d]),
                               layers.unsqueeze(gate, [3]))
    return layers.reshape(o, [0, 0, heads * d])


def kimi_delta_attention(x, prefix, cfg):
    """[q, k, v, a] = x W_qkva; q, k, v through a causal depthwise
    convolution and SiLU; b = x W_b (a head each); the delta rule with a
    decay a key channel (``layers.kda``); one RMSNorm over all the heads'
    channels, the head gate, W_o."""
    h, d = cfg["heads"], cfg["kda_head_dim"]
    width = h * d
    qkva = linear(x, 4 * width, prefix + "w_qkva", cfg)
    qkv, a = layers.split(qkva, [3 * width, width], dim=-1)
    # PyTorch's Conv1d default for a fan-in of conv_kernel taps
    bound = 1.0 / math.sqrt(cfg["conv_kernel"])
    qkv = layers.swish(layers.causal_conv1d(
        qkv, cfg["conv_kernel"],
        param_attr=attr(prefix + "conv_w", cfg, Uniform(-bound, bound))))
    q, k, v = layers.split(qkv, 3, dim=-1)
    b = linear(x, h, prefix + "w_b", cfg)
    o = layers.kda(
        q, k, v, a, b, cfg["kda_lower_bound"], chunk_size=cfg["kda_chunk"],
        # A = exp(A_log) from U(1, 16) in the release; its log from
        # U(0, ln 16) here (PERF.md §4, `assumed`)
        a_log_attr=attr(prefix + "a_log", cfg, Uniform(0.0, math.log(16.0))),
        dt_bias_attr=ParamAttr(name=prefix + "dt_bias",
                               initializer=Constant(0.0)))
    o = head_gate(rms_norm(o, prefix + "norm", cfg), x, h, d, prefix, cfg)
    return linear(o, cfg["hidden"], prefix + "w_o", cfg)


def latent_attention(x, prefix, cfg):
    """Q = x W_q (no query latent: ``latent_qkv`` reads the normed layer
    input itself); [c_KV ; k_R] = x W_kv_a, c_KV = N(c_KV); the heads
    from ``layers.latent_qkv`` with rotate-half rotary; causal attention
    at D = nope + rope over V of v_dim, the head gate, W_o."""
    heads, rope = cfg["heads"], cfg["rope_dim"]
    c_kv, k_rope = layers.split(
        linear(x, cfg["kv_latent"] + rope, prefix + "w_kv_a", cfg),
        [cfg["kv_latent"], rope], dim=-1)
    c_kv = rms_norm(c_kv, prefix + "kv_a_norm", cfg)
    q, k, v = layers.latent_qkv(
        x, c_kv, k_rope, heads, cfg["nope_dim"], rope, cfg["v_dim"],
        cfg["rope_theta"], q_attr=attr(prefix + "w_q", cfg),
        kv_attr=attr(prefix + "w_kv_b", cfg), rope_interleave=False)
    o = fused_multihead_attention(q, k, v, heads, causal=True,
                                  n_v_head=heads)
    o = head_gate(o, x, heads, cfg["v_dim"], prefix, cfg)
    return linear(o, cfg["hidden"], prefix + "w_o", cfg)


def sparse_moe(x, prefix, cfg):
    """The held experts' part under the grouped, biased sigmoid router +
    the shared expert (no gate on it, no auxiliary loss: the source has
    a key for neither)."""
    idx, weight, _ = layers.moe_router(
        x, cfg["num_experts"], cfg["experts_per_tok"], scoring="sigmoid",
        scale=cfg["routed_scale"], param_attr=attr(prefix + "w_router", cfg),
        bias_update_rate=cfg["bias_update_rate"],
        bias_name=prefix + "router_bias", n_group=cfg["n_group"],
        topk_group=cfg["topk_group"])
    routed = layers.moe_expert_ffn(
        x, idx, weight, cfg["experts_held"], cfg["expert_width"],
        expert_start=cfg["expert_start"], num_experts=cfg["num_experts"],
        gate_up_attr=attr(prefix + "w_gate_up", cfg),
        down_attr=attr(prefix + "w_down", cfg))
    return layers.elementwise_add(
        routed, gated_ffn(x, cfg["shared_width"], prefix + "shared_", cfg))


def decoder_layer(x, i, cfg):
    prefix = f"layers.{i}."
    mixer = latent_attention if cfg["layer_types"][i] == "mla" \
        else kimi_delta_attention
    h = rms_norm(x, prefix + "input_norm", cfg)
    x = layers.elementwise_add(x, mixer(h, prefix + "attn.", cfg))
    h = rms_norm(x, prefix + "post_norm", cfg)
    if cfg["mlp_types"][i] == "dense":
        y = gated_ffn(h, cfg["mlp_width"], prefix + "mlp.", cfg)
    else:
        y = sparse_moe(h, prefix + "moe.", cfg)
    return layers.elementwise_add(x, y)


def build_ling3_flash_pretrain_program(cfg=None, seq_len=4096, lr=1e-4,
                                       recompute=True):
    """Next-token pretraining step over ``len(cfg["mlp_types"])`` layers
    (their mixers ``cfg["layer_types"]``, read to that length). Feeds:
    ``ids`` [B, S] and ``labels`` [B, S, 1] (the ids shifted by one),
    int64. Trained and fetched: the cross entropy averaged over the
    positions. ``recompute``: one RecomputeOptimizer checkpoint at the
    embedding's output and at every decoder layer's, so that a layer's
    internals live only while its backward runs. -> (main, startup,
    feeds, fetches)."""
    cfg = cfg or ling3_flash_config()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = fluid.data("ids", shape=[seq_len], dtype="int64")
        labels = fluid.data("labels", shape=[seq_len, 1], dtype="int64")
        x = layers.embedding(ids, [cfg["vocab_size"], cfg["hidden"]],
                             param_attr=attr("embed_tokens", cfg))
        checkpoints = [x]
        for i in range(len(cfg["mlp_types"])):
            x = decoder_layer(x, i, cfg)
            checkpoints.append(x)
        logits = linear(rms_norm(x, "final_norm", cfg), cfg["vocab_size"],
                        "lm_head", cfg)
        loss = layers.mean(layers.softmax_with_cross_entropy(logits, labels))
        minimize(loss, lr, recompute, checkpoints)
    return main, startup, [ids, labels], [loss]

