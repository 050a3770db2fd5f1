"""Laguna-XS.2 as a Fluid program: a decoder LM whose layers attend in
full or under a sliding window, with a head count, a rotary embedding
and a mask of the layer's kind (48 query heads, YaRN on half of each
head, causal on a full layer; 64 heads, plain rotary on the whole head,
window 512 on a window layer; 8 KV heads both), a per-head sigmoid gate
on the attention output, a leading dense gated FFN and then sparse
experts under a sigmoid-scored top-k router with one ungated shared
expert; RMSNorm, untied embedding and head. Source:
https://huggingface.co/poolside/Laguna-XS.2 (config.json); the layer
equations and each departure from the release are written out in
benchmark/configs/laguna_xs2_reference.py, the plain float32 reference
the tests and ``chip_smoke.py`` hold this program to.

The expert layer is one expert-parallel rank's: it routes over all
``num_experts``, holds ``experts_held`` of them from ``expert_start`` and
adds their part alone (ops/decoder_ops.moe_expert_ffn); on one chip it
runs without the exchange. Parameter names are the reference's:
``layers.<i>.attn.*``, ``layers.<i>.mlp.*`` (dense) or
``layers.<i>.moe.*`` (sparse), ``layers.<i>.input_norm`` / ``post_norm``.
"""
from __future__ import annotations

from .. import fluid
from ..fluid import layers
from ._decoder_parts import (attention_sites, attr, expert_passes, gated_ffn,
                             linear, minimize, rms_norm,
                             synthetic_pretrain_batch)
from .bert import fused_multihead_attention

__all__ = ["laguna_config", "build_laguna_pretrain_program",
           "attention_sites", "expert_passes", "synthetic_pretrain_batch"]


def laguna_config():
    """The published sizes (config.json), under this program's names."""
    return dict(
        vocab_size=100352, hidden=2048, kv_heads=8, head_dim=128,
        layer_types=["full", "sliding", "sliding", "sliding"] * 10,
        heads_per_layer=[48, 64, 64, 64] * 10,
        mlp_types=["dense"] + ["sparse"] * 39,
        window=512, mlp_width=8192, num_experts=256, experts_per_tok=8,
        expert_width=512, shared_width=512, routed_scale=2.5, eps=1e-6,
        rope={
            # partial_rotary_factor 0.5 of the 128 dims, YaRN
            "full": dict(theta=500000.0, rotary_dim=64,
                         yarn=dict(factor=64.0, original_max_position=4096,
                                   beta_fast=64.0, beta_slow=1.0),
                         cos_sin_scale=1.4158883083359672),
            "sliding": dict(theta=10000.0, rotary_dim=128)},
        # one rank's share and the training assumptions (not in the source)
        experts_held=256, expert_start=0, init_std=0.02)


def gated_attention(x, prefix, cfg, heads, kind):
    """Grouped-query attention of ``heads`` query heads over the
    configuration's KV heads, causal, under the window where ``kind`` is
    "sliding"; one sigmoid gate a token and head on its output."""
    hkv, d = cfg["kv_heads"], cfg["head_dim"]
    q = linear(x, heads * d, prefix + "w_q", cfg)
    k = linear(x, hkv * d, prefix + "w_k", cfg)
    v = linear(x, hkv * d, prefix + "w_v", cfg)
    gate = layers.sigmoid(linear(x, heads, prefix + "w_g", cfg))
    rope = cfg["rope"][kind]
    q, k = (layers.rotary_embedding(
        t, n, rope["rotary_dim"], rope["theta"], yarn=rope.get("yarn"),
        cos_sin_scale=rope.get("cos_sin_scale", 1.0))
        for t, n in ((q, heads), (k, hkv)))
    o = fused_multihead_attention(
        q, k, v, heads, causal=True, n_kv_head=hkv,
        window=cfg["window"] if kind == "sliding" else 0)
    o = layers.elementwise_mul(layers.reshape(o, [0, 0, heads, d]),
                               layers.unsqueeze(gate, [3]))
    return linear(layers.reshape(o, [0, 0, heads * d]), cfg["hidden"],
                  prefix + "w_o", cfg)


def sparse_moe(x, prefix, cfg):
    """The held experts' part under the sigmoid router + the shared
    expert (no gate on it, no auxiliary loss: the source has a key for
    neither)."""
    idx, weight, _ = layers.moe_router(
        x, cfg["num_experts"], cfg["experts_per_tok"], scoring="sigmoid",
        scale=cfg["routed_scale"], param_attr=attr(prefix + "w_router", cfg))
    routed = layers.moe_expert_ffn(
        x, idx, weight, cfg["experts_held"], cfg["expert_width"],
        expert_start=cfg["expert_start"], num_experts=cfg["num_experts"],
        gate_up_attr=attr(prefix + "w_gate_up", cfg),
        down_attr=attr(prefix + "w_down", cfg))
    return layers.elementwise_add(
        routed, gated_ffn(x, cfg["shared_width"], prefix + "shared_", cfg))


def decoder_layer(x, i, cfg):
    prefix = f"layers.{i}."
    h = rms_norm(x, prefix + "input_norm", cfg)
    x = layers.elementwise_add(x, gated_attention(
        h, prefix + "attn.", cfg, cfg["heads_per_layer"][i],
        cfg["layer_types"][i]))
    h = rms_norm(x, prefix + "post_norm", cfg)
    if cfg["mlp_types"][i] == "dense":
        y = gated_ffn(h, cfg["mlp_width"], prefix + "mlp.", cfg)
    else:
        y = sparse_moe(h, prefix + "moe.", cfg)
    return layers.elementwise_add(x, y)


def build_laguna_pretrain_program(cfg=None, seq_len=8192, lr=1e-4,
                                  recompute=True):
    """Next-token pretraining step over ``len(cfg["layer_types"])``
    layers. Feeds: ``ids`` [B, S] int64 and ``labels`` [B, S, 1] int64
    (the ids shifted by one); the fetched and trained loss is the cross
    entropy averaged over the positions. ``recompute``: one
    RecomputeOptimizer checkpoint at the embedding's and at every
    decoder layer's output, so that a layer's internals live only while
    its backward runs. -> (main, startup, feeds, fetches)."""
    cfg = cfg or laguna_config()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = fluid.data("ids", shape=[seq_len], dtype="int64")
        labels = fluid.data("labels", shape=[seq_len, 1], dtype="int64")
        x = layers.embedding(ids, [cfg["vocab_size"], cfg["hidden"]],
                             param_attr=attr("embed_tokens", cfg))
        checkpoints = [x]
        for i in range(len(cfg["layer_types"])):
            x = decoder_layer(x, i, cfg)
            checkpoints.append(x)
        x = rms_norm(x, "final_norm", cfg)
        logits = linear(x, cfg["vocab_size"], "lm_head", cfg)
        loss = layers.mean(layers.softmax_with_cross_entropy(logits, labels))
        minimize(loss, lr, recompute, checkpoints)
    return main, startup, [ids, labels], [loss]
