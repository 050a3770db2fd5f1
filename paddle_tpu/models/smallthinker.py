"""SmallThinker-21BA3B as a Fluid program: a decoder LM whose every
feed-forward is a sparse expert layer (64 ReLU-gated experts 768 wide,
six a token, no shared expert, no dense layer) and whose router reads
the LAYER'S INPUT, before the input norm and before attention, while
the experts read the post-attention normed state; 28 query heads over 4
KV heads of 128, three layers of four under a sliding window of 4096
with plain rotary on the whole head, the fourth causal in full with no
position embedding at all; RMSNorm, untied embedding and head. Source:
https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct
(config.json; SmallThinker, arXiv:2507.20984); the layer equations and
each departure from the release are written out in
benchmark/configs/smallthinker_21b_a3b_reference.py, the plain float32
reference the tests and ``chip_smoke.py`` hold this program to.

The expert layer is one expert-parallel rank's: it routes over all
``num_experts``, holds ``experts_held`` of them from ``expert_start`` and
adds their part alone (ops/decoder_ops.moe_expert_ffn); on one chip it
runs without the exchange. Parameter names are the reference's:
``layers.<i>.attn.w_q|w_k|w_v|w_o``, ``layers.<i>.moe.w_router|w_gate_up|
w_down``, ``layers.<i>.input_norm`` / ``post_norm``.
"""
from __future__ import annotations

from .. import fluid
from ..fluid import layers
from ..fluid.initializer import Normal
from ._decoder_parts import (attention_sites, attr, expert_passes, linear,
                             minimize, rms_norm, synthetic_pretrain_batch)
from .bert import fused_multihead_attention

__all__ = ["smallthinker_config", "build_smallthinker_pretrain_program",
           "attention_sites", "expert_passes", "synthetic_pretrain_batch"]


def smallthinker_config():
    """The published sizes (config.json), under this program's names."""
    return dict(
        vocab_size=151936, hidden=2560, heads=28, kv_heads=4, head_dim=128,
        # a layer's entry of `rope_layout` / `sliding_window_layout`
        rope_layout=[0, 1, 1, 1] * 13, window_layout=[0, 1, 1, 1] * 13,
        window=4096, rope_theta=1500000.0, num_experts=64, experts_per_tok=6,
        expert_width=768, eps=1e-6,
        # one rank's share and the training assumptions (not in the source):
        # the embedding from normal(0, 1), every other matrix from 0.02. The
        # router reads the stream UN-NORMED, so the stream's own scale
        # matters: under a 0.02 embedding (norm 1 a token) the attention
        # blocks' outputs (normed inputs, norm ~50) are 5-50 x the tokens'
        # own part, uniform random tokens average to one common vector, and
        # every token of a batch picks the same experts
        experts_held=64, expert_start=0, init_std=0.02, embed_init_std=1.0)


def attention(x, prefix, cfg, rotary, windowed):
    """Grouped-query attention, causal: ``rotary`` turns queries and keys
    by their position (the whole head), ``windowed`` keeps a query's last
    ``cfg["window"]`` keys; a layer with neither sees every earlier key
    and no position."""
    heads, hkv, d = cfg["heads"], cfg["kv_heads"], cfg["head_dim"]
    q = linear(x, heads * d, prefix + "w_q", cfg)
    k = linear(x, hkv * d, prefix + "w_k", cfg)
    v = linear(x, hkv * d, prefix + "w_v", cfg)
    if rotary:
        q, k = (layers.rotary_embedding(t, n, d, cfg["rope_theta"])
                for t, n in ((q, heads), (k, hkv)))
    o = fused_multihead_attention(
        q, k, v, heads, causal=True, n_kv_head=hkv,
        window=cfg["window"] if windowed else 0)
    return linear(o, cfg["hidden"], prefix + "w_o", cfg)


def decoder_layer(x, i, cfg):
    """The router scores the layer's INPUT ``x``, un-normed; its choice
    crosses the attention block to the experts, which read the
    post-attention normed state."""
    prefix = f"layers.{i}."
    idx, weight, _ = layers.moe_router(
        x, cfg["num_experts"], cfg["experts_per_tok"], scoring="softmax",
        param_attr=attr(prefix + "moe.w_router", cfg))
    h = rms_norm(x, prefix + "input_norm", cfg)
    x = layers.elementwise_add(x, attention(
        h, prefix + "attn.", cfg, cfg["rope_layout"][i],
        cfg["window_layout"][i]))
    g = rms_norm(x, prefix + "post_norm", cfg)
    y = layers.moe_expert_ffn(
        g, idx, weight, cfg["experts_held"], cfg["expert_width"],
        expert_start=cfg["expert_start"], num_experts=cfg["num_experts"],
        gate_up_attr=attr(prefix + "moe.w_gate_up", cfg),
        down_attr=attr(prefix + "moe.w_down", cfg), activation="relu")
    return layers.elementwise_add(x, y)


def build_smallthinker_pretrain_program(cfg=None, seq_len=16384, lr=1e-4,
                                        recompute=True):
    """Next-token pretraining step over ``len(cfg["rope_layout"])``
    layers. Feeds: ``ids`` [B, S] int64 and ``labels`` [B, S, 1] int64
    (the ids shifted by one); the fetched and trained loss is the cross
    entropy averaged over the positions. ``recompute``: one
    RecomputeOptimizer checkpoint at the embedding's and at every
    decoder layer's output, so that a layer's internals live only while
    its backward runs. -> (main, startup, feeds, fetches)."""
    cfg = cfg or smallthinker_config()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = fluid.data("ids", shape=[seq_len], dtype="int64")
        labels = fluid.data("labels", shape=[seq_len, 1], dtype="int64")
        x = layers.embedding(ids, [cfg["vocab_size"], cfg["hidden"]],
                             param_attr=attr(
                                 "embed_tokens", cfg,
                                 Normal(0.0, cfg["embed_init_std"])))
        checkpoints = [x]
        for i in range(len(cfg["rope_layout"])):
            x = decoder_layer(x, i, cfg)
            checkpoints.append(x)
        x = rms_norm(x, "final_norm", cfg)
        logits = linear(x, cfg["vocab_size"], "lm_head", cfg)
        loss = layers.mean(layers.softmax_with_cross_entropy(logits, labels))
        minimize(loss, lr, recompute, checkpoints)
    return main, startup, [ids, labels], [loss]
