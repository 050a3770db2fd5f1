"""Qwen3-Next-80B-A3B as a Fluid program: a decoder LM whose layers mix
by Gated DeltaNet (three of each four) or gated grouped-query attention
(the fourth), each followed by a sparse-expert FFN with a shared expert.
Source: https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct
(config.json); the layer equations and each departure from the release
are written out in benchmark/configs/qwen3_next_80b_a3b_reference.py,
the plain float32 reference the tests and `chip_smoke.py` hold this
program to.

The expert layer is one expert-parallel rank's: it routes over all
``num_experts``, holds ``experts_held`` of them from ``expert_start`` and
adds their part alone (ops/decoder_ops.moe_expert_ffn); on one chip it
runs without the exchange. Parameter names are the reference's:
``layers.<i>.gdn.*``, ``layers.<i>.attn.*``, ``layers.<i>.moe.*``.
"""
from __future__ import annotations

import math

from .. import fluid
from ..fluid import layers
from ..fluid.initializer import Uniform
from ..fluid.param_attr import ParamAttr
from ._decoder_parts import (attr as _attr, expert_passes, linear as _linear,
                             minimize, rms_norm as _norm,
                             synthetic_pretrain_batch)
from .bert import fused_multihead_attention

__all__ = ["qwen3_next_config", "build_qwen3_next_pretrain_program",
           "expert_passes", "synthetic_pretrain_batch"]


def qwen3_next_config():
    """The published sizes (config.json), under this program's names."""
    return dict(
        vocab_size=151936, hidden=2048, layers=48, full_attention_interval=4,
        heads=16, kv_heads=2, head_dim=256, partial_rotary_factor=0.25,
        rope_theta=1e7, linear_key_heads=16, linear_value_heads=32,
        linear_key_dim=128, linear_value_dim=128, conv_kernel=4,
        num_experts=512, experts_per_tok=10, expert_width=512,
        shared_width=512, eps=1e-6,
        # one rank's share and the training assumptions (not in the source)
        experts_held=512, expert_start=0, aux_coef=0.001, init_std=0.02)


def gated_delta_net(x, prefix, cfg):
    hk, hv = cfg["linear_key_heads"], cfg["linear_value_heads"]
    key, value = hk * cfg["linear_key_dim"], hv * cfg["linear_value_dim"]
    qkvz = _linear(x, 2 * key + 2 * value, prefix + "w_qkvz", cfg)
    ba = _linear(x, 2 * hv, prefix + "w_ba", cfg)
    qkv, z = layers.split(qkvz, [2 * key + value, value], dim=-1)
    # PyTorch's Conv1d default for a fan-in of conv_kernel taps
    bound = 1.0 / math.sqrt(cfg["conv_kernel"])
    qkv = layers.swish(layers.causal_conv1d(
        qkv, cfg["conv_kernel"],
        param_attr=_attr(prefix + "conv_w", cfg, Uniform(-bound, bound))))
    q, k, v = layers.split(qkv, [key, key, value], dim=-1)
    b, a = layers.split(ba, [hv, hv], dim=-1)
    o = layers.gated_delta_rule(
        q, k, v, a, b, hk, hv,
        # the release draws A from U(0, 16) and keeps its log
        a_log_attr=_attr(prefix + "a_log", cfg, Uniform(0.0, math.log(16.0))),
        dt_bias_attr=ParamAttr(name=prefix + "dt_bias"))
    o = _norm(o, prefix + "norm", cfg, group_size=cfg["linear_value_dim"],
              gate=z)
    return _linear(o, cfg["hidden"], prefix + "w_o", cfg)


def gated_attention(x, prefix, cfg):
    h, hkv, d = cfg["heads"], cfg["kv_heads"], cfg["head_dim"]
    qg = layers.reshape(_linear(x, h * 2 * d, prefix + "w_q", cfg),
                        [0, 0, h, 2 * d])
    q, gate = (layers.reshape(t, [0, 0, h * d])
               for t in layers.split(qg, 2, dim=-1))
    k = _linear(x, hkv * d, prefix + "w_k", cfg)
    v = _linear(x, hkv * d, prefix + "w_v", cfg)
    rotary_dim = int(d * cfg["partial_rotary_factor"])
    q = layers.rotary_embedding(
        _norm(q, prefix + "q_norm", cfg, group_size=d, zero_centered=True),
        h, rotary_dim, cfg["rope_theta"])
    k = layers.rotary_embedding(
        _norm(k, prefix + "k_norm", cfg, group_size=d, zero_centered=True),
        hkv, rotary_dim, cfg["rope_theta"])
    o = fused_multihead_attention(q, k, v, h, causal=True, n_kv_head=hkv)
    o = layers.elementwise_mul(o, layers.sigmoid(gate))
    return _linear(o, cfg["hidden"], prefix + "w_o", cfg)


def sparse_moe(x, prefix, cfg):
    """(the held experts' part + the shared expert, the auxiliary loss)."""
    idx, weight, aux = layers.moe_router(
        x, cfg["num_experts"], cfg["experts_per_tok"],
        param_attr=_attr(prefix + "w_router", cfg))
    routed = layers.moe_expert_ffn(
        x, idx, weight, cfg["experts_held"], cfg["expert_width"],
        expert_start=cfg["expert_start"], num_experts=cfg["num_experts"],
        gate_up_attr=_attr(prefix + "w_gate_up", cfg),
        down_attr=_attr(prefix + "w_down", cfg))
    shared = layers.elementwise_mul(
        layers.swish(_linear(x, cfg["shared_width"],
                             prefix + "shared_w_gate", cfg)),
        _linear(x, cfg["shared_width"], prefix + "shared_w_up", cfg))
    shared = _linear(shared, cfg["hidden"], prefix + "shared_w_down", cfg)
    gate = layers.sigmoid(_linear(x, 1, prefix + "shared_gate", cfg))
    return layers.elementwise_add(
        routed, layers.elementwise_mul(shared, gate)), aux


def decoder_layer(x, i, cfg):
    prefix = f"layers.{i}."
    full = (i + 1) % cfg["full_attention_interval"] == 0
    h = _norm(x, prefix + "input_norm", cfg, zero_centered=True)
    x = layers.elementwise_add(
        x, gated_attention(h, prefix + "attn.", cfg) if full
        else gated_delta_net(h, prefix + "gdn.", cfg))
    h = _norm(x, prefix + "post_norm", cfg, zero_centered=True)
    y, aux = sparse_moe(h, prefix + "moe.", cfg)
    return layers.elementwise_add(x, y), aux


def build_qwen3_next_pretrain_program(cfg=None, seq_len=4096, lr=1e-4,
                                      recompute=True):
    """Next-token pretraining step. Feeds: ``ids`` [B, S] int64 and
    ``labels`` [B, S, 1] int64 (the ids shifted by one). Trained loss:
    the cross entropy averaged over the positions + aux_coef x the
    layers' mean auxiliary loss; the FETCHED loss is the cross entropy
    alone. ``recompute``: one RecomputeOptimizer checkpoint at the
    embedding's and at every decoder layer's output, so that a layer's
    internals live only while its backward runs.
    -> (main, startup, feeds, fetches)."""
    cfg = cfg or qwen3_next_config()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = fluid.data("ids", shape=[seq_len], dtype="int64")
        labels = fluid.data("labels", shape=[seq_len, 1], dtype="int64")
        x = layers.embedding(ids, [cfg["vocab_size"], cfg["hidden"]],
                             param_attr=_attr("embed_tokens", cfg))
        checkpoints, aux = [x], []
        for i in range(cfg["layers"]):
            x, a = decoder_layer(x, i, cfg)
            checkpoints.append(x)
            aux.append(a)
        x = _norm(x, "final_norm", cfg, zero_centered=True)
        logits = _linear(x, cfg["vocab_size"], "lm_head", cfg)
        ce = layers.mean(layers.softmax_with_cross_entropy(logits, labels))
        loss = layers.elementwise_add(
            ce, layers.scale(layers.sums(aux),
                             scale=cfg["aux_coef"] / cfg["layers"]))
        minimize(loss, lr, recompute, checkpoints)
    return main, startup, [ids, labels], [ce]
