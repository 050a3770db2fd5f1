"""Phi-4-mini-flash-reasoning as a Fluid program: the SambaY
decoder-hybrid-decoder (arXiv:2507.06607). A self-decoder of Mamba
layers alternating with differential attention under a sliding window,
one Mamba layer whose scan output is kept as the memory ``m``, one
full-attention layer whose K and V are kept, and a cross-decoder of
Gated Memory Units (which gate ``m``) alternating with cross-attention
(which reads that K, V); every layer followed by a gated MLP; LayerNorm
with bias, no positional encoding, the output head tied to the input
embedding. Source: https://huggingface.co/microsoft/
Phi-4-mini-flash-reasoning (config.json); the layer equations and each
departure from the release are written out in
benchmark/configs/phi4_mini_flash_reference.py, the plain float32
reference the tests and ``chip_smoke.py`` hold this program to.

Parameter names are the reference's: ``layers.<i>.mamba.*``,
``layers.<i>.attn.*``, ``layers.<i>.gmu.*``, ``layers.<i>.cross.*``,
``layers.<i>.mlp.*``, ``layers.<i>.ln1.*`` / ``ln2.*``.
"""
from __future__ import annotations

import math

import numpy as np

from .. import fluid
from ..fluid import layers
from ..fluid.initializer import Normal, NumpyArrayInitializer, Uniform
from ..fluid.param_attr import ParamAttr
from ._decoder_parts import (attention_sites, attr as _attr, gated_ffn,
                             linear as _linear, minimize,
                             synthetic_pretrain_batch)
from .bert import fused_multihead_attention

__all__ = ["phi4_flash_config", "layer_kinds", "lambda_init",
           "build_phi4_flash_pretrain_program", "attention_sites",
           "synthetic_pretrain_batch"]


def phi4_flash_config():
    """The published sizes (config.json), under this program's names,
    and the family's conventions for what it leaves open (``d_inner`` =
    2 x hidden, ``dt_rank`` = hidden / 16, ``d_state`` 16, ``d_conv`` 4)."""
    return dict(
        vocab_size=200064, hidden=2560, heads=40, kv_heads=20, head_dim=64,
        mlp_width=10240, window=512, eps=1e-5, d_inner=5120, d_state=16,
        d_conv=4, dt_rank=160, layer_kinds=layer_kinds(32),
        published_index=list(range(32)), init_std=0.02)


def layer_kinds(n):
    """The release's rule for ``n`` layers (a multiple of 4), at
    ``mb_per_layer`` 2: even layers are the SSM side, odd the attention
    side; the first half mixes by Mamba / window attention, layer n/2 is
    the Mamba whose scan output is the memory, layer n/2 + 1 the full
    attention whose K, V are kept, the rest GMU / cross-attention."""
    kinds = []
    for i in range(n):
        if i % 2 == 0:
            kinds.append("mamba" if i < n // 2 else
                         "mamba_memory" if i == n // 2 else "gmu")
        else:
            kinds.append("sliding" if i < n // 2 else
                         "full" if i == n // 2 + 1 else "cross")
    return kinds


def lambda_init(published_index):
    return 0.8 - 0.6 * math.exp(-0.3 * published_index)


def _layer_norm(x, prefix, cfg):
    return layers.layer_norm(
        x, begin_norm_axis=2, epsilon=cfg["eps"],
        param_attr=ParamAttr(name=prefix + "w"),
        bias_attr=ParamAttr(name=prefix + "b"))


def _dt_bias(cfg):
    """softplus^-1 of a step on a log-uniform grid over [1e-3, 1e-1]
    across the channels (the family draws it at random from that
    range)."""
    dt = np.exp(np.linspace(math.log(1e-3), math.log(1e-1), cfg["d_inner"]))
    return (dt + np.log(-np.expm1(-dt))).astype("float32")


def mamba(x, prefix, cfg):
    """(the mixer's output, the scan's output before the gate)."""
    inner, n, rank = cfg["d_inner"], cfg["d_state"], cfg["dt_rank"]
    uz = _linear(x, 2 * inner, prefix + "w_in", cfg)
    u, z = layers.split(uz, 2, dim=-1)
    # PyTorch's Conv1d default for a fan-in of d_conv taps
    bound = 1.0 / math.sqrt(cfg["d_conv"])
    u = layers.causal_conv1d(
        u, cfg["d_conv"],
        param_attr=_attr(prefix + "conv_w", cfg, Uniform(-bound, bound)))
    conv_b = layers.create_parameter(
        [inner], "float32", attr=_attr(prefix + "conv_b", cfg,
                                       Uniform(-bound, bound)))
    u = layers.swish(layers.elementwise_add(u, conv_b, axis=-1))
    dt, b, c = layers.split(_linear(u, rank + 2 * n, prefix + "w_x", cfg),
                            [rank, n, n], dim=-1)
    dt = _linear(dt, inner, prefix + "w_dt", cfg,
                 initializer=Uniform(-rank ** -0.5, rank ** -0.5))
    a_log = np.tile(np.log(np.arange(1, n + 1, dtype="float32")), (inner, 1))
    y = layers.selective_scan(
        u, dt, b, c,
        a_log_attr=_attr(prefix + "a_log", cfg, NumpyArrayInitializer(a_log)),
        d_attr=ParamAttr(name=prefix + "d"),
        dt_bias_attr=_attr(prefix + "dt_bias", cfg,
                           NumpyArrayInitializer(_dt_bias(cfg))))
    out = _linear(layers.elementwise_mul(y, layers.swish(z)), cfg["hidden"],
                  prefix + "w_out", cfg)
    return out, y


def _differential(q, k, v, prefix, cfg, window, lam_init):
    """Differential attention of queries q [B, S, heads * d] over k
    [B, S, kv_heads * d] and v [B, S, kv_heads / 2 * 2 d] (a pair of key
    heads shares one value head twice as wide): ONE attention op whose
    heads are laid out [group, map, differential head], then the two
    maps' difference, the sub-norm and the output projection."""
    h, hkv, d = cfg["heads"], cfg["kv_heads"], cfg["head_dim"]
    o = fused_multihead_attention(q, k, v, h, causal=True, n_kv_head=hkv,
                                  n_v_head=hkv // 2, window=window)
    o = layers.differential_combine(
        o, hkv // 2, d, lam_init,
        lambda_attrs=[_attr(prefix + n, cfg, Normal(0.0, 0.1)) for n in (
            "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")])
    o = layers.rms_norm(o, group_size=2 * d, epsilon=cfg["eps"],
                        param_attr=ParamAttr(name=prefix + "subln"))
    o = layers.scale(o, scale=1.0 - lam_init)
    return _linear(o, cfg["hidden"], prefix + "w_o", cfg, prefix + "b_o")


def self_attention(x, prefix, cfg, window, lam_init):
    """(the mixer's output, K, V)."""
    h, hkv, d = cfg["heads"], cfg["kv_heads"], cfg["head_dim"]
    qkv = _linear(x, (h + 2 * hkv) * d, prefix + "w_qkv", cfg,
                  prefix + "b_qkv")
    q, k, v = layers.split(qkv, [h * d, hkv * d, hkv * d], dim=-1)
    return _differential(q, k, v, prefix, cfg, window, lam_init), k, v


def cross_attention(x, k, v, prefix, cfg, lam_init):
    q = _linear(x, cfg["heads"] * cfg["head_dim"], prefix + "w_q", cfg,
                prefix + "b_q")
    return _differential(q, k, v, prefix, cfg, 0, lam_init)


def gated_memory_unit(x, m, prefix, cfg):
    gate = layers.swish(_linear(x, cfg["d_inner"], prefix + "w_in", cfg))
    return _linear(layers.elementwise_mul(gate, m), cfg["hidden"],
                   prefix + "w_out", cfg)


def decoder_layer(x, i, shared, cfg):
    """x after layer i; ``shared`` gains what later layers read: "m"
    from the memory layer, "k" and "v" from the full-attention layer."""
    prefix, kind = f"layers.{i}.", cfg["layer_kinds"][i]
    lam_init = lambda_init(cfg["published_index"][i])
    h = _layer_norm(x, prefix + "ln1.", cfg)
    if kind in ("mamba", "mamba_memory"):
        y, m = mamba(h, prefix + "mamba.", cfg)
        if kind == "mamba_memory":
            shared["m"] = m
    elif kind in ("sliding", "full"):
        y, k, v = self_attention(
            h, prefix + "attn.", cfg,
            cfg["window"] if kind == "sliding" else 0, lam_init)
        if kind == "full":
            shared["k"], shared["v"] = k, v
    elif kind == "gmu":
        y = gated_memory_unit(h, shared["m"], prefix + "gmu.", cfg)
    elif kind == "cross":
        y = cross_attention(h, shared["k"], shared["v"], prefix + "cross.",
                            cfg, lam_init)
    else:
        raise ValueError(f"layer kind {kind!r}")
    x = layers.elementwise_add(x, y)
    h = _layer_norm(x, prefix + "ln2.", cfg)
    return layers.elementwise_add(
        x, gated_ffn(h, cfg["mlp_width"], prefix + "mlp.", cfg))


def build_phi4_flash_pretrain_program(cfg=None, seq_len=4096, lr=1e-4,
                                      recompute=True):
    """Next-token pretraining step. Feeds: ``ids`` [B, S] int64 and
    ``labels`` [B, S, 1] int64 (the ids shifted by one); the fetched
    loss is the cross entropy averaged over the positions, over logits
    x E^T with E the input embedding. ``recompute``: one
    RecomputeOptimizer checkpoint at the embedding's and at every
    layer's output, so that a layer's internals live only while its
    backward runs; the memory ``m`` and the kept K, V leave their
    layer's segment as outputs the later layers read, and the lowering
    (fluid/recompute_lowering.py) sums what each reader sends back.
    -> (main, startup, feeds, fetches)."""
    cfg = cfg or phi4_flash_config()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = fluid.data("ids", shape=[seq_len], dtype="int64")
        labels = fluid.data("labels", shape=[seq_len, 1], dtype="int64")
        x = layers.embedding(ids, [cfg["vocab_size"], cfg["hidden"]],
                             param_attr=_attr("embed_tokens", cfg))
        checkpoints, shared = [x], {}
        for i in range(len(cfg["layer_kinds"])):
            x = decoder_layer(x, i, shared, cfg)
            checkpoints.append(x)
        x = _layer_norm(x, "final_norm.", cfg)
        logits = layers.matmul(x, main.global_block().var("embed_tokens"),
                               transpose_y=True)
        loss = layers.mean(layers.softmax_with_cross_entropy(logits, labels))
        minimize(loss, lr, recompute, checkpoints)
    return main, startup, [ids, labels], [loss]
