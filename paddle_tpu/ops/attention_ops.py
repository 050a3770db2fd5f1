"""Fused attention ops.

``fused_attention_qkv``: the TPU-native fused attention op used by
models/bert.py — Q/K/V [B, S, H·D] → context [B, S, H·D], dispatching to
the Pallas flash-attention kernel on TPU.

``multihead_matmul``: wire-compatible with the reference's fused inference
op (reference: operators/fused/multihead_matmul_op.cu — Input [B,S,3,H,D]
packed QKV + BiasQK additive mask), so reference-transpiled inference
programs run.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .registry import register_op, register_grad_maker, first, out
from .math_ops import mxu_available as _mxu_backend
from .pallas.flash_attention import flash_attention, _use_kernels


def _keypad_bias(bias, q, k):
    """[B, Sk] view of ``bias`` iff it is EXACTLY the key-padding form
    [B, 1, 1, Sk] (else None). A merely broadcastable bias (e.g.
    [B,1,1,1] or [1,1,1,Sk]) must NOT qualify — the kernel's (1, blk_k)
    bias block indexes the real B and Sk extents. q, k: [B, H, S, D]."""
    if bias is not None and bias.ndim == 4 and bias.shape[1] == 1 \
            and bias.shape[2] == 1 and bias.shape[0] == q.shape[0] \
            and bias.shape[3] == k.shape[2]:
        return bias.reshape(bias.shape[0], bias.shape[3])
    return None


def _split_heads(x, n_head):
    b, s, hd = x.shape
    d = hd // n_head
    return jnp.transpose(x.reshape(b, s, n_head, d), (0, 2, 1, 3))


def _merge_heads(x):
    b, h, s, d = x.shape
    return jnp.transpose(x, (0, 2, 1, 3)).reshape(b, s, h * d)


@register_op("fused_attention_qkv", inputs=("Q", "K", "V", "Bias"),
             diff_inputs=("Q", "K", "V"), needs_rng=True,
             attr_defaults={"num_heads": 1, "dropout_rate": 0.0,
                            "causal": False})
def _fused_attention_qkv(ins, attrs):
    """Optional Bias: additive attention mask broadcastable to
    [B, H, Sq, Sk] (e.g. padding mask [B, 1, 1, Sk] with -inf/0).

    Dispatch: the Pallas flash kernel serves the no-bias case AND the
    exact key-padding bias form [B, 1, 1, Sk] (in-kernel); attention
    dropout runs INSIDE the kernel (mask regenerated in the backward,
    seeded per step from the executor rng). The einsum path (XLA fuses
    it) serves every other bias shape and shapes the kernel doesn't
    cover. Causal masking is TOP-LEFT aligned (query i sees keys <= i)
    on both paths."""
    q = first(ins, "Q")
    k = first(ins, "K")
    v = first(ins, "V")
    bias = first(ins, "Bias")
    h = attrs.get("num_heads", 1)
    d = q.shape[-1] // h
    sm_scale = 1.0 / math.sqrt(d)
    out_dtype = q.dtype
    from ..fluid import core as _core
    if _core.globals_["FLAGS_use_bf16_matmul"] and q.dtype == jnp.float32 \
            and _mxu_backend():
        # MXU-native attention (same contract as _mm in math_ops): bf16
        # QK^T/PV matmuls — softmax statistics stay f32 inside both the
        # flash kernel and the einsum path; output restored to f32
        q, k, v = (t.astype(jnp.bfloat16) for t in (q, k, v))
    qh, kh, vh = (_split_heads(t, h) for t in (q, k, v))
    causal = attrs.get("causal", False)
    drop = float(attrs.get("dropout_rate", 0.0) or 0.0)
    kp_bias = _keypad_bias(bias, qh, kh)
    flash_can = _use_kernels() and (bias is None or kp_bias is not None)
    if (bias is None and drop == 0.0) or flash_can:
        seed = None
        if drop > 0.0:
            seed = jax.random.randint(attrs["_rng"], (1,), 0,
                                      2 ** 31 - 1, dtype=jnp.int32)
        o = flash_attention(qh, kh, vh, sm_scale, causal,
                            dropout_rate=drop, dropout_seed=seed,
                            bias=kp_bias if flash_can else None)
    else:
        # f32-accumulation contract shared with the flash kernel: bf16
        # MXU tiles accumulate in f32 (preferred_element_type), so the
        # softmax statistics see f32 scores — NOT scores rounded to bf16
        # by a bf16-output dot. Without this the two dispatch paths
        # diverge numerically for the same program depending on bias
        # shape (r5 advisor finding).
        s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh,
                       preferred_element_type=jnp.float32) * sm_scale
        if bias is not None:
            s = s + bias.astype(jnp.float32)
        if causal:
            S, Sk = qh.shape[2], kh.shape[2]
            idx_q = jnp.arange(S)[:, None]
            idx_k = jnp.arange(Sk)[None, :]
            s = jnp.where(idx_q >= idx_k, s, jnp.finfo(jnp.float32).min)
        p = jax.nn.softmax(s, axis=-1).astype(qh.dtype)
        if drop > 0.0:
            keep = jax.random.bernoulli(attrs["_rng"], 1.0 - drop, p.shape)
            p = jnp.where(keep, p / (1.0 - drop), 0.0).astype(p.dtype)
        o = jnp.einsum("bhqk,bhkd->bhqd", p, vh,
                       preferred_element_type=jnp.float32)
    return out(Out=_merge_heads(o).astype(out_dtype))


@register_op("multihead_matmul", inputs=("Input", "W", "Bias", "BiasQK"),
             diff_inputs=("Input", "W", "Bias"),
             attr_defaults={"transpose_Q": False, "transpose_K": True,
                            "transpose_V": False, "alpha": 1.0,
                            "head_number": 1})
def _multihead_matmul(ins, attrs):
    """Reference contract (operators/fused/multihead_matmul_op.cc:80 —
    MultiHeadMatMulV2Op): Input is the RAW hidden [B, S, N] with the
    packed projection W [N, 3, H·D] and Bias [3, H·D] (the layout
    multihead_matmul_fuse_pass_v2 packs, ir/multihead_matmul_fuse_pass.cc:470);
    the op does QKV projection + alpha·QKᵀ + BiasQK + softmax + PV + merge
    in one fused computation. Pre-projected packed-QKV inputs
    ([B,S,3,H,D] / [B,S,3HD] without W) are also accepted."""
    x = first(ins, "Input")
    w = first(ins, "W")
    b = first(ins, "Bias")
    bias_qk = first(ins, "BiasQK")
    h = attrs.get("head_number", 1)
    alpha = attrs.get("alpha", 1.0)
    if w is not None and w.ndim >= 3:  # raw hidden + packed projection
        wm = w.reshape(w.shape[0], 3, -1)            # [N, 3, H·D]
        qkv = jnp.einsum("bsn,nch->bsch", x, wm)     # [B, S, 3, H·D]
        if b is not None:
            qkv = qkv + b.reshape(3, -1)
        q = _split_heads(qkv[:, :, 0], h)
        k = _split_heads(qkv[:, :, 1], h)
        v = _split_heads(qkv[:, :, 2], h)
    elif x.ndim == 5:  # [B, S, 3, H, D]
        q = jnp.transpose(x[:, :, 0], (0, 2, 1, 3))
        k = jnp.transpose(x[:, :, 1], (0, 2, 1, 3))
        v = jnp.transpose(x[:, :, 2], (0, 2, 1, 3))
    else:  # [B, S, 3·H·D]
        bsz, s, hd3 = x.shape
        x5 = x.reshape(bsz, s, 3, h, hd3 // (3 * h))
        q = jnp.transpose(x5[:, :, 0], (0, 2, 1, 3))
        k = jnp.transpose(x5[:, :, 1], (0, 2, 1, 3))
        v = jnp.transpose(x5[:, :, 2], (0, 2, 1, 3))
    # Fast path (the reference op IS its fast path — multihead_matmul_op.cu):
    # no bias, or the exact key-padding BiasQK form [B,1,1,Sk] (the common
    # BERT inference padding mask), dispatches to the Pallas flash kernel
    # via its in-kernel bias input. Generic [B,H,Sq,Sk] biases keep the
    # einsum path (XLA fuses it).
    kp_bias = _keypad_bias(bias_qk, q, k)
    if _use_kernels() and (bias_qk is None or kp_bias is not None):
        o = flash_attention(q, k, v, alpha, causal=False, bias=kp_bias)
    else:
        # same f32-accumulation contract as the flash path (see
        # _fused_attention_qkv above)
        s_mat = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                           preferred_element_type=jnp.float32) * alpha
        if bias_qk is not None:
            s_mat = s_mat + bias_qk.astype(jnp.float32)
        p = jax.nn.softmax(s_mat, axis=-1).astype(q.dtype)
        o = jnp.einsum("bhqk,bhkd->bhqd", p, v,
                       preferred_element_type=jnp.float32).astype(q.dtype)
    return out(Out=_merge_heads(o))
