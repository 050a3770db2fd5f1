"""Fused attention ops.

``fused_attention_qkv``: the TPU-native fused attention op used by
models/bert.py — Q/K/V [B, S, H·D] → context [B, S, H·D].

``multihead_matmul``: wire-compatible with the reference's fused inference
op (reference: operators/fused/multihead_matmul_op.cu — Input [B,S,3,H,D]
packed QKV + BiasQK additive mask), so reference-transpiled inference
programs run.

Both compute the same attention one of two ways, chosen per call by
``_use_flash`` from the call's mask and sequence lengths: the Pallas
flash kernels (ops/pallas/flash_attention.py) where there are K/V blocks
to stream, ``_dense_attention`` (plain XLA ops) everywhere else. What a
query may see is one descriptor both take, ``flash_attention.Mask``:
causal | window(w) | an additive bias (the kernels: the key-padding form
alone).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .registry import register_op, register_grad_maker, first, out
from .math_ops import mxu_available as _mxu_backend
from .pallas.flash_attention import (
    NEG_INF, Mask, flash_attention, _blocks_of, _use_kernels, grid_steps,
    visited_blocks)

# Longest sequence (queries AND keys) that takes XLA's dense attention
# where the flash kernels could serve the call. 128 is one 128 x 128 tile
# of scores, the kernels' smallest block: at or below it a head's whole
# score tile is ONE block — nothing is streamed, the online softmax has one
# step and the grid is (B·H, 1, 1) — and on the v5e the kernels take 3.5 times
# the dense computation (tools/attention_paths.py; PERF.md §6, PR 27).
# It moves only with that table run again AND a benchmark cell above it:
# dense holds an S×S tensor a head for the backward, the kernels do not.
DENSE_MAX_SEQ = 128


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


def _use_flash(mask, sq, sk):
    """The one choice between the two paths, read by both ops: the
    kernels serve a call they have a form for — ``mask`` with no bias,
    or with the key-padding bias [B, Sk] (``_keypad_bias``) — on a
    backend that runs them (``_use_kernels``), once either length passes
    ``DENSE_MAX_SEQ``. Every other call is ``_dense_attention``'s."""
    return (_use_kernels() and (mask.bias is None or mask.bias.ndim == 2)
            and max(sq, sk) > DENSE_MAX_SEQ)


def _dense_attention(q, k, v, sm_scale, mask=Mask(), dropout_rate=0.0,
                     rng=None):
    """softmax(scale·QKᵀ + mask)·V in XLA's own ops; q, k [B, H, S, D],
    v [B, H, Sk, Dv], result f32 [B, H, Sq, Dv]. ``mask``: a ``Mask`` (a
    bool stands for its causal flag); its bias is the kernels'
    key-padding form [B, Sk] or anything broadcastable to
    [B, H, Sq, Sk]. The one dense computation: the ops' own path, what
    ``flash_attention`` runs on a backend without the kernels, and what
    the kernels' tests compare with.

    The flash kernels' f32-accumulation contract: bf16 MXU tiles
    accumulate in f32 (preferred_element_type), so the softmax
    statistics see f32 scores — NOT scores rounded to bf16 by a
    bf16-output dot (r5 advisor finding: the two paths diverged for the
    same program) — and the probabilities are cast to the operands'
    dtype for P·V. Causal and window masking are top-left aligned (row
    t sees keys j <= t, under a window w also j > t - w). Dropout draws
    its mask from ``rng`` (the kernels hash theirs in-kernel: another
    stream of the same distribution). A row whose keys are ALL masked
    by a key-padding bias [B, Sk] gives zeros, as in the kernels; by a
    finite bias of another shape it attends uniformly."""
    mask = Mask.of(mask)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    bias = mask.bias
    keypad = bias is not None and bias.ndim == 2
    if bias is not None:
        bias = bias.astype(jnp.float32)
        s = s + (jnp.maximum(bias, NEG_INF)[:, None, None, :] if keypad
                 else bias)
    if mask.causal or mask.window:
        idx_q = jnp.arange(q.shape[2])[:, None]
        idx_k = jnp.arange(k.shape[2])[None, :]
        seen = idx_q >= idx_k
        if mask.window:
            seen = seen & (idx_k > idx_q - mask.window)
        s = jnp.where(seen, s, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s, axis=-1)
    if keypad:
        dead = jnp.max(s, axis=-1, keepdims=True) <= NEG_INF * 0.5
        p = jnp.where(dead, 0.0, p)
    p = p.astype(q.dtype)
    if dropout_rate > 0.0:
        keep = jax.random.bernoulli(rng, 1.0 - dropout_rate, p.shape)
        p = jnp.where(keep, p / (1.0 - dropout_rate), 0.0).astype(p.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v,
                      preferred_element_type=jnp.float32)


def _split_heads(x, n_head):
    b, s, hd = x.shape
    d = hd // n_head
    return jnp.transpose(x.reshape(b, s, n_head, d), (0, 2, 1, 3))


def _merge_heads(x):
    b, h, s, d = x.shape
    return jnp.transpose(x, (0, 2, 1, 3)).reshape(b, s, h * d)


@register_op("fused_attention_qkv", inputs=("Q", "K", "V", "Bias"),
             diff_inputs=("Q", "K", "V"), needs_rng=True,
             attr_defaults={"num_heads": 1, "num_kv_heads": 0,
                            "num_v_heads": 0, "dropout_rate": 0.0,
                            "causal": False, "window": 0, "site": ""})
def _fused_attention_qkv(ins, attrs):
    """Optional Bias: additive attention mask broadcastable to
    [B, H, Sq, Sk] (e.g. padding mask [B, 1, 1, Sk] with -inf/0).
    Grouped queries: with ``num_kv_heads`` (a divisor of ``num_heads``)
    K and V are [B, S, Hkv·D] and each of their heads serves
    num_heads / num_kv_heads consecutive query heads; with
    ``num_v_heads`` V is [B, S, Hv·Dv] with heads of its own count and
    width (differential attention: two key heads share one value head
    twice as wide) and Out is [B, S, H·Dv]. K and V may come from
    another layer (cross-attention). ``window`` w > 0: query t sees keys
    t - w < j <= t (causal with it).

    Dispatch (``_use_flash``): above ``DENSE_MAX_SEQ`` the Pallas flash
    kernels serve the no-bias case AND the exact key-padding bias form
    [B, 1, 1, Sk] (in-kernel), with attention dropout INSIDE the kernel
    (mask regenerated in the backward, seeded per step from the executor
    rng). ``_dense_attention`` serves every other bias shape, every call
    whose score tile fits one kernel block, and a backend without the
    kernels. Causal masking is TOP-LEFT aligned (query i sees keys <= i)
    on both paths. On the kernels' path the gauges
    ``attn_kv_blocks_per_step`` and ``attn_grid_steps_per_step`` are set;
    either way ``attn_query_heads``, ``attn_window``, ``attn_kv_repeat``."""
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
    h_kv = attrs.get("num_kv_heads", 0) or h
    h_v = attrs.get("num_v_heads", 0) or h_kv
    qh, kh, vh = _split_heads(q, h), _split_heads(k, h_kv), \
        _split_heads(v, h_v)
    if h_kv != h:
        kh = jnp.repeat(kh, h // h_kv, axis=1)
    if h_v != h:
        vh = jnp.repeat(vh, h // h_v, axis=1)
    drop = float(attrs.get("dropout_rate", 0.0) or 0.0)
    kp_bias = _keypad_bias(bias, qh, kh)
    mask = Mask(attrs.get("causal", False), attrs.get("window", 0),
                bias if kp_bias is None else kp_bias)
    sq, sk = qh.shape[2], kh.shape[2]
    if _use_flash(mask, sq, sk):
        seed = None
        if drop > 0.0:
            seed = jax.random.randint(attrs["_rng"], (1,), 0,
                                      2 ** 31 - 1, dtype=jnp.int32)
        o = flash_attention(qh, kh, vh, sm_scale, mask, dropout_rate=drop,
                            dropout_seed=seed)
        from ..fluid import telemetry
        blocks = _blocks_of("flash_fwd", qh, kh, vh, mask)
        site, calls = attrs.get("site", ""), qh.shape[0] * h
        telemetry.set_site_gauge(
            "attn_kv_blocks_per_step",
            "(Q block, K block) pairs whose scores the forward flash "
            "kernel computes a step, all heads and sequences: the pairs "
            "its mask keeps, at the blocks chosen for the call", site,
            calls * visited_blocks(sq, sk, *blocks, mask))
        telemetry.set_site_gauge(
            "attn_grid_steps_per_step",
            "steps of the forward flash kernel's grid a step, all heads "
            "and sequences: Q blocks x the K axis's extent, the steps a "
            "causal mask skips included", site,
            calls * grid_steps(sq, sk, *blocks, mask))
    else:
        o = _dense_attention(qh, kh, vh, sm_scale, mask._replace(bias=bias),
                             drop, attrs.get("_rng"))
    # set down here: a Pallas kernel's Python call stack is in its
    # compile-cache key, so nothing is added above the kernels' call
    from ..fluid import telemetry as _telemetry
    _telemetry.set_site_gauge(
        "attn_query_heads",
        "query heads of the attention op, whichever path it takes: a "
        "model's layers may differ in it", attrs.get("site", ""), h)
    _telemetry.set_site_gauge(
        "attn_window",
        "keys a query of the attention op sees under its sliding window, "
        "0 where it has none", attrs.get("site", ""), mask.window)
    _telemetry.set_site_gauge(
        "attn_kv_repeat",
        "query heads that read one key head of the attention op (1: no "
        "grouped queries)", attrs.get("site", ""), h // h_kv)
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
    ([B,S,3,H,D] / [B,S,3HD] without W) are also accepted.

    Dispatch is ``fused_attention_qkv``'s (``_use_flash``): the flash
    kernels above ``DENSE_MAX_SEQ`` for no BiasQK or the exact
    key-padding form [B,1,1,Sk] (the common BERT inference padding
    mask); ``_dense_attention`` for short sequences and generic
    [B,H,Sq,Sk] biases."""
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
    kp_bias = _keypad_bias(bias_qk, q, k)
    mask = Mask(bias=bias_qk if kp_bias is None else kp_bias)
    if _use_flash(mask, q.shape[2], k.shape[2]):
        o = flash_attention(q, k, v, alpha, mask)
    else:
        o = _dense_attention(q, k, v, alpha,
                             Mask(bias=bias_qk)).astype(q.dtype)
    return out(Out=_merge_heads(o))
