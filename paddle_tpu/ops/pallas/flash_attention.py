"""Flash attention (Pallas TPU) — replaces the reference's fused transformer
attention kernel (reference: operators/fused/multihead_matmul_op.cu, which
does QK^T→softmax→V with cuBLAS batched GEMMs in one op).

TPU design (FlashAttention-2 style, written for the MXU/VMEM hierarchy):

Forward: grid (B·H, S/blk_q, S/blk_k) with the K dimension innermost —
Pallas TPU executes the innermost grid dimension sequentially, so the
online-softmax state (f32 accumulator, running row-max m, normalizer l)
lives in VMEM scratch and is carried across K blocks. Only one
(blk_q × D) Q tile and one (blk_k × D) K/V tile are resident per step, so
sequence length is NOT bounded by VMEM (the round-1 full-K/V-in-VMEM
S≤2048 restriction is gone); what a step holds is ``_working_set``'s to
say. Score tiles hit the MXU via jnp.dot with
preferred_element_type=f32; softmax runs in f32 on the VPU. The kernel
also emits the log-sum-exp per row, the residual the backward needs.

Backward: two Pallas kernels (the FlashAttention-2 recipe):
  dK/dV: grid (B·H, S/blk_k, S/blk_q) accumulating over Q blocks,
  dQ:    grid (B·H, S/blk_q, S/blk_k) accumulating over K blocks,
both recomputing P = exp(scale·QKᵀ − lse) tile-by-tile from the stored
lse — no O(S²) materialization anywhere. delta = rowsum(dO ∘ O) is a
cheap elementwise+reduce that XLA fuses outside the kernels.

What a query may see is one descriptor, ``Mask`` (causal | window(w) |
key-padding bias), which the kernels, ``flash_attention`` and the dense
computation (ops/attention_ops._dense_attention, also what the CPU
backend runs with the interpreter off, and what tests compare with) all
take. Causal masking is top-left aligned; fully-masked K blocks are
skipped with pl.when (upper-triangular blocks cost no compute). Under a
window the grids themselves are cut: a Q block's K axis runs over the
blocks that hold a key of ``(t - w, t]`` for one of its rows and no
other (``_window_blocks``), so a block outside the window is neither
fetched nor computed, and the edge blocks are masked. V's head may be
wider than Q's and K's (differential attention reads [v_1 | v_2]).

CPU/tests: `interpret_mode(True)` / `interpret_guard()` run the very
same kernels through the Pallas interpreter so the suite exercises the
real kernel, not a fallback. Ragged lengths (S or Sk not divisible by
the block) STAY on the kernel: boundary blocks are handled by in-kernel
bounds masking, with padded tile regions zeroed at load (they are
uninitialized — NaN under the interpreter — and 0·NaN would leak through
the contractions). On a TPU backend `flash_attention()` IS the kernels: a
kernel that does not compile raises, and this module holds no rule about
shapes — a caller that asks for the kernels gets them
(parallel/ring_attention.py, tools/flash_smoke.py, the kernel tests).
Whether a call should ask is the attention ops' to decide
(ops/attention_ops._use_flash): they come here only where there is more
than one 128 x 128 tile of scores to stream, and compute dense attention
themselves at or under that.

Block geometry is chosen a call and a kernel by ``_block_sizes`` from the
call's shape alone (head widths, lengths, window, item size): the pair of
a short candidate list that a two-term cost reckons cheapest (grid steps
x a step's fixed cost + score elements computed x an element's cost,
both measured on the chip: PERF.md §6, PR 33) among those whose working
set (``_working_set``) fits ``VMEM_BUDGET``. The same estimate is the
kernels' ``vmem_limit_bytes`` where it passes Mosaic's default.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # finite mask value: avoids inf-inf → NaN in the rescale
# Mosaic requires the last two dims of every block shape to be divisible
# by (8, 128) or equal to the array dims. Row-statistics arrays (lse,
# delta) therefore carry a broadcast 128-lane minor dimension on the
# wire — the same layout jax's own TPU flash kernel uses for l/m.
LANES = 128

_INTERPRET = False


def interpret_mode(enable: bool):
    """Force the Pallas kernels through the interpreter (CPU testing)."""
    global _INTERPRET
    _INTERPRET = bool(enable)


@contextlib.contextmanager
def interpret_guard():
    global _INTERPRET
    prev = _INTERPRET
    _INTERPRET = True
    try:
        yield
    finally:
        _INTERPRET = prev


class Mask(NamedTuple):
    """What a query row may see, for kernels and dense computation
    alike. ``causal``: keys j <= t (top-left aligned). ``window`` w > 0:
    keys t - w < j <= t (causal with it). ``bias``: an additive bias;
    the kernels take the key-padding form [B, Sk] alone, the dense
    computation also anything broadcastable to [B, H, Sq, Sk]."""
    causal: bool = False
    window: int = 0
    bias: Any = None

    @classmethod
    def of(cls, mask):
        """``mask`` itself, or the causal flag a bool stands for."""
        return mask if isinstance(mask, cls) else cls(causal=bool(mask))


def _inner_span(i, blk_outer, blk_inner, n_inner_blocks, window,
                outer_is_q):
    """(first, last) block of the inner grid axis that causal outer block
    ``i`` shares a visible score with. Queries outside, keys inside: row
    t sees keys j <= t, under a window also j > t - w. Keys outside,
    queries inside: key j is seen by rows t >= j, under a window also
    t < j + w. (``_inner_block`` is the first of them, traced.)"""
    start, end = i * blk_outer, i * blk_outer + blk_outer - 1
    if outer_is_q:
        lo, hi = (max(start - window + 1, 0) if window else 0), end
    else:
        lo, hi = start, (end + window - 1 if window else None)
    last = n_inner_blocks - 1
    return lo // blk_inner, last if hi is None else min(hi // blk_inner, last)


def _window_count(n_outer, n_inner, blk_outer, blk_inner, window,
                  outer_is_q):
    """Under a window, the most blocks of the inner grid axis any block
    of the outer one needs: the extent of that axis of the grid."""
    spans = (_inner_span(i, blk_outer, blk_inner, -(-n_inner // blk_inner),
                         window, outer_is_q)
             for i in range(-(-n_outer // blk_outer)))
    return max(hi - lo + 1 for lo, hi in spans)


def visited_blocks(sq, sk, blk_q, blk_k, mask):
    """(Q block, K block) pairs whose scores the forward kernel computes,
    a head: every pair without a mask, the pairs at or under the diagonal
    when causal, those that hold a key of some row's window under one."""
    nq, nk = -(-sq // blk_q), -(-sk // blk_k)
    if not (mask.causal or mask.window):
        return nq * nk
    spans = (_inner_span(i, blk_q, blk_k, nk, mask.window, True)
             for i in range(nq))
    return sum(hi - lo + 1 for lo, hi in spans)


def _grid(kernel, sq, sk, blk_q, blk_k, window):
    """(blocks of ``kernel``'s outer grid axis, extent of the inner one):
    Q outside and K inside, the other way round for dK/dV; the inner
    extent is every block, or under a window the most any outer block
    needs (``_window_count``)."""
    outer, inner, outer_is_q = (sq, blk_q), (sk, blk_k), True
    if kernel == "flash_bwd_dkv":
        outer, inner, outer_is_q = inner, outer, False
    extent = (_window_count(outer[0], inner[0], outer[1], inner[1], window,
                            outer_is_q)
              if window else -(-inner[0] // inner[1]))
    return -(-outer[0] // outer[1]), extent


def grid_steps(sq, sk, blk_q, blk_k, mask, kernel="flash_fwd"):
    """Steps of ``kernel``'s grid, a head. The steps ``pl.when`` skips
    above a causal diagonal are counted: they are stepped, and their
    blocks fetched."""
    outer, extent = _grid(kernel, sq, sk, blk_q, blk_k, mask.window)
    return outer * extent


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _use_kernels() -> bool:
    """Whether this backend runs the Pallas kernels: a TPU (a kernel that
    does not compile raises), or the CPU through the interpreter (tests);
    the CPU otherwise runs the XLA reference. Which CALLS they serve is
    `ops/attention_ops._use_flash`'s rule, not this module's."""
    return _on_tpu() or _INTERPRET


def _mxu_operand(x):
    """Dot operand in MXU-native dtype: bf16 tiles feed the MXU dots
    directly (f32 accumulation comes from preferred_element_type), which
    runs at full systolic-array rate; anything else upcasts to f32. The
    softmax statistics (m/l/lse/delta) and accumulators stay f32 either
    way."""
    return x if x.dtype == jnp.bfloat16 else x.astype(jnp.float32)


def _mask_cols(s, k_start, blk_q, blk_k, sk_len):
    cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
    return jnp.where(cols < sk_len, s, NEG_INF)


def _zero_pad_rows(t, start, limit):
    """Zero a tile's rows past the true length — padded regions of a
    boundary block are UNINITIALIZED (NaN under the interpreter), and
    0·NaN = NaN would leak through the contractions."""
    rows = start + jax.lax.broadcasted_iota(jnp.int32, t.shape, 0)
    return jnp.where(rows < limit, t, 0.0)


def _valid_rows(q_start, blk_q, blk_k, s_len):
    rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
    return rows < s_len


def _mask_scores(s, q_start, k_start, blk_q, blk_k, window=0):
    rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
    cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
    seen = rows >= cols
    if window:
        seen = seen & (cols > rows - window)
    return jnp.where(seen, s, NEG_INF)


def _inner_block(outer, inner, blk_outer, blk_inner, window, outer_is_q):
    """The inner grid axis's block the kernel is at: ``inner`` itself, or
    under a window the ``inner``-th of the blocks outer block ``outer``
    needs (the index maps' own rule, ``_window_blocks``)."""
    if not window:
        return inner
    lo = outer * blk_outer - (window - 1 if outer_is_q else 0)
    return jnp.maximum(lo, 0) // blk_inner + inner


def _block_runs(q_start, k_start, blk_q, blk_k, window, inner_blk, n_inner):
    """Whether a causal (Q block, K block) pair holds a visible score:
    not above the diagonal, and under a window not wholly before it nor
    past the inner axis's end (where the index map repeats its last
    block)."""
    runs = k_start <= q_start + blk_q - 1
    if window:
        runs = runs & (k_start + blk_k - 1 > q_start - window) \
            & (inner_blk < n_inner)
    return runs


def _keep_mask(seed, bh, q_start, k_start, blk_q, blk_k, rate):
    """Deterministic counter-based dropout mask for one score tile:
    a Wang-style integer mix over (seed, batch·head, absolute row,
    absolute col) — plain VPU integer ops, so the SAME mask regenerates
    in the backward kernels and in the interpreter (the TPU PRNG
    primitives have no CPU interpret rule). Keep probability 1-rate to
    24-bit resolution."""
    rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
    cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
    x = (rows.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
         ^ cols.astype(jnp.uint32) * jnp.uint32(0x85EBCA77)
         ^ (seed.astype(jnp.uint32) + jnp.uint32(0x27D4EB2F)
            * bh.astype(jnp.uint32)))
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    thresh = jnp.uint32(int(rate * float(1 << 24)))
    return ((x & jnp.uint32(0xFFFFFF)) >= thresh)


def keep_mask_reference(seed, bh, rows, cols, rate):
    """Numpy twin of _keep_mask for exact-parity tests."""
    rows = np.asarray(rows, np.uint32)[:, None]
    cols = np.asarray(cols, np.uint32)[None, :]
    x = (rows * np.uint32(0x9E3779B1)
         ^ cols * np.uint32(0x85EBCA77)
         ^ np.uint32((seed + 0x27D4EB2F * bh) & 0xFFFFFFFF))
    x = x ^ (x >> np.uint32(16))
    x = (x * np.uint32(0x7FEB352D)) & np.uint32(0xFFFFFFFF)
    x = x ^ (x >> np.uint32(15))
    x = (x * np.uint32(0x846CA68B)) & np.uint32(0xFFFFFFFF)
    x = x ^ (x >> np.uint32(16))
    thresh = np.uint32(int(rate * float(1 << 24)))
    return (x & np.uint32(0xFFFFFF)) >= thresh


def _with_optional_bias(kernel, n_named, has_bias):
    """Adapter shared by all three pallas_calls: refs arrive as
    (inputs..., outputs..., scratch...); the kernels take bias_ref (or
    None) right after their ``n_named`` data inputs."""
    def _inner(*refs):
        named = refs[:n_named]
        if has_bias:
            return kernel(*named, refs[n_named], *refs[n_named + 1:])
        return kernel(*named, None, *refs[n_named:])
    return _inner


def _append_bias_input(in_specs, args, bias, H, blk_k, k_axis,
                       k_block=lambda i, j: j):
    """Append the key-padding bias input as [B, 1, Sk] (cast once to
    f32) — the middle singleton makes the block's second-to-last dim
    equal to the array dim, which Mosaic accepts for any size.
    ``k_axis``: which grid dimension indexes K blocks (1 for the bwd-kv
    kernel, 2 for fwd/bwd-q, whose K block is ``k_block(i, j)``)."""
    if bias is None:
        return
    if k_axis == 1:
        spec = pl.BlockSpec((1, 1, blk_k), lambda b, j, i: (b // H, 0, j))
    else:
        spec = pl.BlockSpec((1, 1, blk_k),
                            lambda b, i, j: (b // H, 0, k_block(i, j)))
    in_specs.append(spec)
    args.append(bias.astype(jnp.float32).reshape(bias.shape[0], 1,
                                                 bias.shape[-1]))


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------
def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref,
                *, sm_scale, causal, blk_q, blk_k, dropout_rate,
                has_bias, sk_len=0, window=0, n_k_blocks=0):
    bh, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * blk_q
    k_blk = _inner_block(qi, ki, blk_q, blk_k, window, True)
    k_start = k_blk * blk_k

    def _block():
        q = _mxu_operand(q_ref[0])
        k = _mxu_operand(k_ref[0])
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [blk_q, blk_k]
        if has_bias:
            # key-padding bias [B, 1, Sk] broadcast over query rows (the
            # reference BiasQK padding-mask form); clamped so -inf masks
            # can't produce inf-inf → NaN in the rescale
            s = s + jnp.maximum(bias_ref[0], NEG_INF)
        if sk_len:
            # ragged Sk: the last K block is padded — mask the columns
            # past the true length (padded bias/K values are overridden)
            s = _mask_cols(s, k_start, blk_q, blk_k, sk_len)
        if causal:
            s = _mask_scores(s, q_start, k_start, blk_q, blk_k, window)
        m_prev = m_ref[:, :1]                             # [blk_q, 1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        # the normalizer l uses the FULL probabilities (softmax first);
        # dropout scales only the value accumulation — elementwise, so it
        # commutes with the final 1/l
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        if dropout_rate > 0.0:
            keep = _keep_mask(seed_ref[0], bh, q_start, k_start,
                              blk_q, blk_k, dropout_rate)
            p = p * keep.astype(p.dtype) / (1.0 - dropout_rate)
        v = _mxu_operand(v_ref[0])
        if sk_len:
            v = _zero_pad_rows(v, k_start, sk_len)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    if causal:
        # blocks strictly above the diagonal are fully masked: skip them
        @pl.when(_block_runs(q_start, k_start, blk_q, blk_k, window, k_blk,
                             n_k_blocks))
        def _():
            _block()
    else:
        _block()

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[:, :1]
        m = m_ref[:, :1]
        # fully-masked rows (every key at the clamped NEG_INF, e.g. a
        # key-padding bias masking ALL keys): emit zeros, and poison the
        # lse to +1e30 so the backward's exp(s - lse) underflows to 0 —
        # zero grads instead of data-dependent garbage. Same semantics
        # as the dense computation on a key-padding bias.
        dead = m <= NEG_INF * 0.5
        safe_l = jnp.where(dead, 1.0, l)
        o_ref[0] = jnp.where(dead, 0.0,
                             acc_ref[...] / safe_l).astype(o_ref.dtype)
        lse_col = jnp.where(dead, -NEG_INF, m + jnp.log(safe_l))
        lse_ref[0] = jnp.broadcast_to(lse_col, lse_ref.shape[1:])


def _inner_index(window, blk_outer, blk_inner, n_inner_blocks, outer_is_q):
    """For the index maps: (outer block, step of the inner grid axis) ->
    the inner block to fetch. The step itself, or under a window the
    step-th block of the outer block's span (the last block again past
    the axis's end: no new fetch, and ``_block_runs`` skips the
    compute)."""
    if not window:
        return lambda outer, step: step
    return lambda outer, step: jnp.minimum(
        _inner_block(outer, step, blk_outer, blk_inner, window, outer_is_q),
        n_inner_blocks - 1)


def _pallas_fwd(q, k, v, seed, sm_scale, mask, blk_q, blk_k,
                dropout_rate=0.0):
    mask = Mask.of(mask)
    bias, window = mask.bias, mask.window
    B, H, S, D = q.shape
    Sk, Dv = k.shape[2], v.shape[3]
    qf, kf, vf = (t.reshape(B * H, t.shape[2], t.shape[3])
                  for t in (q, k, v))
    nk = pl.cdiv(Sk, blk_k)
    grid = (B * H, *_grid("flash_fwd", S, Sk, blk_q, blk_k, window))
    kb = _inner_index(window, blk_q, blk_k, nk, True)
    has_bias = bias is not None
    kern = functools.partial(_fwd_kernel, sm_scale=sm_scale,
                             causal=mask.causal or bool(window),
                             blk_q=blk_q, blk_k=blk_k,
                             dropout_rate=dropout_rate, has_bias=has_bias,
                             sk_len=0 if Sk % blk_k == 0 else Sk,
                             window=window, n_k_blocks=nk)
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),                # seed
        pl.BlockSpec((1, blk_q, D), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, blk_k, D), lambda b, i, j: (b, kb(i, j), 0)),
        pl.BlockSpec((1, blk_k, Dv), lambda b, i, j: (b, kb(i, j), 0)),
    ]
    args = [seed, qf, kf, vf]
    _append_bias_input(in_specs, args, bias, H, blk_k, 2, kb)

    o, lse = pl.pallas_call(
        _with_optional_bias(kern, 4, has_bias),
        out_shape=(jax.ShapeDtypeStruct((B * H, S, Dv), q.dtype),
                   jax.ShapeDtypeStruct((B * H, S, LANES), jnp.float32)),
        grid=grid,
        in_specs=in_specs,
        out_specs=(pl.BlockSpec((1, blk_q, Dv), lambda b, i, j: (b, i, 0)),
                   pl.BlockSpec((1, blk_q, LANES),
                                lambda b, i, j: (b, i, 0))),
        scratch_shapes=[
            pltpu.VMEM((blk_q, Dv), jnp.float32),
            pltpu.VMEM((blk_q, 128), jnp.float32),
            pltpu.VMEM((blk_q, 128), jnp.float32),
        ],
        compiler_params=_compiler_params("flash_fwd", blk_q, blk_k, D, Dv,
                                         q.dtype.itemsize, has_bias),
        interpret=_INTERPRET and not _on_tpu(),
        name="flash_fwd",
    )(*args)
    # lse stays in its (B·H, S, LANES) wire form — the backward consumes
    # it as-is, so no slice-then-rebroadcast materialization
    return o.reshape(B, H, S, Dv), lse


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------
def _bwd_kv_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, bias_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                   *, sm_scale, causal, blk_q, blk_k, dropout_rate,
                   has_bias, s_len=0, sk_len=0, window=0, n_q_blocks=0):
    bh, ki, qi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_blk = _inner_block(ki, qi, blk_k, blk_q, window, False)
    q_start = q_blk * blk_q
    k_start = ki * blk_k

    def _block():
        q = _mxu_operand(q_ref[0])
        kk = _mxu_operand(k_ref[0])
        vv = _mxu_operand(v_ref[0])
        do = _mxu_operand(do_ref[0])
        if s_len:
            q = _zero_pad_rows(q, q_start, s_len)
            do = _zero_pad_rows(do, q_start, s_len)
        lse = lse_ref[0][:, :1]                           # [blk_q, 1]
        delta = delta_ref[0][:, :1]
        s = jax.lax.dot_general(
            q, kk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if has_bias:
            s = s + jnp.maximum(bias_ref[0], NEG_INF)
        if causal:
            s = _mask_scores(s, q_start, k_start, blk_q, blk_k, window)
        p = jnp.exp(s - lse)                              # [blk_q, blk_k]
        if s_len:
            # ragged S: padded Q/dO/lse/delta rows would contribute
            # garbage to EVERY dk/dv column — zero their probabilities
            p = jnp.where(_valid_rows(q_start, blk_q, blk_k, s_len),
                          p, 0.0)
        dp = jax.lax.dot_general(
            do, vv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # dO·Vᵀ
        if dropout_rate > 0.0:
            # regenerate the forward's mask; dV sees the dropped/scaled
            # probabilities, dS sees the masked dP (softmax-bwd delta
            # identity still holds: delta = rowsum(dO∘O))
            keep = _keep_mask(seed_ref[0], bh, q_start, k_start,
                              blk_q, blk_k,
                              dropout_rate).astype(jnp.float32)
            p_eff = p * keep / (1.0 - dropout_rate)
            dp = dp * keep / (1.0 - dropout_rate)
        else:
            p_eff = p
        dv_acc[...] += jax.lax.dot_general(
            p_eff.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # p'ᵀ·dO
        ds = p * (dp - delta) * sm_scale
        if s_len:
            # padded lse/delta rows are NaN and 0·NaN = NaN — hard-zero
            ds = jnp.where(_valid_rows(q_start, blk_q, blk_k, s_len),
                           ds, 0.0)
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # dsᵀ·Q

    if causal:
        @pl.when(_block_runs(q_start, k_start, blk_q, blk_k, window, q_blk,
                             n_q_blocks))
        def _():
            _block()
    else:
        _block()

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_q_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                  delta_ref, bias_ref, dq_ref, dq_acc,
                  *, sm_scale, causal, blk_q, blk_k, dropout_rate,
                  has_bias, sk_len=0, window=0, n_k_blocks=0):
    bh, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q_start = qi * blk_q
    k_blk = _inner_block(qi, ki, blk_q, blk_k, window, True)
    k_start = k_blk * blk_k

    def _block():
        q = _mxu_operand(q_ref[0])
        kk = _mxu_operand(k_ref[0])
        vv = _mxu_operand(v_ref[0])
        do = _mxu_operand(do_ref[0])
        if sk_len:
            kk = _zero_pad_rows(kk, k_start, sk_len)
            vv = _zero_pad_rows(vv, k_start, sk_len)
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        s = jax.lax.dot_general(
            q, kk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if has_bias:
            s = s + jnp.maximum(bias_ref[0], NEG_INF)
        if sk_len:
            # ragged Sk: padded K/V columns must not leak into dq
            s = _mask_cols(s, k_start, blk_q, blk_k, sk_len)
        if causal:
            s = _mask_scores(s, q_start, k_start, blk_q, blk_k, window)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, vv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if dropout_rate > 0.0:
            keep = _keep_mask(seed_ref[0], bh, q_start, k_start,
                              blk_q, blk_k,
                              dropout_rate).astype(jnp.float32)
            dp = dp * keep / (1.0 - dropout_rate)
        ds = p * (dp - delta) * sm_scale
        dq_acc[...] += jnp.dot(ds.astype(kk.dtype), kk,
                               preferred_element_type=jnp.float32)

    if causal:
        @pl.when(_block_runs(q_start, k_start, blk_q, blk_k, window, k_blk,
                             n_k_blocks))
        def _():
            _block()
    else:
        _block()

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_operands(q, k, v, o, lse, g):
    """What both backward kernels read, heads flattened: q, k, v, dO and
    the two row statistics. The statistics enter the kernels with the
    broadcast 128-lane minor dim (see LANES), materialized HERE as
    transients — the residual held from forward to backward is the 2-D
    (BH, S) slice, 1/128th the memory (at S=2048 the lane form would pin
    32 MB per layer)."""
    BH, S = q.shape[0] * q.shape[1], q.shape[2]
    qf, kf, vf, of, gf = (t.reshape(BH, t.shape[2], t.shape[3])
                          for t in (q, k, v, o, g))
    lsef = jnp.broadcast_to(lse.reshape(BH, S)[:, :, None], (BH, S, LANES))
    delta2 = jnp.sum(of.astype(jnp.float32) * gf.astype(jnp.float32), -1)
    delta = jnp.broadcast_to(delta2[:, :, None], (BH, S, LANES))
    return qf, kf, vf, gf, lsef, delta


def _pallas_bwd_dkv(operands, seed, H, sm_scale, mask, blk_q, blk_k,
                    dropout_rate=0.0):
    """(dK, dV) [BH, Sk, D | Dv] of ``_bwd_operands``' six, ``H`` heads a
    sequence (the key-padding bias is a sequence's)."""
    mask = Mask.of(mask)
    bias, window = mask.bias, mask.window
    qf, kf, vf = operands[:3]
    (BH, S, D), Sk, Dv = qf.shape, kf.shape[1], vf.shape[2]
    has_bias = bias is not None
    nq = pl.cdiv(S, blk_q)
    qb = _inner_index(window, blk_k, blk_q, nq, False)  # of K block j
    kern = functools.partial(
        _bwd_kv_kernel, sm_scale=sm_scale, causal=mask.causal or bool(window),
        blk_q=blk_q, blk_k=blk_k, dropout_rate=dropout_rate,
        has_bias=has_bias, window=window, n_q_blocks=nq,
        s_len=0 if S % blk_q == 0 else S, sk_len=0 if Sk % blk_k == 0 else Sk)
    specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),                    # seed
        pl.BlockSpec((1, blk_q, D), lambda b, j, i: (b, qb(j, i), 0)),   # q
        pl.BlockSpec((1, blk_k, D), lambda b, j, i: (b, j, 0)),   # k
        pl.BlockSpec((1, blk_k, Dv), lambda b, j, i: (b, j, 0)),  # v
        pl.BlockSpec((1, blk_q, Dv), lambda b, j, i: (b, qb(j, i), 0)),  # do
        pl.BlockSpec((1, blk_q, LANES),
                     lambda b, j, i: (b, qb(j, i), 0)),           # lse
        pl.BlockSpec((1, blk_q, LANES),
                     lambda b, j, i: (b, qb(j, i), 0)),           # delta
    ]
    args = [seed, *operands]
    _append_bias_input(specs, args, bias, H, blk_k, k_axis=1)
    return pl.pallas_call(
        _with_optional_bias(kern, 7, has_bias),
        out_shape=(jax.ShapeDtypeStruct((BH, Sk, D), kf.dtype),
                   jax.ShapeDtypeStruct((BH, Sk, Dv), vf.dtype)),
        grid=(BH, *_grid("flash_bwd_dkv", S, Sk, blk_q, blk_k, window)),
        in_specs=specs,
        out_specs=(pl.BlockSpec((1, blk_k, D), lambda b, j, i: (b, j, 0)),
                   pl.BlockSpec((1, blk_k, Dv), lambda b, j, i: (b, j, 0))),
        scratch_shapes=[pltpu.VMEM((blk_k, D), jnp.float32),
                        pltpu.VMEM((blk_k, Dv), jnp.float32)],
        compiler_params=_compiler_params("flash_bwd_dkv", blk_q, blk_k, D,
                                         Dv, qf.dtype.itemsize, has_bias),
        interpret=_INTERPRET and not _on_tpu(),
        name="flash_bwd_dkv",
    )(*args)


def _pallas_bwd_dq(operands, seed, H, sm_scale, mask, blk_q, blk_k,
                   dropout_rate=0.0):
    """dQ [BH, S, D] of ``_bwd_operands``' six."""
    mask = Mask.of(mask)
    bias, window = mask.bias, mask.window
    qf, kf, vf = operands[:3]
    (BH, S, D), Sk, Dv = qf.shape, kf.shape[1], vf.shape[2]
    has_bias = bias is not None
    nk = pl.cdiv(Sk, blk_k)
    kb = _inner_index(window, blk_q, blk_k, nk, True)   # of Q block i
    kern = functools.partial(
        _bwd_q_kernel, sm_scale=sm_scale, causal=mask.causal or bool(window),
        blk_q=blk_q, blk_k=blk_k, dropout_rate=dropout_rate,
        has_bias=has_bias, window=window, n_k_blocks=nk,
        sk_len=0 if Sk % blk_k == 0 else Sk)
    specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),                    # seed
        pl.BlockSpec((1, blk_q, D), lambda b, i, j: (b, i, 0)),   # q
        pl.BlockSpec((1, blk_k, D), lambda b, i, j: (b, kb(i, j), 0)),   # k
        pl.BlockSpec((1, blk_k, Dv), lambda b, i, j: (b, kb(i, j), 0)),  # v
        pl.BlockSpec((1, blk_q, Dv), lambda b, i, j: (b, i, 0)),  # do
        pl.BlockSpec((1, blk_q, LANES), lambda b, i, j: (b, i, 0)),  # lse
        pl.BlockSpec((1, blk_q, LANES), lambda b, i, j: (b, i, 0)),  # delta
    ]
    args = [seed, *operands]
    _append_bias_input(specs, args, bias, H, blk_k, 2, kb)
    return pl.pallas_call(
        _with_optional_bias(kern, 7, has_bias),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), qf.dtype),
        grid=(BH, *_grid("flash_bwd_dq", S, Sk, blk_q, blk_k, window)),
        in_specs=specs,
        out_specs=pl.BlockSpec((1, blk_q, D), lambda b, i, j: (b, i, 0)),
        scratch_shapes=[pltpu.VMEM((blk_q, D), jnp.float32)],
        compiler_params=_compiler_params("flash_bwd_dq", blk_q, blk_k, D,
                                         Dv, qf.dtype.itemsize, has_bias),
        interpret=_INTERPRET and not _on_tpu(),
        name="flash_bwd_dq",
    )(*args)


# --------------------------------------------------------------------------
# block geometry
# --------------------------------------------------------------------------
KERNELS = ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq")
# Candidate block lengths: each a multiple of Mosaic's (8, 128) tile, so a
# block is legal at any length (a shorter length takes its exact
# dimension, legal too).
BLOCK_Q_CANDIDATES = (128, 256, 512, 1024)
BLOCK_K_CANDIDATES = (128, 256, 512, 1024, 2048)
# Most VMEM a grid step's working set (`_working_set`) may take: a quarter
# of the v5e's 128 MiB. Every pair the sweep found fastest fits (the
# widest, dK/dV at 1024 x 1024 and D 256, is 22 MiB); 1024 x 2048, slower
# wherever it was measured, mostly does not.
VMEM_BUDGET = 32 * 2 ** 20
# Mosaic's default scoped-VMEM limit on the v5e; a working set above it
# is asked for by name (`vmem_limit_bytes`), with room for what the
# estimate does not see (it reads 1.05 to 1.8 times the least limit the
# chip's compiler accepts, bisected at 14 block pairs a kernel, D 64 and
# 256: PERF.md §6, PR 33).
SCOPED_VMEM_DEFAULT = 16 * 2 ** 20
VMEM_HEADROOM = 1.25
# The chooser's cost: two constants fitted to the sweep of the cells' four
# attention calls on the v5e, 70 (shape, pair) rows x three kernels
# (tools/flash_smoke.py; PERF.md §6, PR 33), and the MXU's own.
STEP_NS = 430.0      # a grid step, whatever it computes
ELEMENT_NS = 0.003   # a score element's VPU work: mask, exp, rescale
MAC_NS = 2 / 197e3   # a multiply-add at the bf16 peak, 197 TFLOP/s
# multiply-adds a score element: QK^T and PV; S, dP, dV and dK; S, dP, dQ
MACS = {"flash_fwd": lambda D, Dv: D + Dv,
        "flash_bwd_dkv": lambda D, Dv: 2 * (D + Dv),
        "flash_bwd_dq": lambda D, Dv: 2 * D + Dv}

_BLOCK_OVERRIDE = None  # (blk_q, blk_k) set by block_override()


@contextlib.contextmanager
def block_override(blk_q, blk_k):
    """Pin the block sizes of all three kernels inside the context — the
    sweep on the chip (tools/flash_smoke.py) and the tests measure and
    check blk_q×blk_k pairs with it; the override applies to forward AND
    the custom-vjp backward, so wrap the whole grad computation."""
    global _BLOCK_OVERRIDE
    prev = _BLOCK_OVERRIDE
    _BLOCK_OVERRIDE = (int(blk_q), int(blk_k))
    try:
        yield
    finally:
        _BLOCK_OVERRIDE = prev


def _working_set(kernel, blk_q, blk_k, D, Dv, itemsize, has_bias=False):
    """Bytes of VMEM one grid step of ``kernel`` holds at these blocks:
    every input and output block twice (the pipeline fetches the next
    while this one computes), the f32 scratch accumulators, and the f32
    [blk_q, blk_k] score-sized temporaries the body keeps live (s, p and
    the cast or keep mask forward; dp and ds as well backward). A minor
    dimension takes whole 128-lane tiles, so a 64-wide head costs 128.
    f32 operands reach the MXU as bf16 pieces, up to three an operand
    at the "highest" matmul precision (the float32 parity programs'):
    half as much again, bisected like the rest. The one estimate: the
    chooser's budget, ``vmem_limit_bytes`` and the sweep's
    ``vmem_kb_est`` all read it."""
    def lanes(n):
        return -(-n // LANES) * LANES
    d, dv, bk = lanes(D), lanes(Dv), lanes(blk_k)
    q_t, k_t = blk_q * d * itemsize, blk_k * d * itemsize
    v_t, o_t = blk_k * dv * itemsize, blk_q * dv * itemsize  # o_t: dO too
    stat = blk_q * LANES * 4                                 # lse, delta
    bias = 8 * bk * 4 if has_bias else 0
    if kernel == "flash_fwd":
        piped = q_t + k_t + v_t + bias + o_t + stat
        scratch, tiles = blk_q * dv * 4 + 2 * stat, 2
    elif kernel == "flash_bwd_dkv":
        piped = q_t + k_t + v_t + o_t + 2 * stat + bias + k_t + v_t
        scratch, tiles = blk_k * (d + dv) * 4, 3
    else:
        piped = q_t + k_t + v_t + o_t + 2 * stat + bias + q_t
        scratch, tiles = blk_q * d * 4, 2
    total = 2 * piped + scratch + tiles * blk_q * bk * 4
    return total if itemsize <= 2 else total * 3 // 2


def _compiler_params(kernel, blk_q, blk_k, D, Dv, itemsize, has_bias):
    """The three kernels' Mosaic parameters: the inner grid axis carries
    the accumulators; the working set is named where it passes the
    default scoped limit."""
    need = int(_working_set(kernel, blk_q, blk_k, D, Dv, itemsize, has_bias)
               * VMEM_HEADROOM)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=need if need > SCOPED_VMEM_DEFAULT else None)


def _cost_ns(kernel, S, Sk, blk_q, blk_k, D, Dv, mask):
    """What the chooser minimises, ns a head: every step of the grid
    pays ``STEP_NS`` (a skipped one too), every score element of a block
    pair that is computed pays ``ELEMENT_NS`` of VPU work (mask, exp,
    rescale; a masked element as much as a kept one) and the MXU's time
    for its products over D and Dv. Larger blocks buy fewer steps with
    more elements computed and thrown away: along a causal diagonal,
    and far more under a window."""
    elements = visited_blocks(S, Sk, blk_q, blk_k, mask) * blk_q * blk_k
    return (grid_steps(S, Sk, blk_q, blk_k, mask, kernel) * STEP_NS
            + elements * (ELEMENT_NS + MACS[kernel](D, Dv) * MAC_NS))


def _block_sizes(kernel, S, Sk, D, Dv, mask=Mask(), itemsize=2):
    """(blk_q, blk_k) of ``kernel`` for a call of this shape: of the
    candidate pairs whose working set fits ``VMEM_BUDGET``, the one
    ``_cost_ns`` reckons cheapest (the larger on a tie). A length
    shorter than a candidate takes its EXACT dimension — a block equal
    to the array dim is always Mosaic-legal regardless of (8, 128)
    alignment, so tiny and tiny-ragged shapes lower without padding
    games; a length no candidate divides stays on the in-kernel bounds
    masks. Inside ``block_override`` the pinned pair wins."""
    if _BLOCK_OVERRIDE:
        return min(S, _BLOCK_OVERRIDE[0]), min(Sk, _BLOCK_OVERRIDE[1])
    pairs = sorted({(min(S, bq), min(Sk, bk)) for bq in BLOCK_Q_CANDIDATES
                    for bk in BLOCK_K_CANDIDATES}, reverse=True)
    fits = [p for p in pairs
            if _working_set(kernel, *p, D, Dv, itemsize,
                            mask.bias is not None) <= VMEM_BUDGET]
    # the smallest pair is the fallback of a head too wide for the budget
    return min(fits or pairs[-1:],
               key=lambda p: _cost_ns(kernel, S, Sk, *p, D, Dv, mask))


def _blocks_of(kernel, q, k, v, mask):
    """``_block_sizes`` of a call's operands [B, H, S, D] and its mask
    (with its bias, where it has one: its block is in the working set)."""
    return _block_sizes(kernel, q.shape[2], k.shape[2], q.shape[3],
                        v.shape[3], Mask.of(mask), q.dtype.itemsize)


# --------------------------------------------------------------------------
# public entry
# --------------------------------------------------------------------------
# The names a call gives the two values its backward reads beside q, k, v:
# the output and the one-column log-sum-exp [B·H, S] (never the 128-lane
# wire form). A recomputed segment keeps values by these names
# (fluid/recompute_lowering.KEPT), so its backward does not run the
# forward kernel again; anywhere else a name changes nothing.
FLASH_OUT = "flash_out"
FLASH_LSE = "flash_lse"


def _flash_pallas(q, k, v, seed, bias, sm_scale, mask, dropout_rate):
    """``mask``: a ``Mask`` without its bias (static: a bool stands for
    its causal flag); the key-padding ``bias`` is an operand. The forward
    kernel runs as a plain call on operands that carry no gradient, and
    ``_flash_grad`` attaches the gradient, its inputs the kernel's
    results: so the names are on plain values of the caller's trace,
    given once a trace, which a checkpoint's policy sees whatever jax
    does inside a custom_vjp (a name on a custom_vjp's OUTPUT would not
    do: its forward rule would run again for the residuals)."""
    from ...fluid.recompute_lowering import keep
    fq, fk, fv, fbias = jax.lax.stop_gradient((q, k, v, bias))
    biased = Mask.of(mask)._replace(bias=fbias)
    o, lse = _pallas_fwd(fq, fk, fv, seed, sm_scale, biased,
                         *_blocks_of("flash_fwd", q, k, v, biased),
                         dropout_rate)
    o, lse = keep(o, FLASH_OUT), keep(lse[:, :, 0], FLASH_LSE)
    return _flash_grad(q, k, v, o, lse, seed, bias, sm_scale, mask,
                       dropout_rate)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _flash_grad(q, k, v, o, lse, seed, bias, sm_scale, mask, dropout_rate):
    """``o`` itself, with the flash gradient of q, k and v attached."""
    return o


def _fp_fwd(q, k, v, o, lse, seed, bias, sm_scale, mask, dropout_rate):
    return o, (q, k, v, o, lse, seed, bias)


def _fp_bwd(sm_scale, mask, dropout_rate, res, g):
    q, k, v, o, lse, seed, bias = res
    operands = _bwd_operands(q, k, v, o, lse, g)
    biased = Mask.of(mask)._replace(bias=bias)
    H = q.shape[1]
    dk, dv = _pallas_bwd_dkv(operands, seed, H, sm_scale, biased,
                             *_blocks_of("flash_bwd_dkv", q, k, v, biased),
                             dropout_rate)
    dq = _pallas_bwd_dq(operands, seed, H, sm_scale, biased,
                        *_blocks_of("flash_bwd_dq", q, k, v, biased),
                        dropout_rate)
    dseed = np.zeros(seed.shape, jax.dtypes.float0)  # int arg: zero tangent
    dbias = None if bias is None else jnp.zeros_like(bias)  # mask input
    # o and lse come from operands under stop_gradient: nothing to send
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape), \
        jnp.zeros_like(o), jnp.zeros_like(lse), dseed, dbias


_flash_grad.defvjp(_fp_fwd, _fp_bwd)

_MESH = None  # the device mesh whose step is being traced (mesh_guard)


@contextlib.contextmanager
def mesh_guard(mesh):
    """While a step is traced for a ("dp", "mp") device mesh, tell the
    kernels so: XLA cannot partition a Mosaic kernel ("wrap the call in
    a shard_map"), so under such a mesh `flash_attention` does the
    partitioning itself. ``None`` (one device) is a no-op."""
    global _MESH
    prev = _MESH
    _MESH = mesh
    try:
        yield
    finally:
        _MESH = prev


def _flash_on_mesh(mesh, q, k, v, seed, bias, sm_scale, mask,
                   dropout_rate):
    """`_flash_pallas` under a shard_map over ``mesh``: batch split over
    "dp" and heads over "mp" where they divide (attention is independent
    per batch row and head, so no collective is needed), replicated work
    where they do not. Each shard that holds different rows or heads
    mixes its mesh position into the dropout seed — the kernel's mask
    hashes LOCAL row/head indices, and equal seeds would drop the same
    entries in every shard."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    B, H = q.shape[:2]
    split = [name if name in mesh.axis_names
             and dim % mesh.shape[name] == 0 else None
             for name, dim in (("dp", B), ("mp", H))]
    qkv = P(*split, None, None)

    def per_shard(q, k, v, seed, bias):
        shard, stride = jnp.int32(0), 1  # linear index over the split axes
        for name in filter(None, split):
            shard = shard + jax.lax.axis_index(name).astype(jnp.int32) * stride
            stride *= mesh.shape[name]
        return _flash_pallas(q, k, v, seed + jnp.int32(1000003) * shard,
                             bias, sm_scale, mask, dropout_rate)

    # check_vma off: neither pallas_call's outputs nor the Pallas
    # interpreter carry varying-axes types under jax 0.9.0. What that
    # leaves unchecked — the transpose over an axis the operands are
    # replicated on — is pinned by the N-vs-1 loss parity in
    # tests/test_parallel.py.
    return shard_map(
        per_shard, mesh=mesh,
        in_specs=(qkv, qkv, qkv, P(),
                  None if bias is None else P(split[0], None)),
        out_specs=qkv, check_vma=False)(q, k, v, seed, bias)

# numpy, NOT jnp: a lazily-created jnp array inside someone's jit trace
# would cache a tracer in this global and poison every later trace
_ZERO_SEED = np.zeros((1,), np.int32)


def flash_attention(q, k, v, sm_scale, mask=Mask(), dropout_rate=0.0,
                    dropout_seed=None):
    """q, k [B,H,S,D], v [B,H,Sk,Dv] → [B,H,S,Dv]. The Pallas flash
    kernels on a TPU backend (and under interpret mode); the dense
    computation on the CPU backend otherwise. ``mask``: a ``Mask`` (a
    bool stands for its causal flag); its bias is the additive
    key-padding form [B, Sk] broadcast over query rows (the reference
    BiasQK padding form), a constant wrt gradients.
    dropout_rate > 0 applies attention-probability dropout INSIDE the
    kernel (mask regenerated in the backward from dropout_seed, an int32
    [1] array — pass a fresh per-step value when training)."""
    mask = Mask.of(mask)
    if dropout_rate > 0.0 and dropout_seed is None:
        # a silent default seed would drop the SAME attention entries
        # every step — training bias with no symptom
        raise ValueError(
            "flash_attention: dropout_rate > 0 requires dropout_seed "
            "(int32 [1] array, fresh per training step)")
    if not (q.dtype == k.dtype == v.dtype):
        # the in-kernel MXU-native dots require matching operand dtypes
        # (bf16 tiles are fed to the MXU unconverted) — normalize mixed
        # inputs up front instead of failing inside the kernel trace
        ct = jnp.result_type(q.dtype, k.dtype, v.dtype)
        q, k, v = (t.astype(ct) for t in (q, k, v))
    if _use_kernels():
        if dropout_seed is None:
            dropout_seed = _ZERO_SEED
        static = mask._replace(bias=None)
        if _MESH is not None and {"dp", "mp"} & set(_MESH.axis_names):
            return _flash_on_mesh(_MESH, q, k, v, dropout_seed, mask.bias,
                                  sm_scale, static, float(dropout_rate))
        return _flash_pallas(q, k, v, dropout_seed, mask.bias, sm_scale,
                             static, float(dropout_rate))
    if dropout_rate > 0.0:
        raise NotImplementedError(
            "attention dropout requires the Pallas path (a TPU backend "
            "or interpret_mode(True))")
    from ..attention_ops import _dense_attention
    return _dense_attention(q, k, v, sm_scale, mask).astype(q.dtype)
