"""Ops of hybrid decoder LMs: linear attention and sparse experts (the
Qwen3-Next family; layer equations and departures:
benchmark/configs/qwen3_next_80b_a3b_reference.py), state-space scans
and differential attention (the SambaY family:
benchmark/configs/phi4_mini_flash_reference.py), sigmoid-scored
routing and rotary embeddings scaled by YaRN (the Laguna family:
benchmark/configs/laguna_xs2_reference.py), latent attention's
up-projections and a router's choice-only bias (the DeepSeek-V3 family:
benchmark/configs/joyai_llm_flash_reference.py), the delta rule with a
decay a key channel and a router's group stage (the Ling family:
benchmark/configs/ling3_flash_vl_reference.py).

``rms_norm``            RMSNorm over groups of the last dim, zero-centred
                        weight or plain, optionally gated by SiLU(Gate)
``rotary_embedding``    partial rotate-half rotary embedding a head, its
                        frequencies plain or YaRN's
``causal_conv1d``       depthwise causal convolution along the sequence
``gated_delta_rule``    the gated delta rule, in chunks (WY form)
``kda``                 Kimi delta attention: the same rule, its decay a
                        key channel, bounded below
``selective_scan``      Mamba's diagonal state-space recurrence, in chunks
``differential_combine`` A_1 V - lambda A_2 V of differential attention
``latent_qkv``          Q, K, V of latent attention from the normed
                        latents and the shared rotary key (interleaved or
                        rotate-half)
``moe_router``          softmax or sigmoid scores over all experts, top-k,
                        auxiliary loss; optionally a group stage, a
                        choice-only bias and its load-balancing update
``moe_expert_ffn``      the held experts' part of a routed gated FFN

One pure JAX kernel each; gradients are the registry's vjp of it (the
scan and the expert passes bring their own under it). Matmul
operands are bf16 under FLAGS_use_bf16_matmul on a backend with an MXU
(``math_ops._mm``'s gate); the router, every norm, the gates and the
delta rule's chunk state and the scan's state and decay stay float32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .registry import register_grad_maker, register_op, first, out
from .math_ops import mxu_available
from .pallas import delta_rule, grouped_matmul, selective_scan


def _operands(*xs):
    """``_mm``'s gate for the products below: float32 operands go to the
    MXU as bf16 and accumulate in float32."""
    from ..fluid import core as _core
    if _core.globals_["FLAGS_use_bf16_matmul"] and mxu_available():
        return tuple(x.astype(jnp.bfloat16) if x.dtype == jnp.float32 else x
                     for x in xs)
    return xs


def _einsum(spec, a, b):
    return jnp.einsum(spec, *_operands(a, b),
                      preferred_element_type=jnp.float32)


def _gauge(name, help_, site, value):
    from ..fluid import telemetry
    telemetry.set_site_gauge(name, help_, site, value)


def _silu(x):
    return x * jax.nn.sigmoid(x)


# --------------------------------------------------------------------------
@register_op("rms_norm", inputs=("X", "Scale", "Gate"),
             diff_inputs=("X", "Scale", "Gate"),
             attr_defaults={"epsilon": 1e-6, "zero_centered": False})
def _rms_norm(ins, attrs):
    """y = x * rsqrt(mean(x^2) + eps) * w over each group of len(Scale)
    of the last dim (a head's dims, or the whole of it), w = 1 + Scale
    where ``zero_centered``; with Gate, y * SiLU(Gate). Float32 inside."""
    x, scale, gate = first(ins, "X"), first(ins, "Scale"), first(ins, "Gate")
    d = scale.shape[-1]
    xr = x.astype(jnp.float32).reshape(x.shape[:-1] + (-1, d))
    y = xr * lax.rsqrt(jnp.mean(xr * xr, -1, keepdims=True)
                       + attrs.get("epsilon", 1e-6))
    w = scale.astype(jnp.float32)
    y = (y * (1.0 + w if attrs.get("zero_centered", False) else w)
         ).reshape(x.shape)
    if gate is not None:
        y = y * _silu(gate.astype(jnp.float32))
    return out(Out=y.astype(x.dtype))


def yarn_correction_range(rotary_dim, theta, original_max_position,
                          beta_fast, beta_slow):
    """(low, high): the dims of a head's ``rotary_dim`` / 2 frequencies
    between which YaRN's ramp runs, whole numbers: dim c(n) turns n times
    over the original context, c(n) = rotary_dim ln(original / (2 pi n))
    / (2 ln theta); low = floor(c(beta_fast)), high = ceil(c(beta_slow)),
    kept inside the head."""
    def dim(rotations):
        return rotary_dim * math.log(
            original_max_position / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))
    return (max(math.floor(dim(beta_fast)), 0),
            min(math.ceil(dim(beta_slow)), rotary_dim - 1))


def yarn_inv_freq(rotary_dim, theta, factor, original_max_position,
                  beta_fast, beta_slow):
    """YaRN's rotary_dim / 2 inverse frequencies (Peng et al. 2023, as
    `transformers` computes them, the range truncated to whole dims):
    dims below ``low`` turn often enough inside the original context and
    keep theta^(-2i / r), dims above ``high`` are interpolated (divided
    by ``factor``), a linear ramp between. Made on the host in float64
    and rounded once to float32: constants of the traced op, the same
    whatever compiles it."""
    i = np.arange(rotary_dim // 2, dtype=np.float64)
    pos = float(theta) ** (2 * i / rotary_dim)
    low, high = yarn_correction_range(rotary_dim, theta,
                                      original_max_position, beta_fast,
                                      beta_slow)
    ramp = np.clip((i - low) / (high - low if high != low else 0.001), 0, 1)
    return ((1 - ramp) / pos + ramp / (factor * pos)).astype(np.float32)


@register_op("rotary_embedding", inputs=("X",),
             attr_defaults={"num_heads": 1, "rotary_dim": 0, "theta": 1e4,
                            "yarn_factor": 0.0, "original_max_position": 0,
                            "beta_fast": 32.0, "beta_slow": 1.0,
                            "cos_sin_scale": 1.0})
def _rotary_embedding(ins, attrs):
    """Rotate-half rotary embedding on the first ``rotary_dim`` dims of
    each of ``num_heads`` heads; X [B, S, H*D], a token's position is
    its index in the sequence. ``yarn_factor`` > 0: the frequencies are
    YaRN's (``yarn_inv_freq``; its four numbers are attrs, the dims'
    frequencies are made here, where the op is traced) and
    ``cos_sin_scale`` multiplies cos and sin (YaRN's attention factor);
    the defaults are the plain embedding."""
    x = first(ins, "X")
    b, s, hd = x.shape
    h = attrs.get("num_heads", 1)
    r = attrs.get("rotary_dim", 0) or hd // h
    xh = x.reshape(b, s, h, hd // h)
    if attrs.get("yarn_factor", 0.0):
        inv = jnp.asarray(yarn_inv_freq(
            r, attrs.get("theta", 1e4), attrs["yarn_factor"],
            attrs["original_max_position"], attrs.get("beta_fast", 32.0),
            attrs.get("beta_slow", 1.0)))
    else:
        inv = attrs.get("theta", 1e4) ** (
            -jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.tile(jnp.cos(angle), (1, 2))[None, :, None, :]
    sin = jnp.tile(jnp.sin(angle), (1, 2))[None, :, None, :]
    scale = attrs.get("cos_sin_scale", 1.0)
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    rot, rest = xh[..., :r], xh[..., r:]
    half = jnp.concatenate([-rot[..., r // 2:], rot[..., :r // 2]], -1)
    y = jnp.concatenate([rot * cos + half * sin, rest], -1)
    return out(Out=y.reshape(x.shape).astype(x.dtype))


@register_op("causal_conv1d", inputs=("X", "Filter"))
def _causal_conv1d(ins, attrs):
    """Depthwise causal convolution: X [B, S, C], Filter [C, K],
    y[t, c] = sum_j Filter[c, j] * x[t - (K - 1) + j, c], zeros before
    the sequence; no bias."""
    x, w = first(ins, "X"), first(ins, "Filter")
    k, s = w.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    y = padded[:, 0:s] * w[:, 0]
    for j in range(1, k):
        y = y + padded[:, j:j + s] * w[:, j]
    return out(Out=y)


# --------------------------------------------------------------------------
def _chunked_delta_rule(q, k, v, g, beta, chunk, block=None, kernels=None):
    """The gated delta rule in chunks. q, k [B, H, S, dk] (normalised, q
    scaled), v [B, H, S, dv], beta [B, H, S], g the log of the decay (<=
    0): [B, H, S], one a head (Gated DeltaNet), or [B, H, S, dk], one a
    key channel (Kimi delta attention, whose decays are then factored in
    blocks of ``block`` positions: ``_channel_pairs``); -> [B, H, S, dv].

    Token form: S_t = Diag(a_t) S_{t-1} + k_t u_t^T, u_t = beta_t (v_t -
    (Diag(a_t) S_{t-1})^T k_t), o_t = S_t^T q_t. Within a chunk of C
    positions with G_i = sum_{j<=i} g_j and D_ij = exp(G_i - G_j) (i >=
    j; over the key channels where the decay is one a channel), the u_i
    solve (I + L) U = beta V - (beta K e^G) S_0, L_ij = beta_i (k_i . D_ij
    k_j) (i > j): one triangular solve gives T = (I + L)^-1, the WY
    form's two products T (beta V) and T (beta K e^G) are made for every
    chunk at once, and only the chunk states are scanned, in float32:
    S_0 -> S_C = e^{G_C} S_0 + (K e^{G_C - G})^T U. Two lowerings of
    that recurrence: ``kernels`` (the (heads, chunks) a grid step of
    ``_delta_blocks``) takes the Pallas pair of ``pallas/delta_rule.py``,
    which makes T and keeps the state in VMEM; None takes
    ``solve_triangular`` and ``lax.scan``, differentiated by jax."""
    b, h, s, dk = q.shape
    dv = v.shape[-1]
    channels = g.ndim == 4
    pad = -s % chunk
    if pad:  # padded positions: k = v = beta = 0 write nothing
        q, k, v = (jnp.pad(t, ((0, 0), (0, 0), (0, pad), (0, 0)))
                   for t in (q, k, v))
        g, beta = (jnp.pad(t, ((0, 0), (0, 0), (0, pad))
                           + ((0, 0),) * (t.ndim - 3)) for t in (g, beta))
    n = (s + pad) // chunk
    q, k, v = (t.reshape(b, h, n, chunk, -1) for t in (q, k, v))
    g, beta = (t.reshape((b, h, n, chunk) + t.shape[3:]) for t in (g, beta))
    gc = jnp.cumsum(g, 3)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    strictly_lower = jnp.tril(lower, -1)
    if channels:
        pairs = _channel_pairs(k, gc, block)

        def col(t):
            return t
    else:
        # exp of a masked difference: the upper triangle would overflow
        decay = jnp.exp(jnp.where(lower, gc[..., :, None] - gc[..., None, :],
                                  -jnp.inf))

        def pairs(a):
            return _einsum("bhnid,bhnjd->bhnij", a, k) * decay

        def col(t):
            return t[..., None]
    k_beta = k * beta[..., None]
    kk = pairs(k_beta)
    if kernels:
        carry = jnp.exp(gc[:, :, :, -1])
        o = delta_rule.recurrence(
            jnp.where(strictly_lower, kk, 0.0),
            *_operands(jnp.where(lower, pairs(q), 0.0), v * beta[..., None],
                       k_beta * col(jnp.exp(gc)), q * col(jnp.exp(gc)),
                       k * col(jnp.exp(gc[:, :, :, -1:] - gc))),
            carry if channels else jnp.broadcast_to(carry[..., None],
                                                    carry.shape + (dk,)),
            kernels)
        return o.reshape(b, h, n * chunk, dv)[:, :, :s]
    eye = jnp.eye(chunk, dtype=jnp.float32)
    system = eye + jnp.where(strictly_lower, kk, 0.0)
    t_inv = jax.scipy.linalg.solve_triangular(
        system, jnp.broadcast_to(eye, system.shape), lower=True)
    u = _einsum("bhnij,bhnjd->bhnid", t_inv, v * beta[..., None])
    w = _einsum("bhnij,bhnjd->bhnid", t_inv, k_beta * col(jnp.exp(gc)))
    qk = jnp.where(lower, pairs(q), 0.0)
    q_in = q * col(jnp.exp(gc))
    k_out = k * col(jnp.exp(gc[:, :, :, -1:] - gc))
    carry_decay = jnp.exp(gc[:, :, :, -1])

    def step(state, xs):
        qk_i, u_i, w_i, q_i, k_i, decay_i = xs
        v_new = u_i - _einsum("bhid,bhdv->bhiv", w_i, state)
        o = _einsum("bhid,bhdv->bhiv", q_i, state) \
            + _einsum("bhij,bhjv->bhiv", qk_i, v_new)
        state = state * (decay_i[..., None] if channels
                         else decay_i[..., None, None]) \
            + _einsum("bhid,bhiv->bhdv", k_i, v_new)
        return state, o

    xs = tuple(jnp.moveaxis(t, 2, 0)
               for t in (qk, u, w, q_in, k_out, carry_decay))
    _, o = lax.scan(step, jnp.zeros((b, h, dk, dv), jnp.float32), xs)
    return jnp.moveaxis(o, 0, 2).reshape(b, h, n * chunk, dv)[:, :, :s]


def _delta_blocks(b, h, s, chunk, dk, dv):
    """(heads, chunks) a grid step of the delta rule's kernels for this
    call, or None: the ``lax.scan`` lowering (a backend without the
    kernels, a mesh, a chunk or widths they do not serve)."""
    if not delta_rule.use_kernels():
        return None
    itemsize = _operands(jnp.zeros((), jnp.float32))[0].dtype.itemsize
    return delta_rule.block_sizes(b * h, -(-s // chunk), chunk, dk, dv,
                                  itemsize)


def _delta_gauges(name, help_, site, b, h, s, chunk, blocks):
    """The chunks the recurrence steps over, and where the kernels ran,
    the steps of the forward kernel's grid."""
    _gauge(name, help_, site, b * -(-s // chunk))
    if blocks:
        _gauge("delta_grid_steps_per_step",
               "steps of the delta rule's forward Pallas kernel's grid, "
               "every sequence and head and chunk block; unset where the "
               "lax.scan lowering ran", site,
               delta_rule.grid_steps(b * h, -(-s // chunk), blocks))


def _channel_pairs(k, gc, block):
    """For a decay a key channel: a -> [.., C, C], entry (i, j) = sum_c
    a_i[c] k_j[c] exp(G_i[c] - G_j[c]) for j up to the end of i's block
    of ``block`` positions, 0 past it; k, gc [.., C, dk], a like k. The
    decay is never made [C, C, dk] (4.3 GB a layer at S 4096): with R
    the middle of G over i's block (the mean of its first and last G, a
    channel), exp(G_i - G_j) = exp(G_i - R) exp(R - G_j), the first
    factor within exp(+-h), h = |bound| (block - 1) / 2 (``kda_block``
    keeps h <= EXP_LIMIT), the second too for j in the block and <= 1
    before it; past the block the EXPONENT is masked to -inf, not the
    product. Balanced so, because the MXU's passes flush what falls
    under float32's normal range: against the block's first position a
    factor reaches exp(-75), and its products with a gradient's ~1e-9
    terms would be lost. R is a constant of the gradient: the product
    does not depend on it. Two MXU products a block, as the scalar
    form's one [C, C] product."""
    *lead, c, dk = k.shape
    nb = c // block
    ends = gc.reshape(*lead, nb, block, dk)
    ref = lax.stop_gradient((ends[..., 0, :] + ends[..., -1, :]) / 2)
    ahead = jnp.arange(c)[None, :] >= (jnp.arange(nb)[:, None] + 1) * block
    k_ref = k[..., None, :, :] * jnp.exp(jnp.where(
        ahead[:, :, None], -jnp.inf,
        ref[..., :, None, :] - gc[..., None, :, :]))

    def pairs(a):
        a_ref = a.reshape(*lead, nb, block, dk) * jnp.exp(
            ends - ref[..., :, None, :])
        return _einsum("bhnxid,bhnxjd->bhnxij", a_ref, k_ref).reshape(
            *lead, c, c)
    return pairs


@register_op("gated_delta_rule",
             inputs=("Q", "K", "V", "A", "B", "ALog", "DtBias"),
             attr_defaults={"num_key_heads": 1, "num_value_heads": 1,
                            "chunk_size": 64, "site": ""})
def _gated_delta_rule(ins, attrs):
    """Gated DeltaNet's mixing: Q, K [B, S, Hk*dk], V [B, S, Hv*dv],
    A, B [B, S, Hv] (the decay's and the write strength's
    pre-activations), ALog, DtBias [Hv] -> Out [B, S, Hv*dv]. Key heads
    are repeated to the value heads, q and k L2-normalised a head, q
    scaled by dk^-1/2, beta = sigmoid(B), log decay = -exp(ALog) *
    softplus(A + DtBias); then ``_chunked_delta_rule``."""
    q, k, v = first(ins, "Q"), first(ins, "K"), first(ins, "V")
    hk, hv = attrs["num_key_heads"], attrs["num_value_heads"]
    chunk = attrs.get("chunk_size", 64)
    b, s, _ = q.shape

    def heads(t, n):
        return jnp.moveaxis(t.astype(jnp.float32).reshape(b, s, n, -1), 1, 2)

    q, k = (jnp.repeat(heads(t, hk), hv // hk, axis=1) for t in (q, k))
    q, k = (t * lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)
            for t in (q, k))
    q = q * q.shape[-1] ** -0.5
    beta = jax.nn.sigmoid(first(ins, "B").astype(jnp.float32))
    g = -jnp.exp(first(ins, "ALog").astype(jnp.float32)) * jax.nn.softplus(
        first(ins, "A").astype(jnp.float32) + first(ins, "DtBias"))
    blocks = _delta_blocks(b, hv, s, chunk, q.shape[-1], v.shape[-1] // hv)
    o = _chunked_delta_rule(q, k, heads(v, hv), jnp.moveaxis(g, 1, 2),
                            jnp.moveaxis(beta, 1, 2), chunk, kernels=blocks)
    _delta_gauges("gdn_chunks_per_step",
                  "chunks of the sequence the delta rule's state scan "
                  "steps over, a sequence of the batch each",
                  attrs.get("site", ""), b, hv, s, chunk, blocks)
    return out(Out=jnp.moveaxis(o, 1, 2).reshape(b, s, -1).astype(v.dtype))


# The largest log a factor of ``_channel_pairs`` may carry either way:
# e^+-40 is 2.4e17 / 4.2e-18, so a factor times a gradient's ~1e-10 stays
# far inside float32's normal range (1.2e-38 .. 3.4e38).
EXP_LIMIT = 40.0


def kda_block(lower_bound, chunk):
    """Positions of the blocks ``_channel_pairs`` factors a chunk's decays
    in: the largest power-of-two part of ``chunk`` over which the
    log-decay, each step above ``lower_bound``, moves at most EXP_LIMIT
    either way of its middle: -lower_bound (block - 1) / 2 <= EXP_LIMIT
    (16 at the bound -5). An unbounded gate has no such block and
    raises."""
    if not lower_bound < 0:
        raise ValueError(f"kda: lower_bound {lower_bound} is not below 0")
    block = chunk
    while block > 1 and -lower_bound * (block - 1) / 2 > EXP_LIMIT:
        block //= 2
    return block


@register_op("kda", inputs=("Q", "K", "V", "A", "B", "ALog", "DtBias"),
             attr_defaults={"lower_bound": -5.0, "chunk_size": 64,
                            "site": ""})
def _kda(ins, attrs):
    """Kimi delta attention (arXiv:2510.26692): the delta rule with a
    decay a KEY CHANNEL. Q, K, V [B, S, H*d] (after their convolution
    and SiLU), A [B, S, H*dk] (the decay's pre-activation, a channel), B
    [B, S, H] (the write strength's, a head), ALog [H], DtBias [H*dk] ->
    Out [B, S, H*dv]. q and k L2-normalised a head, q scaled by
    dk^-1/2, beta = sigmoid(B), log decay g = ``lower_bound`` *
    sigmoid(exp(ALog) (A + DtBias)) in (lower_bound, 0) (the bounded
    "safe" gate); then ``_chunked_delta_rule`` over the key channels, its
    chunks factored in ``kda_block`` positions. Float32 inside."""
    q, k, v = first(ins, "Q"), first(ins, "K"), first(ins, "V")
    h = first(ins, "B").shape[-1]
    bound = attrs.get("lower_bound", -5.0)
    chunk = attrs.get("chunk_size", 64)
    b, s, _ = q.shape

    def heads(t):
        return jnp.moveaxis(t.astype(jnp.float32).reshape(b, s, h, -1), 1, 2)

    q, k = (t * lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)
            for t in (heads(q), heads(k)))
    q = q * q.shape[-1] ** -0.5
    beta = jax.nn.sigmoid(first(ins, "B").astype(jnp.float32))
    g = bound * jax.nn.sigmoid(
        jnp.exp(first(ins, "ALog").astype(jnp.float32))[:, None, None]
        * heads(first(ins, "A") + first(ins, "DtBias")))
    blocks = _delta_blocks(b, h, s, chunk, q.shape[-1], v.shape[-1] // h)
    o = _chunked_delta_rule(q, k, heads(v), g, jnp.moveaxis(beta, 1, 2),
                            chunk, kda_block(bound, chunk), blocks)
    _delta_gauges("kda_chunks_per_step",
                  "chunks of the sequence Kimi delta attention's state "
                  "scan steps over, a sequence of the batch each",
                  attrs.get("site", ""), b, h, s, chunk, blocks)
    return out(Out=jnp.moveaxis(o, 1, 2).reshape(b, s, -1).astype(v.dtype))


# --------------------------------------------------------------------------
def _scan_chunk(state, u, dt, b, c, a):
    """One chunk of the recurrence, a position a step: state [B, C, N]
    before the chunk, u, dt [B, T, C], b, c [B, T, N], a [C, N] ->
    (the state after it, y [B, T, C]). The decay exp(dt a) and the write
    (dt u) b of all T positions are made at once; only the T updates
    s <- decay_t s + write_t and the readout s . c_t are sequential."""
    decay = jnp.exp(dt[..., None] * a)
    write = (dt * u)[..., None] * b[:, :, None, :]

    def step(s, xs):
        decay_t, write_t, c_t = xs
        s = decay_t * s + write_t
        return s, jnp.sum(s * c_t[:, None, :], -1)

    state, y = lax.scan(step, state, tuple(
        jnp.moveaxis(t, 1, 0) for t in (decay, write, c)))
    return state, jnp.moveaxis(y, 0, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _chunked_scan(u, dt, b, c, a, kernels=False):
    """s_t = exp(dt_t a) s_{t-1} + (dt_t u_t) b_t, y_t = s_t . c_t from
    s_0 = 0, over chunks [n, B, T, .] of the sequence; float32. Its own
    vjp: the forward keeps the state at each chunk's START only (n of
    them, not S: the whole history at s4096 x 5120 x 16 is 1.34 GB a
    layer), and the backward walks the chunks last to first, each made
    anew from its boundary state. Two lowerings of the one recurrence:
    ``kernels`` (the caller's ``selective_scan.use_kernels()``: a TPU
    backend, off a mesh) takes the Pallas pair of
    ``pallas/selective_scan.py``, which keeps the state in VMEM; any
    other backend takes ``lax.scan`` over ``_scan_chunk`` and its vjp."""
    return _chunked_scan_fwd(u, dt, b, c, a, kernels)[0]


def _chunked_scan_fwd(u, dt, b, c, a, kernels=False):
    if kernels:
        y, starts = selective_scan.forward(u, dt, b, c, a)
        return y, (u, dt, b, c, a, starts)

    def chunk(state, xs):
        after, y = _scan_chunk(state, *xs, a)
        return after, (state, y)

    zero = jnp.zeros((u.shape[1], u.shape[3], a.shape[1]), jnp.float32)
    _, (starts, y) = lax.scan(chunk, zero, (u, dt, b, c))
    return y, (u, dt, b, c, a, starts)


def _chunked_scan_bwd(kernels, residuals, d_y):
    u, dt, b, c, a, starts = residuals
    if kernels:
        return selective_scan.backward(u, dt, b, c, a, starts, d_y)

    def chunk(carry, xs):
        d_state, d_a = carry
        start, d_y_i, *inputs = xs
        _, vjp = jax.vjp(_scan_chunk, start, *inputs, a)
        d_start, *d_inputs, d_a_i = vjp((d_state, d_y_i))
        return (d_start, d_a + d_a_i), tuple(d_inputs)

    (_, d_a), d_inputs = lax.scan(
        chunk, (jnp.zeros_like(starts[0]), jnp.zeros_like(a)),
        (starts, d_y, u, dt, b, c), reverse=True)
    return d_inputs + (d_a,)


_chunked_scan.defvjp(_chunked_scan_fwd, _chunked_scan_bwd)


@register_op("selective_scan",
             inputs=("X", "Dt", "B", "C", "ALog", "D", "DtBias"),
             attr_defaults={"chunk_size": 64, "site": ""})
def _selective_scan(ins, attrs):
    """Mamba's selective state-space scan: X [B, S, C] (the channels
    after their convolution and SiLU), Dt [B, S, C] (the step's
    pre-activation), B, C [B, S, N] (the input and output maps), ALog
    [C, N], D, DtBias [C] -> Out [B, S, C]. Delta = softplus(Dt +
    DtBias), A = -exp(ALog), a channel's state in R^N from zero:
    s_t = exp(Delta_t A) s_{t-1} + (Delta_t x_t) B_t, y_t = s_t . C_t +
    D x_t; ``_chunked_scan`` at ``chunk_size`` positions a chunk (a
    padded position has Delta 0: it neither decays nor writes)."""
    x = first(ins, "X")
    batch, s, ch = x.shape
    chunk = min(attrs.get("chunk_size", 64), s)
    u = x.astype(jnp.float32)
    dt = jax.nn.softplus(first(ins, "Dt").astype(jnp.float32)
                         + first(ins, "DtBias"))
    a = -jnp.exp(first(ins, "ALog").astype(jnp.float32))
    pad = -s % chunk
    n = (s + pad) // chunk

    def chunks(t):
        t = jnp.pad(t, ((0, 0), (0, pad), (0, 0)))
        return jnp.moveaxis(t.reshape(batch, n, chunk, -1), 1, 0)

    states = a.shape[1]
    kernels = selective_scan.use_kernels() and selective_scan._block_sizes(
        ch, states, n * chunk, chunk) is not None
    y = _chunked_scan(chunks(u), chunks(dt),
                      chunks(first(ins, "B").astype(jnp.float32)),
                      chunks(first(ins, "C").astype(jnp.float32)), a,
                      kernels)
    y = jnp.moveaxis(y, 0, 1).reshape(batch, n * chunk, ch)[:, :s]
    site = attrs.get("site", "")
    if kernels:
        _gauge("ssm_grid_steps_per_step",
               "steps of the scan's forward Pallas kernel's grid, every "
               "sequence, channel block and position block; unset where "
               "the lax.scan lowering ran", site,
               selective_scan.grid_steps(batch, ch, n * chunk, states,
                                         chunk))
    _gauge("ssm_chunks_per_step",
           "chunks of the sequence the state-space scan steps over, a "
           "sequence of the batch each", site, batch * n)
    _gauge("ssm_state_bytes",
           "bytes of scan state kept from the forward for the backward: "
           "a float32 [channels, d_state] state a chunk and sequence",
           site, batch * n * ch * states * 4)
    return out(Out=(y + first(ins, "D") * u).astype(x.dtype))


@register_op("differential_combine",
             inputs=("X", "LambdaQ1", "LambdaK1", "LambdaQ2", "LambdaK2"),
             attr_defaults={"num_groups": 1, "lambda_init": 0.0})
def _differential_combine(ins, attrs):
    """The two softmax maps' difference of differential attention. X
    [B, S, G * 2 * J * Dv]: the attention op's output with its heads
    laid out [group, c, j] (c the map, j the differential head of the
    group), the four Lambda vectors [d] -> Out [B, S, G * J * Dv] =
    X[c = 0] - lambda X[c = 1], lambda = exp(LambdaQ1 . LambdaK1) -
    exp(LambdaQ2 . LambdaK2) + ``lambda_init``."""
    x = first(ins, "X")
    lq1, lk1, lq2, lk2 = (first(ins, n).astype(jnp.float32) for n in (
        "LambdaQ1", "LambdaK1", "LambdaQ2", "LambdaK2"))
    lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) \
        + attrs.get("lambda_init", 0.0)
    maps = x.reshape(x.shape[:2] + (attrs.get("num_groups", 1), 2, -1))
    o = maps[:, :, :, 0] - lam.astype(x.dtype) * maps[:, :, :, 1]
    return out(Out=o.reshape(x.shape[:2] + (-1,)))


def _rotary_angles(x, theta):
    """cos, sin [1, S, 1.., r / 2] of the angles position x theta^(-2i /
    r) for x [B, S, .., r]; a token's position is its index in S."""
    r, s = x.shape[-1], x.shape[1]
    inv = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angle = (jnp.arange(s, dtype=jnp.float32)[:, None] * inv).reshape(
        (1, s) + (1,) * (x.ndim - 3) + (r // 2,))
    return jnp.cos(angle), jnp.sin(angle)


def interleaved_rotary(x, theta):
    """Rotary embedding over the whole last dim of x [B, S, .., r] that
    turns each pair of dims (2i, 2i + 1) by position x theta^(-2i / r)
    (``rope_interleave``); a token's position is its index in S. Float32."""
    r = x.shape[-1]
    cos, sin = _rotary_angles(x, theta)
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (r // 2, 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     -1).reshape(x.shape)


def half_split_rotary(x, theta):
    """The same turns on the pairs of dims (i, i + r / 2) (rotate-half:
    ``rope_interleave`` false). Float32."""
    cos, sin = _rotary_angles(x, theta)
    lo, hi = jnp.split(x.astype(jnp.float32), 2, -1)
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


@register_op("latent_qkv", inputs=("CQ", "CKV", "KRope", "WUQ", "WUKV"),
             attr_defaults={"num_heads": 1, "nope_dim": 128, "rope_dim": 64,
                            "v_dim": 128, "theta": 1e4,
                            "rope_interleave": True, "site": ""})
def _latent_qkv(ins, attrs):
    """Latent attention's heads (DeepSeek-V2 §2.1): CQ [B, S, Lq] and CKV
    [B, S, Lkv] the normed query and key-value latents (or, where the
    query has no latent, CQ the normed layer input), KRope [B, S, r]
    the one rotary key all heads share, WUQ [Lq, H (n + r)] (a head's
    [no-rotary n | rotary r] columns), WUKV [Lkv, H (n + v)] (a head's
    [K n | V v]) -> Q, K [B, S, H (n + r)], V [B, S, H v], the attention
    op's layout: Q = [CQ WUQ_n | rope(CQ WUQ_r)], K = [CKV WUK |
    rope(KRope)], the same rotated key in every head, V = CKV WUV.
    Rotary pairs dims (2i, 2i + 1) (``interleaved_rotary``) where
    ``rope_interleave``, else (i, i + r / 2) (``half_split_rotary``).
    The vjp sums KRope's gradient over the heads."""
    cq, ckv, kr = first(ins, "CQ"), first(ins, "CKV"), first(ins, "KRope")
    h, n, r, dv = (attrs.get("num_heads", 1), attrs.get("nope_dim", 128),
                   attrs.get("rope_dim", 64), attrs.get("v_dim", 128))
    theta = attrs.get("theta", 1e4)
    interleave = attrs.get("rope_interleave", True)
    rope = interleaved_rotary if interleave else half_split_rotary
    b, s = cq.shape[:2]
    q = _einsum("bsl,lf->bsf", cq, first(ins, "WUQ")).reshape(b, s, h, n + r)
    kv = _einsum("bsl,lf->bsf", ckv, first(ins, "WUKV")).reshape(
        b, s, h, n + dv)
    q = jnp.concatenate([q[..., :n], rope(q[..., n:], theta)], -1)
    k_rope = jnp.broadcast_to(rope(kr, theta)[:, :, None], (b, s, h, r))
    k = jnp.concatenate([kv[..., :n], k_rope], -1)
    site = attrs.get("site", "")
    _gauge("mla_heads", "heads latent attention's Q, K and V are made for",
           site, h)
    _gauge("mla_q_latent_width",
           "width of the query latent c_Q (of the layer's input where the "
           "query has no latent)", site, cq.shape[-1])
    _gauge("mla_kv_latent_width", "width of the key-value latent c_KV",
           site, ckv.shape[-1])
    _gauge("mla_rope_dim",
           "rotary dims of a head: one rotary key all heads share", site, r)
    _gauge("mla_rope_interleaved",
           "1 where rotary pairs dims (2i, 2i + 1), 0 where (i, i + r / 2)",
           site, int(interleave))
    return out(Q=q.reshape(b, s, -1).astype(cq.dtype),
               K=k.reshape(b, s, -1).astype(cq.dtype),
               V=kv[..., n:].reshape(b, s, -1).astype(cq.dtype))


# --------------------------------------------------------------------------
def _in_kept_groups(choice, groups, kept):
    """``choice`` [.., E] where its expert's group of E / ``groups`` is
    among the ``kept`` groups of largest score, -inf elsewhere; a group
    scores the sum of its two largest entries (DeepSeek-V3's
    ``noaux_tc``)."""
    by_group = choice.reshape(choice.shape[:-1] + (groups, -1))
    score = jnp.sum(lax.top_k(by_group, 2)[0], -1)
    top_groups = lax.top_k(score, kept)[1]
    keep = jnp.any(top_groups[..., :, None] == jnp.arange(groups), -2)
    return jnp.where(keep[..., None], by_group, -jnp.inf).reshape(
        choice.shape)


@register_op("moe_router", inputs=("X", "W", "Bias", "TopkIdx"),
             diff_inputs=("X", "W"),
             attr_defaults={"top_k": 1, "scoring": "softmax", "scale": 1.0,
                            "bias_update_rate": 0.0, "n_group": 1,
                            "topk_group": 1, "site": ""})
def _moe_router(ins, attrs):
    """X [.., D], W [D, E] -> TopkIdx [.., k] int32, TopkWeight [.., k]
    (the chosen experts' scores, renormalised to sum 1, times ``scale``),
    AuxLoss [1] = E * sum_e (assignments_e / tokens) * mean_t p_{t,e}.
    ``scoring``: "softmax" over the experts, or "sigmoid" of each logit
    (DeepSeek-V3's router: the k largest sigmoids, renormalised among
    themselves; p of the auxiliary loss is then the sigmoids over their
    sum). Logits and scores in float32 at the highest matmul precision
    whatever the flags say: a rounded logit changes which expert is
    chosen.

    ``n_group`` > 1 (DeepSeek-V3's ``noaux_tc``, §2.1.2): the experts
    are ``n_group`` groups in order, each scored by the sum of its two
    largest choice scores, and the k experts are chosen inside the
    ``topk_group`` best groups alone (``_in_kept_groups``); 1 is no group
    stage, the choice of all E as before.

    With Bias [E]: the k experts are chosen by scores + Bias (in the
    group stage too), the weights are the
    chosen experts' UNBIASED scores, and BiasOut [E] = Bias +
    ``bias_update_rate`` * sign(mean load - load_e) over all E experts,
    the loads counted from this step's TopkIdx: the persistable the op
    reads, written back once a step, no gradient. Its backward is handed
    the forward's TopkIdx (``_moe_router_grad``) and chooses nothing
    again: by then the bias has moved."""
    x, w = first(ins, "X"), first(ins, "W")
    k, e = attrs.get("top_k", 1), w.shape[1]
    bias = first(ins, "Bias")
    logits = jnp.matmul(x.astype(jnp.float32), w.astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    scoring = attrs.get("scoring", "softmax")
    if scoring == "softmax":
        scores = probs = jax.nn.softmax(logits, -1)
    elif scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        probs = scores / jnp.sum(scores, -1, keepdims=True)
    else:
        raise ValueError(f"moe_router: scoring {scoring!r}")
    groups = attrs.get("n_group", 1)
    if bias is None and groups == 1:
        top_p, top_i = lax.top_k(scores, k)
    else:
        top_i = first(ins, "TopkIdx")
        if top_i is None:
            choice = scores if bias is None \
                else scores + bias.astype(jnp.float32)
            if groups > 1:
                choice = _in_kept_groups(choice, groups,
                                         attrs.get("topk_group", 1))
            top_i = lax.top_k(choice, k)[1]
        top_p = jnp.take_along_axis(scores, top_i, -1)
    top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    if attrs.get("scale", 1.0) != 1.0:
        top_p = top_p * attrs["scale"]
    tokens = probs.size // e
    share = jnp.zeros((e,), jnp.float32).at[top_i.reshape(-1)].add(1.0) \
        / tokens
    aux = e * jnp.sum(share * jnp.mean(probs.reshape(tokens, e), 0))
    site = attrs.get("site", "")
    _gauge("moe_router_width",
           "experts the router scores a token over, held here or not",
           site, e)
    outs = out(TopkIdx=top_i.astype(jnp.int32), TopkWeight=top_p,
               AuxLoss=aux.reshape(1))
    if bias is not None:
        rate = attrs.get("bias_update_rate", 0.0)
        load = share * tokens
        outs.update(out(BiasOut=lax.stop_gradient(
            bias + (rate * jnp.sign(tokens * k / e - load)).astype(
                bias.dtype))))
        _gauge("moe_router_bias_rate",
               "the step of a router's choice-only bias update, "
               "gamma x sign(mean load - load); unset where the router "
               "has no bias", site, rate)
    if groups > 1:
        _gauge("moe_router_groups",
               "groups of experts a router's group stage scores; unset "
               "where it has none", site, groups)
        _gauge("moe_router_groups_kept",
               "groups a token's experts are chosen from; unset where the "
               "router has no group stage", site,
               attrs.get("topk_group", 1))
    _gauge("moe_router_biased",
           "1 where the router chooses by scores + a bias it keeps out of "
           "the gradient, 0 where by the scores alone", site,
           int(bias is not None))
    return outs


@register_grad_maker("moe_router")
def _moe_router_grad(op, grad_names):
    """The default grad op, and where the router has a Bias also the
    forward's TopkIdx among what its vjp re-runs the kernel on: the op
    wrote the next step's bias in place, so the backward must not choose
    again. A router without Bias gets the default op unchanged."""
    from ..fluid.backward import EMPTY_VAR, _default_grad_op_descs
    outputs = set(op.output_arg_names)
    grad_map = {n: g for n, g in grad_names.items()
                if n in outputs and g != EMPTY_VAR}
    req = {n for n in grad_names if n not in outputs}
    res = _default_grad_op_descs(op, grad_map, req, set())
    if res is None:
        return None
    descs = res[0]
    if op.input("Bias"):
        descs[0]["attrs"]["_fwd_in"].append("TopkIdx")
    return descs


def _ragged(rows, weights, group_sizes):
    return lax.ragged_dot(*_operands(rows, weights), group_sizes,
                          preferred_element_type=jnp.float32)


# Rows a tile of the grouped kernel, the unit ``row_bound`` rounds up to:
# a module constant beside ``attention_ops.DENSE_MAX_SEQ``, not a flag.
ROW_TILE = 512


def row_bound(tokens, k, held, num_experts):
    """Rows a pass of ``moe_expert_ffn``'s grouped products runs over,
    from shapes alone. ``full`` = tokens * min(k, held) is the most a
    no-drop layer can be sent; the layer IS sent a binomial count around
    ``expected`` = tokens * k * held / num_experts, the share of the
    router it holds. The bound is twice that, rounded up to ROW_TILE,
    and never above ``full``: 2 x is tens of sigma above the mean at a
    uniform router and well above the 1.2-1.5 x a balanced trained
    router puts on a rank. A router's width of 0 is unknown: ``full``,
    as is a layer that holds every expert."""
    full = tokens * min(k, held)
    if not num_experts:
        return full
    tiles = -(-2 * tokens * k * held // (num_experts * ROW_TILE))
    return min(full, tiles * ROW_TILE)


ACTIVATIONS = {"silu": _silu, "relu": jax.nn.relu}


def _in_window(ends, sizes, lo, bound):
    """The whole layer's group sizes (``ends`` their running sum),
    clipped to the sorted assignments lo .. lo + bound - 1."""
    return jnp.clip(jnp.minimum(ends, lo + bound)
                    - jnp.maximum(ends - sizes, lo), 0)


def _kernel_blocks(bound, x, w_gate_up):
    """The blocks of ``pallas/grouped_matmul.py``'s kernels for a pass of
    ``bound`` rows of these operands, or None where ``lax.ragged_dot``
    runs it: a backend without the kernels, a step traced under a mesh,
    a shape ``_block_sizes`` does not take."""
    if not grouped_matmul.use_kernels():
        return None
    held, d, f2 = w_gate_up.shape
    return grouped_matmul._block_sizes(bound, d, f2 // 2, held,
                                       _operands(x)[0].dtype.itemsize)


def _window(o, x, weight, w_gate_up, w_down, order, lo, sizes, k,
            activation="silu"):
    """``o`` [T, D] + what the sorted assignments lo .. lo + len(order)
    - 1 give: ``order`` their indices into the T*k assignments, ``sizes``
    [held] the whole layer's group sizes, clipped here to the window;
    ``activation`` of the gate's half, a name of ``ACTIVATIONS``. Two
    lowerings of the one pass: the Pallas kernels where
    ``_kernel_blocks`` gives them blocks, which bring their own vjp,
    else ``lax.ragged_dot`` between masks, differentiated by JAX."""
    if _kernel_blocks(order.shape[0], x, w_gate_up):
        return _window_kernels(o, x, weight, w_gate_up, w_down, order, lo,
                               sizes, k, activation)
    # the operands' cast before the gather: the same values, half the rows'
    # bytes
    x, w_gate_up, w_down = _operands(x, w_gate_up, w_down)
    bound, f = order.shape[0], w_down.shape[1]
    ends = jnp.cumsum(sizes)
    valid = (lo + jnp.arange(bound) < ends[-1])[:, None]
    in_window = _in_window(ends, sizes, lo, bound)
    token = order // k
    x_rows = jnp.where(valid, x[token], 0)
    h = jnp.where(valid, _ragged(x_rows, w_gate_up, in_window), 0.0)
    act = ACTIVATIONS[activation](h[:, :f]) * h[:, f:]
    y = jnp.where(valid, _ragged(act, w_down, in_window), 0.0) \
        * weight[order][:, None]
    return o.at[token].add(y)


def _kernel_rows(x, weight, w_gate_up, order, lo, sizes, k):
    """What both ways of a kernel pass start from: its blocks, the
    sorted assignments padded to whole row tiles (a padded row lies past
    every group), their tokens, and the operands of
    ``grouped_matmul.forward``'s rows, routing weights and sizes."""
    bound = order.shape[0]
    blocks = _kernel_blocks(bound, x, w_gate_up)
    order = jnp.pad(order, (0, -bound % blocks.tm))
    token = order // k
    return blocks, order, token, (x[token], weight[order]), \
        _in_window(jnp.cumsum(sizes), sizes, lo, bound)


def _add_rows(o, y, token, sizes):
    """``o`` plus a kernel pass's rows ``y`` at their tokens, the rows
    of the groups ``sizes`` alone (the rest are zero): the combine
    kernel, which gives what ``o.at[token].add(y)`` gives."""
    return grouped_matmul.combine(o, y, token, sizes,
                                  grouped_matmul._combine_blocks(
                                      *o.shape, y.shape[0], sizes.shape[0]))


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def _window_kernels(o, x, weight, w_gate_up, w_down, order, lo, sizes, k,
                    activation):
    """``_window`` on the kernels of ``pallas/grouped_matmul.py``: no
    mask (the kernels walk the routed row tiles and write zero past the
    last group), the activation and the routing weight in the products'
    epilogues. Its own vjp, ``_window_kernels_bwd``."""
    x, w_gate_up, w_down = _operands(x, w_gate_up, w_down)
    blocks, _, token, rows, in_window = _kernel_rows(
        x, weight, w_gate_up, order, lo, sizes, k)
    return _add_rows(o, grouped_matmul.forward(
        *rows, w_gate_up, w_down, in_window, activation, blocks), token,
        in_window)


def _window_kernels_fwd(o, x, weight, w_gate_up, w_down, order, lo, sizes,
                        k, activation):
    return (_window_kernels(o, x, weight, w_gate_up, w_down, order, lo,
                            sizes, k, activation),
            (x, weight, w_gate_up, w_down, order, lo, sizes))


def _window_kernels_back(sums, x, weight, w_gate_up, w_down, order, lo,
                         sizes, k, activation, g):
    """``sums`` = the gradients of (x, weight, w_gate_up, w_down) so
    far, plus this pass's: x, w_gate_up, w_down the MXU's operands
    (``_operands``), ``g`` [T, D] the output's gradient as one. The
    rows' and routing weights' sums are float32; the weights' are kept
    as their operands are (as the other lowering's: they stay alive
    until the optimizer reads them) and added where they lie
    (``grouped_matmul.backward``): a pass writes the experts it holds."""
    blocks, order, token, rows, in_window = _kernel_rows(
        x, weight, w_gate_up, order, lo, sizes, k)
    d_rows, d_row_weight, d_w_gate_up, d_w_down = grouped_matmul.backward(
        *rows, w_gate_up, w_down, in_window, activation, blocks, g[token],
        sums[2:])
    return (_add_rows(sums[0], d_rows, token, in_window),
            sums[1].at[order].add(d_row_weight), d_w_gate_up, d_w_down)


def _float32_zeros(*xs):
    return tuple(jnp.zeros(x.shape, jnp.float32) for x in xs)


def _window_kernels_bwd(k, activation, residuals, d_out):
    *primals, order, lo, sizes = residuals
    x, w_gate_up, w_down = _operands(primals[0], *primals[2:])
    # no weights' gradient so far: the kernels write this pass's whole
    sums = _window_kernels_back(
        _float32_zeros(*primals[:2]) + (None, None), x, primals[1],
        w_gate_up, w_down, order, lo, sizes, k, activation,
        d_out.astype(x.dtype))
    return (d_out,) + tuple(s.astype(p.dtype) for s, p in zip(
        sums, primals)) + (None, None, None)


_window_kernels.defvjp(_window_kernels_fwd, _window_kernels_bwd)


def _passes_run(sizes, bound):
    return -(-jnp.sum(sizes) // bound)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _windows(x, weight, w_gate_up, w_down, order, sizes, k, activation):
    """The windows ``order`` [passes, bound] of ``_window``, as many as
    hold a routed assignment, one after the other: a loop of that many
    trips, so a window past the routed count costs nothing. Its own
    vjp: JAX differentiates no loop of a traced number of trips, and
    through a scan of conds it stacks every pass's residuals, the
    weights with them (3.99 -> 9.69 GB of temporaries at the Qwen
    cell's sizes). The backward runs the same trips, each the vjp of
    one window made anew, and sums them."""
    zero = jnp.zeros(x.shape, jnp.float32)
    # the weights' cast before the passes: once, not a pass
    x, w_gate_up, w_down = _operands(x, w_gate_up, w_down)
    return lax.fori_loop(
        0, _passes_run(sizes, order.shape[1]),
        lambda p, o: _window(o, x, weight, w_gate_up, w_down, order[p],
                             p * order.shape[1], sizes, k, activation), zero)


def _windows_fwd(x, weight, w_gate_up, w_down, order, sizes, k, activation):
    return (_windows(x, weight, w_gate_up, w_down, order, sizes, k,
                     activation),
            (x, weight, w_gate_up, w_down, order, sizes))


def _windows_bwd(k, activation, residuals, d_out):
    *primals, order, sizes = residuals
    x, w_gate_up, w_down = _operands(primals[0], *primals[2:])
    bound = order.shape[1]
    if _kernel_blocks(bound, x, w_gate_up):
        g = d_out.astype(x.dtype)

        def one_pass(p, sums):
            return _window_kernels_back(
                sums, x, primals[1], w_gate_up, w_down, order[p], p * bound,
                sizes, k, activation, g)

        sums = _float32_zeros(*primals[:2]) + (
            jnp.zeros_like(w_gate_up), jnp.zeros_like(w_down))
    else:
        operands = (x, primals[1], w_gate_up, w_down)
        zero = jnp.zeros(x.shape, jnp.float32)

        def one_pass(p, sums):
            _, vjp = jax.vjp(
                lambda *args: _window(zero, *args, order[p], p * bound,
                                      sizes, k, activation), *operands)
            return tuple(map(jnp.add, sums, vjp(d_out)))

        # summed as a window's gradient comes, in its operand's dtype: an
        # expert's rows lie side by side, so all but the two windows its
        # group may straddle add exact zeros to its weights' gradient
        sums = tuple(map(jnp.zeros_like, operands))
    sums = lax.fori_loop(0, _passes_run(sizes, bound), one_pass, sums)
    return tuple(s.astype(p.dtype) for s, p in zip(sums, primals)) \
        + (None, None)


_windows.defvjp(_windows_fwd, _windows_bwd)


@register_op("moe_expert_ffn",
             inputs=("X", "TopkIdx", "TopkWeight", "WGateUp", "WDown"),
             diff_inputs=("X", "TopkWeight", "WGateUp", "WDown"),
             attr_defaults={"expert_start": 0, "num_experts": 0, "site": "",
                            "activation": "silu"})
def _moe_expert_ffn(ins, attrs):
    """The held experts' part of a routed gated FFN, no assignment
    dropped: Out[t] = sum over the k experts e chosen for token t that
    are held here, ``expert_start <= e < expert_start + held``, of
    TopkWeight[t, e] * (act(x W_g,e) * x W_u,e) W_d,e, act the
    ``activation`` attr: "silu" (SwiGLU experts) or "relu" (ReGLU
    ones); any other name raises. WGateUp [held, D, 2F] (gate then up),
    WDown [held, F, D]; what the experts held elsewhere would add is
    left out. Passes [1] int32: the passes that ran (below), 1 wherever
    the routing fits the bound.

    Static shapes: the T*k assignments are sorted by held expert (those
    of absent experts last); the first T * min(k, held) of them are the
    most that can be held here. They are cut in windows of ``row_bound``
    rows (twice the share of the router's width ``num_experts`` that is
    held; all of them where the width is not given), and a window is the
    rows of one grouped product an expert's projection
    (``lax.ragged_dot``, each group's size clipped to the window). Only
    the windows that hold a routed assignment run, so a step whose
    routing fits runs ONE pass of ``row_bound`` rows and an overflow is
    exact: it costs a further pass a window, never an assignment. Rows
    past the last group are masked on both sides of each product: what a
    grouped product leaves there is not defined."""
    x, idx, weight = (first(ins, "X"), first(ins, "TopkIdx"),
                      first(ins, "TopkWeight"))
    w_gate_up, w_down = first(ins, "WGateUp"), first(ins, "WDown")
    activation = attrs.get("activation", "silu")
    if activation not in ACTIVATIONS:
        raise ValueError(f"moe_expert_ffn: activation {activation!r}")
    held, d = w_gate_up.shape[0], x.shape[-1]
    k = idx.shape[-1]
    tokens = idx.size // k
    local = idx.reshape(-1) - attrs.get("expert_start", 0)
    key = jnp.where((local >= 0) & (local < held), local, held)
    full = tokens * min(k, held)
    bound = row_bound(tokens, k, held, attrs.get("num_experts", 0))
    passes = -(-full // bound)
    order = jnp.argsort(key, stable=True)[:full]
    sizes = jnp.zeros((held + 1,), jnp.int32).at[key].add(1)[:held]
    operands = (x.reshape(tokens, d), weight.reshape(-1), w_gate_up, w_down)
    if passes == 1:
        o = _window(jnp.zeros((tokens, d), jnp.float32), *operands, order,
                    0, sizes, k, activation)
    else:
        order = jnp.pad(order, (0, passes * bound - full))
        o = _windows(*operands, order.reshape(passes, bound), sizes, k,
                     activation)
    site = attrs.get("site", "")
    _gauge("moe_experts_held", "experts whose weights the layer holds",
           site, held)
    _gauge("moe_rows_per_step",
           "rows the grouped expert products run over in a step whose "
           "routing fits one pass: row_bound, twice the held share of "
           "the router rounded up to ROW_TILE, at most tokens x min(k, "
           "experts held)", site, bound)
    _gauge("moe_row_passes_max",
           "passes of moe_rows_per_step rows the most a no-drop layer "
           "can be sent would take", site, passes)
    blocks = _kernel_blocks(bound, operands[0], w_gate_up)
    if blocks:
        _gauge("moe_row_tile",
               "rows a tile of the expert products' Pallas kernels, chosen "
               "from the rows a held expert is expected to see; unset where "
               "lax.ragged_dot ran", site, blocks.tm)
        _gauge("moe_grid_row_tiles_per_step",
               "row tiles the grids of the expert products' Pallas kernels "
               "span over moe_row_passes_max passes: the most a routing "
               "can make live; unset where lax.ragged_dot ran", site,
               passes * -(-bound // blocks.tm))
    _gauge("moe_combine_kernel",
           "1 where the combine kernel adds the expert passes' rows back "
           "into token order (beside the Pallas products), 0 where XLA's "
           "scatter-add does (beside lax.ragged_dot)", site, int(bool(blocks)))
    _gauge("moe_activation_relu",
           "1 where the experts' gate is ReLU (ReGLU), 0 where SiLU",
           site, int(activation == "relu"))
    return out(Out=o.reshape(x.shape).astype(x.dtype),
               Passes=jnp.maximum(1, _passes_run(sizes, bound))
               .astype(jnp.int32).reshape(1))
