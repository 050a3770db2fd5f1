"""Pallas TPU kernels of the delta rule's chunk recurrence

    T = (I + L)^-1,  u = T (beta V),  w = T (beta K e^G),
    v_new = u - w S,  o = q_in S + qk v_new,  S <- Diag(d) S + k_out^T v_new,

``ops/decoder_ops._chunked_delta_rule``'s second lowering, for Gated
DeltaNet and Kimi delta attention alike: the kernels are given only what
both decay forms share, a chunk of C positions at a time (L strictly
lower and qk lower, [C, C]; beta V, beta K e^G, q e^G and K e^(G_C - G),
[C, d]; the state's decay over the chunk, a key channel each, a scalar
decay broadcast), and have no branch on the form.

Forward: a grid step holds ``hb`` heads x ``nc`` chunks. It first
inverts all of their systems at once (no chunk's inverse depends on the
state): forward substitution inside the BASE x BASE blocks on the
diagonal, a column of every block of every chunk a trip of one loop,
then blocked merges T21 = -T22 L21 T11 on the MXU, each level a product
pair over the whole [C, C] with L masked to the blocks it joins. The
powers of the strictly lower part are never formed: for keys nearly
parallel they reach C(64, 32) where T stays O(1). Then it walks its
chunks in order, the heads' states float32 in VMEM scratch across the
grid's chunk axis, and writes o, T and the state at each chunk's START,
which is what the backward keeps. The state is held transposed, [dv,
dk], so that the decay, a key channel each, scales its lanes.

Backward: the chunks last to first, carrying the state's gradient in
VMEM; each chunk's v_new is made again from its start state, and every
cotangent is an MXU product; those through T (T^T du, T^T dw and dL =
-T^T (du (beta V)^T + dw (beta K e^G)^T) T^T) are taken for all of a
step's chunks at once after the walk.

Precision is the ``lax.scan`` lowering's, product by product: the six
products it takes through ``decoder_ops._einsum`` take operands of the
dtype the caller gives qk, beta V, beta K e^G, q e^G and K e^(G_C - G)
in (bf16 where ``decoder_ops._operands`` makes them so, float32
otherwise) and accumulate in float32; L, T, the state and its decay are
float32, and the inverse's products run at the highest precision, as the
triangular solve and its gradient do.

Grid: (heads / hb, chunks / nc), the second axis sequential. The loops
walk chunks and diagonal columns without unrolling them: a kernel body
is traced at every start of a program, cache or not (PERF.md §6, the
selective scan's kernels).
Which backend runs the kernels is ``use_kernels``'s rule; a step traced
under a device mesh keeps the ``lax.scan`` lowering (XLA does not
partition a Mosaic kernel).
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import flash_attention as fa

# Rows of the blocks on the diagonal that the inverse substitutes in; a
# chunk is BASE x 2^k positions (or shorter than BASE: one block).
BASE = 16
# Heads and chunks a grid step holds, at most.
MAX_HEADS = 4
MAX_CHUNKS = 8

_HIGHEST = lax.Precision.HIGHEST
_BLOCK_OVERRIDE = None  # (hb, nc) set by block_override()


@contextlib.contextmanager
def block_override(hb, nc):
    """Pin both kernels' (heads, chunks) a grid step inside the context:
    the sweep on the chip (tools/delta_paths.py) and the tests. It
    applies to the backward too, so wrap the whole grad computation."""
    global _BLOCK_OVERRIDE
    prev = _BLOCK_OVERRIDE
    _BLOCK_OVERRIDE = (int(hb), int(nc))
    try:
        yield
    finally:
        _BLOCK_OVERRIDE = prev


def use_kernels() -> bool:
    """The backends the flash kernels run on, off a device mesh."""
    return fa._use_kernels() and fa._MESH is None


def _tile(rows, cols, itemsize):
    """Bytes of VMEM a [rows, cols] block takes: whole (8, 128) tiles of
    32-bit words, (16, 128) of 16-bit ones."""
    sub = 8 * 4 // itemsize
    return -(-rows // sub) * sub * -(-cols // fa.LANES) * fa.LANES * itemsize


def _working_set(kernel, hb, nc, c, dk, dv, itemsize):
    """Bytes of VMEM a grid step holds: every piped block twice, the
    scratch, and the larger temporaries of the body, a chunk each; half
    as much again for float32 operands, which reach the MXU in bf16
    pieces (``fa._working_set``'s rule)."""
    square, square_op = _tile(c, c, 4), _tile(c, c, itemsize)
    rows_k, rows_v = _tile(c, dk, itemsize), _tile(c, dv, itemsize)
    state, decay = _tile(dv, dk, 4), _tile(1, dk, 4)
    ins = square + square_op + rows_v + 3 * rows_k + decay
    if kernel == "delta_fwd":
        piped = ins + _tile(c, dv, 4) + square + state
        scratch = _tile(c, dv, 4) + rows_k + square
        temps = 6 * square
    else:
        piped = ins + state + _tile(c, dv, 4) + square + square_op \
            + rows_v + 3 * rows_k + decay
        scratch = 2 * _tile(c, dv, 4) + rows_k + _tile(c, dk, 4)
        temps = 4 * square + 2 * _tile(c, dv, 4)
    items = hb * nc
    total = 2 * items * piped + items * (scratch + temps) + hb * state
    return total if itemsize <= 2 else total * 3 // 2


def _divisors(n, most):
    return [d for d in range(min(n, most), 0, -1) if n % d == 0]


def block_sizes(heads, chunks, c, dk, dv, itemsize):
    """(hb, nc) of both kernels for a call of ``heads`` sequences-and-
    heads, ``chunks`` chunks of ``c`` positions each and these widths,
    or None where the kernels do not serve it (a chunk that is not BASE x
    2^k positions, or one whose blocks fit no VMEM budget): the most
    heads, then the most chunks, that divide the call, ``MAX_HEADS`` and
    ``MAX_CHUNKS`` at most, whose working set fits ``fa.VMEM_BUDGET``.
    Inside ``block_override`` the pinned pair wins."""
    base = min(BASE, c)
    levels = c // base
    if c % base or levels & (levels - 1):
        return None
    if _BLOCK_OVERRIDE:
        hb, nc = _BLOCK_OVERRIDE
        if heads % hb or chunks % nc:
            raise ValueError(f"pinned blocks {hb} x {nc} do not divide "
                             f"{heads} heads x {chunks} chunks")
        return _BLOCK_OVERRIDE
    for hb in _divisors(heads, MAX_HEADS):
        for nc in _divisors(chunks, MAX_CHUNKS):
            if _working_set("delta_bwd", hb, nc, c, dk, dv,
                            itemsize) <= fa.VMEM_BUDGET:
                return hb, nc
    return None


def grid_steps(heads, chunks, blocks):
    """Steps of the forward kernel's grid for a call of this shape."""
    hb, nc = blocks
    return heads // hb * (chunks // nc)


def _params(kernel, hb, nc, c, dk, dv, itemsize):
    need = int(_working_set(kernel, hb, nc, c, dk, dv, itemsize)
               * fa.VMEM_HEADROOM)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=need if need > fa.SCOPED_VMEM_DEFAULT else None)


# --------------------------------------------------------------------------
def _mm(a, b, contract, precision=None):
    """A product over the leading batch dim of a and b, contracting
    ``contract`` = (a's dim, b's dim), accumulated in float32."""
    return lax.dot_general(a, b, (((contract[0],), (contract[1],)), ((0,), (0,))),
                           precision=precision,
                           preferred_element_type=jnp.float32)


def _nn(a, b, precision=None):   # a b
    return _mm(a, b, (2, 1), precision)


def _nt(a, b, precision=None):   # a b^T
    return _mm(a, b, (2, 2), precision)


def _tn(a, b, precision=None):   # a^T b
    return _mm(a, b, (1, 1), precision)


def _inverse(l, t_scr):
    """T = (I + l)^-1 of strictly lower l [N, C, C], float32, in the
    scratch ``t_scr`` [N, C / base, base, C] and returned [N, C, C]."""
    n, c, _ = l.shape
    base = t_scr.shape[2]
    row = lax.broadcasted_iota(jnp.int32, l.shape, 1)
    col = lax.broadcasted_iota(jnp.int32, l.shape, 2)
    first = row - row % base  # the first row of a row's diagonal block
    t_scr[...] = jnp.where(row == col, 1.0, 0.0).reshape(t_scr.shape)

    def column(s, carry):
        # T <- T - l[:, j] T[j, :] for j the s-th column of each diagonal
        # block: row j is final (its columns before j went before), and
        # the row a row r reads, T[first(r) + s], is zero outside r's block
        picked = jnp.sum(jnp.where(col == first + s, l, 0.0), -1,
                         keepdims=True)
        rows = jnp.broadcast_to(t_scr[:, :, pl.ds(s, 1), :], t_scr.shape)
        t_scr[...] = t_scr[...] - picked.reshape(n, c // base, base, 1) * rows
        return carry

    lax.fori_loop(0, base - 1, column, 0)
    t = t_scr[...].reshape(n, c, c)
    size = base
    while size < c:  # join blocks of ``size``: T21 = -T22 L21 T11
        halves = (n, c // (2 * size), 2, size, c)
        joined = (row // (2 * size) == col // (2 * size)) \
            & (row // size != col // size)

        def lower(x):  # the rows of each joined block's second half
            return x.reshape(halves)[:, :, 1].reshape(n, c // 2, c)

        def upper_zero(x):  # [n, c / 2, c] back into those rows, 0 above
            x = x.reshape(n, c // (2 * size), 1, size, c)
            return jnp.concatenate([jnp.zeros_like(x), x], 2).reshape(n, c, c)

        l21_t11 = upper_zero(_nn(lower(jnp.where(joined, l, 0.0)), t,
                                 _HIGHEST))
        t_lower = lower(t)
        t = jnp.concatenate(
            [t.reshape(halves)[:, :, :1],
             (t_lower - _nn(t_lower, l21_t11, _HIGHEST)).reshape(
                 n, c // (2 * size), 1, size, c)], 2).reshape(n, c, c)
        size *= 2
    return t


def _fwd_kernel(l_ref, qk_ref, bv_ref, bk_ref, qi_ref, ko_ref, d_ref,
                o_ref, t_ref, starts_ref, state, u_scr, w_scr, t_scr):
    hb, nc, c, _ = l_ref.shape
    n, op = hb * nc, qk_ref.dtype

    @pl.when(pl.program_id(1) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    t = _inverse(l_ref[...].reshape(n, c, c), t_scr)
    t_ref[...] = t.reshape(t_ref.shape)
    t = t.astype(op)
    u_scr[...] = _nn(t, bv_ref[...].reshape(n, c, -1)).reshape(u_scr.shape)
    w_scr[...] = _nn(t, bk_ref[...].reshape(n, c, -1)).astype(op).reshape(
        w_scr.shape)

    def chunk(i, z):  # z [hb, dv, dk]: the state before chunk i, transposed
        starts_ref[:, i] = z
        zm = z.astype(op)
        v_new = u_scr[:, i] - _nt(w_scr[:, i], zm)
        vm = v_new.astype(op)
        o_ref[:, i] = _nt(qi_ref[:, i], zm) + _nn(qk_ref[:, i], vm)
        return z * d_ref[:, i] + _tn(vm, ko_ref[:, i])

    state[...] = lax.fori_loop(0, nc, chunk, state[...])


def _bwd_kernel(t_ref, qk_ref, bv_ref, bk_ref, qi_ref, ko_ref, d_ref,
                starts_ref, do_ref, dl_ref, dqk_ref, dbv_ref, dbk_ref,
                dqi_ref, dko_ref, dd_ref, d_state, u_scr, w_scr, du_scr,
                dw_scr):
    hb, nc, c, _ = t_ref.shape
    n, op = hb * nc, qk_ref.dtype

    @pl.when(pl.program_id(1) == 0)
    def _():
        d_state[...] = jnp.zeros_like(d_state)

    t = t_ref[...].reshape(n, c, c)
    tm = t.astype(op)
    bv, bk = bv_ref[...].reshape(n, c, -1), bk_ref[...].reshape(n, c, -1)
    u_scr[...] = _nn(tm, bv).reshape(u_scr.shape)
    w_scr[...] = _nn(tm, bk).astype(op).reshape(w_scr.shape)
    lower = lax.broadcasted_iota(jnp.int32, (hb, c, c), 1) \
        >= lax.broadcasted_iota(jnp.int32, (hb, c, c), 2)

    def chunk(j, dz):  # dz: the gradient of the state after chunk i
        i = nc - 1 - j
        z = starts_ref[:, i]
        zm, dzm = z.astype(op), dz.astype(op)
        w = w_scr[:, i]
        vm = (u_scr[:, i] - _nt(w, zm)).astype(op)
        do = do_ref[:, i].astype(op)
        d_v_new = _tn(qk_ref[:, i], do) + _nt(ko_ref[:, i], dzm)
        dvm = d_v_new.astype(op)
        dqk_ref[:, i] = jnp.where(lower, _nt(do, vm), 0.0).astype(
            dqk_ref.dtype)
        dko_ref[:, i] = _nn(vm, dzm).astype(dko_ref.dtype)
        dqi_ref[:, i] = _nn(do, zm).astype(dqi_ref.dtype)
        dd_ref[:, i] = jnp.sum(z * dz, 1, keepdims=True)
        du_scr[:, i] = d_v_new
        dw_scr[:, i] = -_nn(dvm, zm)
        return dz * d_ref[:, i] + _tn(do, qi_ref[:, i]) - _tn(dvm, w)

    d_state[...] = lax.fori_loop(0, nc, chunk, d_state[...])
    du = du_scr[...].reshape(n, c, -1).astype(op)
    dw = dw_scr[...].reshape(n, c, -1).astype(op)
    dbv_ref[...] = _tn(tm, du).astype(dbv_ref.dtype).reshape(dbv_ref.shape)
    dbk_ref[...] = _tn(tm, dw).astype(dbk_ref.dtype).reshape(dbk_ref.shape)
    p = _nt(du, bv) + _nt(dw, bk)
    dl = -_nt(_tn(t, p, _HIGHEST), t, _HIGHEST)
    strictly = lax.broadcasted_iota(jnp.int32, dl.shape, 1) \
        > lax.broadcasted_iota(jnp.int32, dl.shape, 2)
    dl_ref[...] = jnp.where(strictly, dl, 0.0).reshape(dl_ref.shape)


# --------------------------------------------------------------------------
def _spec(hb, nc, rows, cols, chunk_block):
    return pl.BlockSpec((hb, nc, rows, cols),
                        lambda i, j: (i, chunk_block(j), 0, 0))


@functools.partial(jax.jit, static_argnames=("blocks", "interpret"))
def _forward(l, qk, bv, bk, qi, ko, decay, blocks, interpret):
    (hb, nc), (heads, chunks, c, _) = blocks, l.shape
    dk, dv, op = qi.shape[-1], bv.shape[-1], qk.dtype
    def spec(rows, cols):
        return _spec(hb, nc, rows, cols, lambda j: j)

    square, rows_v, rows_k = spec(c, c), spec(c, dv), spec(c, dk)
    base = min(BASE, c)
    return pl.pallas_call(
        _fwd_kernel,
        out_shape=(jax.ShapeDtypeStruct(bv.shape, jnp.float32),
                   jax.ShapeDtypeStruct(l.shape, jnp.float32),
                   jax.ShapeDtypeStruct((heads, chunks, dv, dk),
                                        jnp.float32)),
        grid=(heads // hb, chunks // nc),
        in_specs=[square, square, rows_v, rows_k, rows_k, rows_k,
                  spec(1, dk)],
        out_specs=(rows_v, square, spec(dv, dk)),
        scratch_shapes=[pltpu.VMEM((hb, dv, dk), jnp.float32),
                        pltpu.VMEM((hb, nc, c, dv), jnp.float32),
                        pltpu.VMEM((hb, nc, c, dk), op),
                        pltpu.VMEM((hb * nc, c // base, base, c),
                                   jnp.float32)],
        compiler_params=_params("delta_fwd", hb, nc, c, dk, dv,
                                jnp.dtype(op).itemsize),
        interpret=interpret,
        name="delta_fwd",
    )(l, qk, bv, bk, qi, ko, decay)


@functools.partial(jax.jit, static_argnames=("blocks", "interpret"))
def _backward(t, qk, bv, bk, qi, ko, decay, starts, d_o, blocks, interpret):
    (hb, nc), (heads, chunks, c, _) = blocks, t.shape
    dk, dv, op = qi.shape[-1], bv.shape[-1], qk.dtype
    steps = chunks // nc

    def spec(rows, cols):
        return _spec(hb, nc, rows, cols, lambda j: steps - 1 - j)

    square, rows_v, rows_k = spec(c, c), spec(c, dv), spec(c, dk)
    decay_spec = spec(1, dk)
    return pl.pallas_call(
        _bwd_kernel,
        out_shape=tuple(jax.ShapeDtypeStruct(x.shape, x.dtype)
                        for x in (t, qk, bv, bk, qi, ko, decay)),
        grid=(heads // hb, steps),
        in_specs=[square, square, rows_v, rows_k, rows_k, rows_k, decay_spec,
                  spec(dv, dk), rows_v],
        out_specs=(square, square, rows_v, rows_k, rows_k, rows_k,
                   decay_spec),
        scratch_shapes=[pltpu.VMEM((hb, dv, dk), jnp.float32),
                        pltpu.VMEM((hb, nc, c, dv), jnp.float32),
                        pltpu.VMEM((hb, nc, c, dk), op),
                        pltpu.VMEM((hb, nc, c, dv), jnp.float32),
                        pltpu.VMEM((hb, nc, c, dk), jnp.float32)],
        compiler_params=_params("delta_bwd", hb, nc, c, dk, dv,
                                jnp.dtype(op).itemsize),
        interpret=interpret,
        name="delta_bwd",
    )(t, qk, bv, bk, qi, ko, decay, starts, d_o)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _recurrence(l, qk, bv, bk, qi, ko, decay, static):
    return _forward(l, qk, bv, bk, qi, ko, decay, *static)[0]


def _recurrence_fwd(l, qk, bv, bk, qi, ko, decay, static):
    o, t, starts = _forward(l, qk, bv, bk, qi, ko, decay, *static)
    return o, (t, qk, bv, bk, qi, ko, decay, starts)


def _recurrence_bwd(static, residuals, d_o):
    return _backward(*residuals, d_o, *static)


_recurrence.defvjp(_recurrence_fwd, _recurrence_bwd)


def recurrence(l, qk, bv, bk, qi, ko, decay, blocks):
    """The chunk recurrence over [B, H, n, ...] chunks: l (float32) and
    qk [.., C, C], bv [.., C, dv], bk, qi, ko [.., C, dk] (qk to ko in
    the products' operand dtype), decay [B, H, n, dk] (float32) -> o [B,
    H, n, C, dv], float32, from a zero state; ``blocks`` the (hb, nc)
    of ``block_sizes``. Differentiable: its own vjp, the backward
    kernel, keeps T and the chunks' start states."""
    lead = l.shape[:2]

    def flat(x):
        return x.reshape((-1,) + x.shape[2:])

    static = (tuple(blocks), fa._INTERPRET and not fa._on_tpu())
    o = _recurrence(*map(flat, (l, qk, bv, bk, qi, ko)),
                    flat(decay)[:, :, None, :], static)
    return o.reshape(lead + o.shape[1:])
