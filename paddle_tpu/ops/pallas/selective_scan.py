"""Pallas TPU kernels of the diagonal state-space recurrence

    s_t = exp(dt_t a) s_{t-1} + (dt_t u_t) b_t,    y_t = s_t . c_t,

``ops/decoder_ops._chunked_scan``'s second lowering. A channel block's
state is a float32 [N, cb] VMEM scratch (the state dimension N on
sublanes, channels on the 128 lanes) that never leaves the chip between
positions: a grid step reads T positions of u, dt [T, cb] and of b, c
(transposed a group of GROUP positions, [T / GROUP, N, GROUP]: a
position's column broadcasts over the lanes), walks them one by one, a
group a trip of its loop, and writes y [T, cb] and the state at each
chunk's START, which is all the backward keeps. The decay exp(dt_t a)
and the write (dt_t u_t) b_t exist a position at a time, in registers.
The loops are not unrolled over T: a step's kernel calls are traced and
lowered at every start, cache or not (64 positions unrolled cost the Phi
cell 8 s of warm set-up; PERF.md §6, PR 37).

The backward kernel walks the position blocks last to first: from a
block's start state it makes the block's T states again into VMEM, then
walks t = T-1 .. 0 carrying the state's gradient, and emits d_u, d_dt
[T, cb], a channel block's part of d_b, d_c (summed over the blocks
outside) and d_a [N, cb], accumulated in scratch and written at
the last step.

Grid: (batch, channels / cb, positions / T); the last axis is
sequential and carries the scratch. Float32 throughout; the recurrence
position by position, as the ``lax.scan`` lowering computes it (only
the order in which a position's N products are summed into y_t differs).

Which backend runs the kernels is ``use_kernels``'s rule. Mosaic kernels
are not partitioned by XLA, and no program runs a scan on a mesh today:
a step traced under a device mesh keeps the ``lax.scan`` lowering.
"""
from __future__ import annotations

import contextlib
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import flash_attention as fa

# 128-lane tiles of channels a block, at most: the fastest width measured
# on the v5e (tools/scan_paths.py at the Phi cell's call, the backward
# kernel: 8.44 ms at 256 channels, 4.56 at 512, 3.90 at 640, 2.98 at
# 1024, 2.93 at 1280, 3.14 at 2560, 3.60 at 5120; PERF.md §6, PR 37). A
# position's fixed costs, the columns of b and c and the lane reductions
# of their gradients, are paid a block; past ten tiles the [N, cb]
# values the loop carries spill.
MAX_CHANNEL_TILES = 10
# Positions a grid step walks, at most: the backward keeps that many
# recomputed states in VMEM (128 or 256 measured no faster).
MAX_POSITIONS = 64

_BLOCK_OVERRIDE = None  # (cb, T) set by block_override()


@contextlib.contextmanager
def block_override(cb, t):
    """Pin both kernels' blocks inside the context (T a whole number of
    chunks): the sweep on the chip (tools/scan_paths.py) and the tests.
    It applies to the backward too, so wrap the whole grad computation."""
    global _BLOCK_OVERRIDE
    prev = _BLOCK_OVERRIDE
    _BLOCK_OVERRIDE = (int(cb), int(t))
    try:
        yield
    finally:
        _BLOCK_OVERRIDE = prev


def use_kernels() -> bool:
    """The backends the flash kernels run on, off a device mesh."""
    return fa._use_kernels() and fa._MESH is None


def _working_set(kernel, cb, t, n, chunk):
    """Bytes of VMEM a grid step holds: every piped block twice, the
    scratch, and the backward's T recomputed states."""
    rows, cols = t * cb * 4, max(n, 8) * fa.LANES * 4
    state = max(n, 8) * cb * 4
    if kernel == "ssm_scan_fwd":
        piped = 3 * rows + 2 * cols + state * (1 + t // chunk)
        return 2 * piped + state
    piped = 5 * rows + 4 * cols + 3 * state
    return 2 * piped + (2 + t) * state


def _block_sizes(channels, n, positions, chunk):
    """(cb, T) of both kernels for a call of this shape, or None where
    no block fits (a chunk too long to keep its states in VMEM): the
    widest channel block of whole 128-lane tiles, ``MAX_CHANNEL_TILES``
    at most, that divides the channels padded to whole tiles, and as
    many whole chunks a grid step as ``MAX_POSITIONS`` and the sequence
    hold, a multiple of the 8 sublanes. Inside ``block_override`` the
    pinned pair wins."""
    if _BLOCK_OVERRIDE:
        return _BLOCK_OVERRIDE
    t = chunk
    while t % 8:
        t += chunk
    t *= max(1, min(MAX_POSITIONS, positions) // t)
    tiles = -(-channels // fa.LANES)
    for width in range(min(tiles, MAX_CHANNEL_TILES), 0, -1):
        if tiles % width == 0 and _working_set(
                "ssm_scan_bwd", width * fa.LANES, t, n,
                chunk) <= fa.VMEM_BUDGET:
            return width * fa.LANES, t
    return None


def grid_steps(batch, channels, positions, n, chunk):
    """Steps of the forward kernel's grid for a call of this shape."""
    cb, t = _block_sizes(channels, n, positions, chunk)
    return batch * (-(-channels // cb)) * (-(-positions // t))


def _params(kernel, cb, t, n, chunk):
    need = int(_working_set(kernel, cb, t, n, chunk) * fa.VMEM_HEADROOM)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=need if need > fa.SCOPED_VMEM_DEFAULT else None)


# --------------------------------------------------------------------------
GROUP = 8  # positions a trip of the kernels' loops walks: a sublane tile


def _rows_of(g):
    """Group ``g``'s rows of a [1, T, cb] ref: a sublane tile."""
    return pl.ds(pl.multiple_of(g * GROUP, GROUP), GROUP)


def _advance(s, a, dt, write, b, k):
    """The state after position ``k`` of a group: dt, write [GROUP, cb]
    the step and dt u, b [N, GROUP] the input map, a [N, cb]."""
    return jnp.exp(dt[k:k + 1] * a) * s + write[k:k + 1] * b[:, k:k + 1]


def _fwd_kernel(u_ref, dt_ref, bt_ref, ct_ref, a_ref, y_ref, starts_ref,
                state, *, chunk):
    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    a = a_ref[...]

    def group(g, s):
        dt = dt_ref[0, _rows_of(g), :]
        b, c, write = bt_ref[0, g], ct_ref[0, g], dt * u_ref[0, _rows_of(g), :]
        y = jnp.zeros_like(dt)
        row = jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
        for k in range(GROUP):
            if k % math.gcd(GROUP, chunk) == 0:  # a chunk may start here
                t = g * GROUP + k

                @pl.when(t % chunk == 0)
                def _(s=s, t=t):
                    starts_ref[0, t // chunk] = s
            s = _advance(s, a, dt, write, b, k)
            y = jnp.where(row == k,
                          jnp.sum(s * c[:, k:k + 1], 0, keepdims=True), y)
        y_ref[0, _rows_of(g), :] = y
        return s

    state[...] = jax.lax.fori_loop(0, u_ref.shape[1] // GROUP, group,
                                   state[...])


def _bwd_kernel(u_ref, dt_ref, bt_ref, ct_ref, a_ref, dy_ref, start_ref,
                du_ref, ddt_ref, dbt_ref, dct_ref, da_ref,
                before, d_state, d_a):
    @pl.when(pl.program_id(2) == 0)
    def _():
        d_state[...] = jnp.zeros_like(d_state)
        d_a[...] = jnp.zeros_like(d_a)

    a = a_ref[...]
    groups = u_ref.shape[1] // GROUP

    def again(g, s):  # before[t]: the state position t reads
        dt = dt_ref[0, _rows_of(g), :]
        b, write = bt_ref[0, g], dt * u_ref[0, _rows_of(g), :]
        for k in range(GROUP):
            before[g * GROUP + k] = s
            s = _advance(s, a, dt, write, b, k)
        return s

    def group(i, carry):
        s, d_s, acc = carry
        g = groups - 1 - i
        u, dt, dy = (ref[0, _rows_of(g), :] for ref in (u_ref, dt_ref, dy_ref))
        b, c, write = bt_ref[0, g], ct_ref[0, g], dt * u
        d_u, d_dt = jnp.zeros_like(u), jnp.zeros_like(u)
        d_b, d_c = jnp.zeros_like(b), jnp.zeros_like(c)
        row = jax.lax.broadcasted_iota(jnp.int32, u.shape, 0)
        column = jax.lax.broadcasted_iota(jnp.int32, b.shape, 1)
        for k in reversed(range(GROUP)):
            s_before = before[g * GROUP + k]
            dt_t, u_t, dy_t = dt[k:k + 1], u[k:k + 1], dy[k:k + 1]
            decay = jnp.exp(dt_t * a)
            d_s = d_s + dy_t * c[:, k:k + 1]
            d_c = jnp.where(column == k,
                            jnp.sum(s * dy_t, 1, keepdims=True), d_c)
            d_b = jnp.where(column == k, jnp.sum(
                d_s * write[k:k + 1], 1, keepdims=True), d_b)
            d_write = jnp.sum(d_s * b[:, k:k + 1], 0, keepdims=True)
            d_exponent = d_s * s_before * decay
            d_u = jnp.where(row == k, d_write * dt_t, d_u)
            d_dt = jnp.where(row == k, d_write * u_t + jnp.sum(
                d_exponent * a, 0, keepdims=True), d_dt)
            acc = acc + d_exponent * dt_t
            d_s = decay * d_s
            s = s_before
        du_ref[0, _rows_of(g), :] = d_u
        ddt_ref[0, _rows_of(g), :] = d_dt
        dbt_ref[0, 0, g] = d_b
        dct_ref[0, 0, g] = d_c
        return s, d_s, acc

    s = jax.lax.fori_loop(0, groups, again, start_ref[0, 0])
    _, d_state[...], d_a[...] = jax.lax.fori_loop(
        0, groups, group, (s, d_state[...], d_a[...]))

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _():
        da_ref[0] = d_a[...]


# --------------------------------------------------------------------------
def _rows(x, cb, t):
    """The op's chunks [n, B, chunk, C] -> [B, S, C], the channels padded
    to whole blocks (a padded channel has u = 0, dt = 0) and S to whole
    grid steps (a padded position has dt 0, as the op's own have)."""
    n_chunks, batch, chunk, channels = x.shape
    x = jnp.moveaxis(x, 0, 1).reshape(batch, n_chunks * chunk, channels)
    return jnp.pad(x, ((0, 0), (0, -x.shape[1] % t), (0, -channels % cb)))


def _cols(x, t):
    """The op's chunks [n, B, chunk, N] -> [B, S / GROUP, N, GROUP]: a
    group's positions along the lanes."""
    rows = _rows(x, 1, t)
    return jnp.swapaxes(
        rows.reshape(x.shape[1], -1, GROUP, x.shape[3]), 2, 3)


def _operands(u, dt, b, c, a, cb, t):
    return (_rows(u, cb, t), _rows(dt, cb, t), _cols(b, t), _cols(c, t),
            jnp.pad(a, ((0, -a.shape[0] % cb), (0, 0))).T)


def _chunks(x, like):
    """[B, S, C] of the kernels back to the op's chunks, shaped ``like``."""
    n_chunks, batch, chunk, channels = like.shape
    x = x[:, :n_chunks * chunk, :channels]
    return jnp.moveaxis(x.reshape(batch, n_chunks, chunk, channels), 1, 0)


def _specs(cb, t, n, position):
    """Block specs of (u or dt or y, b or c, a) at grid (b, j, i), the
    position block ``position(i)``."""
    return (pl.BlockSpec((1, t, cb), lambda b, j, i: (b, position(i), j)),
            pl.BlockSpec((1, t // GROUP, n, GROUP),
                         lambda b, j, i: (b, position(i), 0, 0)),
            pl.BlockSpec((n, cb), lambda b, j, i: (0, j)))


def _static(u, a):
    """What a call is traced by besides its operands' shapes: the blocks
    chosen for it and whether the interpreter runs them. Both kernels'
    entries are jitted on it, so that a step's equal calls (two Mamba
    layers, each forward and recomputed) are traced once: every start of
    a program traces its step, compile cache or not, and six separate
    traces of the kernels cost the Phi cell 2.5 s of warm set-up where
    these cost 0.35 (PERF.md §6, PR 37)."""
    chunk = u.shape[2]
    return dict(blocks=_block_sizes(u.shape[3], a.shape[1],
                                    u.shape[0] * chunk, chunk),
                interpret=fa._INTERPRET and not fa._on_tpu())


def forward(u, dt, b, c, a):
    """The op's chunks u, dt [n, B, chunk, C], b, c [n, B, chunk, N] and
    a [C, N] -> (y [n, B, chunk, C], the states at the chunks' starts
    [B, n', N, C'] in the kernels' layout, padding included)."""
    return _forward(u, dt, b, c, a, **_static(u, a))


@functools.partial(jax.jit, static_argnames=("blocks", "interpret"))
def _forward(u, dt, b, c, a, blocks, interpret):
    chunk, n, (cb, t) = u.shape[2], a.shape[1], blocks
    u_k, dt_k, bt, ct, a_k = _operands(u, dt, b, c, a, cb, t)
    batch, positions, channels = u_k.shape
    grid = (batch, channels // cb, positions // t)
    row, col, a_spec = _specs(cb, t, n, lambda i: i)
    y, starts = pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk),
        out_shape=(jax.ShapeDtypeStruct(u_k.shape, jnp.float32),
                   jax.ShapeDtypeStruct(
                       (batch, positions // chunk, n, channels),
                       jnp.float32)),
        grid=grid,
        in_specs=[row, row, col, col, a_spec],
        out_specs=(row, pl.BlockSpec((1, t // chunk, n, cb),
                                     lambda b, j, i: (b, i, 0, j))),
        scratch_shapes=[pltpu.VMEM((n, cb), jnp.float32)],
        compiler_params=_params("ssm_scan_fwd", cb, t, n, chunk),
        interpret=interpret,
        name="ssm_scan_fwd",
    )(u_k, dt_k, bt, ct, a_k)
    return _chunks(y, u), starts


def backward(u, dt, b, c, a, starts, d_y):
    """The gradients of ``forward``'s five operands, from its operands,
    the chunk-start states it kept and y's gradient."""
    return _backward(u, dt, b, c, a, starts, d_y, **_static(u, a))


@functools.partial(jax.jit, static_argnames=("blocks", "interpret"))
def _backward(u, dt, b, c, a, starts, d_y, blocks, interpret):
    chunk, n, (cb, t) = u.shape[2], a.shape[1], blocks
    u_k, dt_k, bt, ct, a_k = _operands(u, dt, b, c, a, cb, t)
    batch, positions, channels = u_k.shape
    blocks, steps = channels // cb, positions // t
    row, col, a_spec = _specs(cb, t, n, lambda i: steps - 1 - i)
    part = pl.BlockSpec((1, 1, t // GROUP, n, GROUP),
                        lambda b, j, i: (b, j, steps - 1 - i, 0, 0))
    d_u, d_dt, d_bt, d_ct, d_a = pl.pallas_call(
        _bwd_kernel,
        out_shape=(jax.ShapeDtypeStruct(u_k.shape, jnp.float32),) * 2
        + (jax.ShapeDtypeStruct((batch, blocks, positions // GROUP, n,
                                 GROUP), jnp.float32),) * 2
        + (jax.ShapeDtypeStruct((batch, n, channels), jnp.float32),),
        grid=(batch, blocks, steps),
        in_specs=[row, row, col, col, a_spec, row,
                  pl.BlockSpec((1, 1, n, cb), lambda b, j, i: (
                      b, (steps - 1 - i) * (t // chunk), 0, j))],
        out_specs=(row, row, part, part,
                   pl.BlockSpec((1, n, cb), lambda b, j, i: (b, 0, j))),
        scratch_shapes=[pltpu.VMEM((t, n, cb), jnp.float32),
                        pltpu.VMEM((n, cb), jnp.float32),
                        pltpu.VMEM((n, cb), jnp.float32)],
        compiler_params=_params("ssm_scan_bwd", cb, t, n, chunk),
        interpret=interpret,
        name="ssm_scan_bwd",
    )(u_k, dt_k, bt, ct, a_k, _rows(d_y, cb, t), starts)

    def cols(x):  # [B, blocks, S / GROUP, N, GROUP] -> [n, B, chunk, N]
        x = jnp.swapaxes(jnp.sum(x, 1), 2, 3).reshape(batch, positions, n)
        return _chunks(x, b)

    return (_chunks(d_u, u), _chunks(d_dt, u), cols(d_bt), cols(d_ct),
            jnp.sum(d_a, 0).T[:a.shape[0]])
