"""Pallas TPU kernels of one pass of the expert layer: the grouped
products of ``ops/decoder_ops._window``, its second lowering beside
``lax.ragged_dot``.

A pass holds ``rows`` sorted assignments (``decoder_ops.row_bound``), the
first ``offsets[-1]`` of them routed: group g (a held expert) owns rows
``offsets[g] .. offsets[g + 1]``, the rest is padding. The kernels walk a
SCHEDULE of visits, made from the group sizes and handed in by scalar
prefetch: visit s works on group ``groups[s]`` in row tile ``tiles[s]``,
every tile a group's rows touch one after the other (a tile two groups
share is visited once by each, consecutively), an empty group once, so
that its weights' gradient is written. ``counts[0]`` visits are live;
the tiles past the last routed row come after them. A grid has
``rows / tm + held`` steps, the most a routing can make; a step past the
live ones moves no block (its index maps repeat the last live visit's)
and computes nothing, so a pass costs the tiles that hold routed rows.

Two grids:

``_gmm``   rows [rows, K] x a group's [K, N] (or [N, K], contracted on
    its minor dimension: the transposed weights of the way back) ->
    [rows, N]. Grid (N / tn, steps), K whole: a group's weight block is
    fetched once a column tile, not once a row tile. An epilogue works on
    the f32 product before it is stored: the gate's activation times the
    up half (``gate_up``), the routing weight a row (``down``), the whole
    backward of both (``down_bwd``). A row a visit's group does not hold
    keeps what the tile's earlier visit wrote, or zero: rows past the
    last group come back zero whatever the operands hold there (a
    select, not a product: NaN stays out). The tiles past the last routed
    row are written zero where XLA reads the result (``zero_dead``) and
    left alone where only these kernels do.
``_tgmm``  a group's rows of lhs [rows, K], transposed, x its rows of
    rhs [rows, N] -> [held, K, N] in the operands' dtype. Grid (K / tk,
    N / tn, steps): an output block is accumulated in VMEM, float32,
    over its group's visits and written ONCE, zero for a group without
    rows; given ``existing`` (aliased to the output) it adds to that in
    place and touches only the groups the pass holds.

Operands are what ``decoder_ops._operands`` makes them (bf16 on the MXU
under FLAGS_use_bf16_matmul, float32 otherwise, at the ambient matmul
precision); products accumulate in float32, activations and routing
weights are applied in float32.

Which backend runs the kernels is ``use_kernels``'s rule (a TPU, off a
device mesh; the CPU through the interpreter in tests), which shapes
``_block_sizes``'s: it returns None for one it does not take, and the op
keeps ``lax.ragged_dot``.
"""
from __future__ import annotations

import contextlib
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import flash_attention as fa

# Rows a tile: 256 where twice an expert's expected rows fill two such
# tiles (a group then spans several and its weights are fetched once for
# all of them), 128 below (a tile of 256 would mostly hold other groups'
# rows, each masked out of a product that was computed).
ROW_TILES = (256, 128)

_BLOCK_OVERRIDE = None  # tm set by block_override()


@contextlib.contextmanager
def block_override(tm):
    """Pin the row tile inside the context and take every shape, aligned
    to the lanes or not (blocks then span whole dimensions): the tests,
    through the interpreter. It applies to the backward too, so wrap the
    whole grad computation."""
    global _BLOCK_OVERRIDE
    prev = _BLOCK_OVERRIDE
    _BLOCK_OVERRIDE = int(tm)
    try:
        yield
    finally:
        _BLOCK_OVERRIDE = prev


def use_kernels() -> bool:
    """The backends the flash kernels run on, off a device mesh (XLA
    does not partition a Mosaic kernel, and an expert-parallel mesh
    shards these operands)."""
    return fa._use_kernels() and fa._MESH is None


class Blocks(NamedTuple):
    """Row tile and the column tiles of each call of a pass."""
    tm: int
    gate_up: int   # of F: gate_up's and its columns of W_gate_up
    project: int   # of 2F: the gate/up product kept whole for the backward
    down: int      # of D: down's columns of W_down
    rows_bwd: int  # of D: the rows' gradient against W_gate_up transposed
    w_gate_up: tuple  # (tk of D, tn of 2F): W_gate_up's gradient
    w_down: tuple     # (tk of F, tn of D): W_down's gradient


def _tile_sizes(n):
    """Column tiles of a dimension, widest first: the whole of it, then
    its divisors in whole 128-lane tiles."""
    return [n] + [t for t in range(n - fa.LANES, 0, -fa.LANES)
                  if n % t == 0 and t % fa.LANES == 0]


def _gmm_working_set(tm, k, tn, itemsize, views=1, out_bytes=4, sides=0):
    """Bytes of VMEM a ``_gmm`` step holds: every piped block twice, the
    f32 products and the epilogue's temporaries of their size; float32
    operands half as much again (``flash_attention._working_set``)."""
    piped = (tm * k + views * k * tn) * itemsize + tm * tn * out_bytes \
        + sides
    total = 2 * piped + (views + 2) * tm * tn * 4
    return total if itemsize <= 2 else total * 3 // 2


def _tgmm_working_set(tm, tk, tn, itemsize, existing=True):
    piped = tm * (tk + tn) * itemsize + (1 + existing) * tk * tn * 4
    total = 2 * piped + 2 * tk * tn * 4
    return total if itemsize <= 2 else total * 3 // 2


def _widest(sizes, fits):
    return next((t for t in sizes if fits(t)), None)


def _block_sizes(rows, d, f, held, itemsize=2):
    """``Blocks`` of a pass of this shape, or None for one the kernels do
    not take: D or F not in whole 128-lane tiles, or a block that does
    not fit ``VMEM_BUDGET`` at its narrowest. The row tile from the rows
    a group is expected to hold (``rows`` is twice that a held expert),
    each column tile the widest whose working set fits. Inside
    ``block_override`` the pinned row tile wins and no shape is
    declined."""
    budget = fa.VMEM_BUDGET
    if _BLOCK_OVERRIDE:
        tm = min(_BLOCK_OVERRIDE, -(-rows // 8) * 8)
        budget = float("inf")

        def sizes(n):
            return _tile_sizes(n) if n % fa.LANES == 0 else [n]
    elif d % fa.LANES or f % fa.LANES:
        return None
    else:
        tm = ROW_TILES[0] if rows >= 2 * ROW_TILES[0] * held \
            else ROW_TILES[1]
        tm = min(tm, -(-rows // 16) * 16)
        sizes = _tile_sizes
    side = 2 * tm * fa.LANES * 4  # a [tm, 1] block takes whole lanes
    chosen = dict(
        gate_up=_widest(sizes(f), lambda t: _gmm_working_set(
            tm, d, t, itemsize, views=2, out_bytes=itemsize) <= budget),
        project=_widest(sizes(2 * f), lambda t: _gmm_working_set(
            tm, d, t, itemsize) <= budget),
        down=_widest(sizes(d), lambda t: _gmm_working_set(
            tm, f, t, itemsize, sides=side) <= budget),
        rows_bwd=_widest(sizes(d), lambda t: _gmm_working_set(
            tm, 2 * f, t, itemsize) <= budget))
    # down_bwd takes F whole: the routing weight's gradient sums over it
    fits = _gmm_working_set(
        tm, d, f, itemsize, out_bytes=3 * itemsize,
        sides=side + tm * 2 * f * 4) <= budget

    def pair(k, n):
        for tk in sizes(k):
            tn = _widest(sizes(n), lambda t: _tgmm_working_set(
                tm, tk, t, itemsize) <= budget)
            if tn:
                return tk, tn
    w_gate_up, w_down = pair(d, 2 * f), pair(f, d)
    if not (fits and w_gate_up and w_down and all(chosen.values())):
        return None
    return Blocks(tm, w_gate_up=w_gate_up, w_down=w_down, **chosen)


def _params(need, grid_rank):
    need = int(need * fa.VMEM_HEADROOM)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * (grid_rank - 1) + ("arbitrary",),
        vmem_limit_bytes=need if need > fa.SCOPED_VMEM_DEFAULT else None)


# --------------------------------------------------------------------------
class Schedule(NamedTuple):
    """What the kernels take by scalar prefetch, all int32."""
    offsets: jax.Array  # [held + 1]: group g's rows are offsets[g:g + 2]
    groups: jax.Array   # [steps]: the group a visit works on
    filled: jax.Array   # [steps]: that group, or the nearest one with rows
    tiles: jax.Array    # [steps]: the row tile a visit works in
    counts: jax.Array   # [2]: live visits, tiles that hold a routed row


def schedule(sizes, rows, tm):
    """The visits of a pass of ``rows`` rows (whole tiles of ``tm``)
    whose groups hold ``sizes`` [held] rows, side by side from row 0.
    ``filled`` names the block of weights a visit keeps in VMEM: an empty
    group's visit, which computes nothing, moves none."""
    held, n_tiles = sizes.shape[0], rows // tm
    steps = n_tiles + held
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = jnp.minimum(starts // tm, n_tiles - 1)
    last = jnp.where(sizes > 0, (ends - 1) // tm, first)
    visits = last - first + 1
    visit_ends = jnp.cumsum(visits)
    live, live_tiles = visit_ends[-1], -(-ends[-1] // tm)
    s = jnp.arange(steps, dtype=jnp.int32)
    group = jnp.minimum(jnp.sum(s[:, None] >= visit_ends, 1), held - 1)
    tile = first[group] + s - (visit_ends[group] - visits[group])
    dead = jnp.minimum(live_tiles + s - live, n_tiles - 1)
    ids = jnp.arange(held, dtype=jnp.int32)
    nearest = lax.cummax(jnp.where(sizes > 0, ids, -1))
    nearest = jnp.where(nearest < 0, jnp.argmax(sizes > 0).astype(jnp.int32),
                        nearest)
    return Schedule(
        jnp.concatenate([jnp.zeros((1,), jnp.int32), ends]),
        jnp.where(s < live, group, held - 1), nearest[group],
        jnp.where(s < live, tile, dead).astype(jnp.int32),
        jnp.stack([live, live_tiles]).astype(jnp.int32))


def _live_tile(tiles, counts, s):
    """Visit s's row tile, a dead step's held at the last live one: no
    block of the operands moves past the live visits."""
    return jnp.minimum(tiles[s], jnp.maximum(counts[1] - 1, 0))


def _out_tile(tiles, counts, s, zero_dead):
    """The row tile visit s writes: its own, the tiles past the last
    routed row too where they are to be written zero, else held like the
    operands'."""
    return tiles[s] if zero_dead else _live_tile(tiles, counts, s)


def _group_rows(offsets, groups, tiles, s, tm):
    """[tm, 1]: which rows of visit s's tile its group holds."""
    g = groups[s]
    rows = tiles[s] * tm + lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    return (rows >= offsets[g]) & (rows < offsets[g + 1])


def _dot(a, b, contract):
    return lax.dot_general(a, b, ((contract, ((), ()))),
                           preferred_element_type=jnp.float32)


def _gmm_kernel(offsets, groups, filled, tiles, counts, lhs_ref, *refs,
                tm, views, sides, transposed, zero_dead, epilogue):
    rhs_refs, side_refs = refs[:views], refs[views:views + sides]
    out_refs = refs[views + sides:]
    s = pl.program_id(1)
    g = groups[s]

    def out_tile(i):
        return _out_tile(tiles, counts, i, zero_dead)

    first = (s == 0) | (out_tile(s) != out_tile(jnp.maximum(s - 1, 0)))
    work = (s < counts[0]) & (offsets[g + 1] > offsets[g])

    @pl.when(work)
    def _():
        mine = _group_rows(offsets, groups, tiles, s, tm)
        lhs = lhs_ref[...]
        products = [_dot(lhs, ref[...], ((1,), (1 if transposed else 0,)))
                    for ref in rhs_refs]
        values = epilogue(products, [ref[...] for ref in side_refs])
        for ref, value in zip(out_refs, values):
            kept = jnp.where(first, 0.0, ref[...].astype(jnp.float32))
            ref[...] = jnp.where(mine, value, kept).astype(ref.dtype)

    @pl.when(jnp.logical_not(work) & first)
    def _():
        for ref in out_refs:
            ref[...] = jnp.zeros_like(ref)


def _gmm(name, sched, lhs, rhs, epilogue, outs, *, tm, tn, views=(0,),
         sides=(), transposed=False, zero_dead=False, interpret=False):
    """``epilogue(products, sides) -> values``: ``products`` the f32
    [tm, tn] product of the row tile with each of ``views`` (column-tile
    offsets into the group's weights, in tiles of tn), ``sides`` [rows,
    w] arrays read a row tile at a time, ``values`` one an entry of
    ``outs`` = ((width, dtype), ..): an output as wide as the product is
    tiled with it, any other is written whole (one column tile then)."""
    rows, k = lhs.shape
    n = rhs.shape[1] if transposed else rhs.shape[2]
    n_tiles = n // tn // len(views)
    assert n_tiles == 1 or all(width == n // len(views)
                               for width, _ in outs), name

    def row_block(width, tile):
        return pl.BlockSpec((tm, width), lambda j, s, *sched: (
            tile(sched[3], sched[4], s), 0))

    out_tile = functools.partial(_out_tile, zero_dead=zero_dead)

    def weights(view):
        if transposed:
            return pl.BlockSpec((None, tn, k), lambda j, s, *sched: (
                sched[2][s], j + view, 0))
        return pl.BlockSpec((None, k, tn), lambda j, s, *sched: (
            sched[2][s], 0, j + view))

    def out_block(width):
        if width != n // len(views):
            return row_block(width, out_tile)
        return pl.BlockSpec((tm, tn), lambda j, s, *sched: (
            out_tile(sched[3], sched[4], s), j))

    itemsize = lhs.dtype.itemsize
    need = _gmm_working_set(
        tm, k, tn, itemsize, len(views),
        sum(jnp.dtype(dt).itemsize * w for w, dt in outs) / tn,
        sum(tm * max(a.shape[1], fa.LANES) * a.dtype.itemsize
            for a in sides))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, views=len(views),
                          sides=len(sides), transposed=transposed,
                          zero_dead=zero_dead, epilogue=epilogue),
        out_shape=tuple(jax.ShapeDtypeStruct((rows, width), dt)
                        for width, dt in outs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(n_tiles, rows // tm + rhs.shape[0]),
            in_specs=[row_block(k, _live_tile)]
            + [weights(view) for view in views]
            + [row_block(a.shape[1], _live_tile) for a in sides],
            out_specs=tuple(out_block(width) for width, _ in outs)),
        compiler_params=_params(need, 2),
        interpret=interpret,
        name=name,
    )(*sched, lhs, *([rhs] * len(views)), *sides)


def _tgmm_kernel(offsets, groups, filled, tiles, counts, lhs_ref, rhs_ref,
                 *refs, tm, add):
    out_ref, acc = refs[-2:]
    s, live = pl.program_id(2), counts[0]
    at = jnp.minimum(s, live - 1)
    block = filled if add else groups
    g = groups[at]
    opens = (s == 0) | ((s < live) & (
        block[at] != block[jnp.maximum(at - 1, 0)]))
    closes = (s < live) & ((s == live - 1) | (
        block[at] != block[jnp.minimum(at + 1, live - 1)]))

    @pl.when(opens)
    def _():
        acc[...] = jnp.zeros_like(acc)

    @pl.when((s < live) & (offsets[g + 1] > offsets[g]))
    def _():
        mine = _group_rows(offsets, groups, tiles, at, tm)
        lhs = jnp.where(mine, lhs_ref[...], 0)
        rhs = jnp.where(mine, rhs_ref[...], 0)
        acc[...] += _dot(lhs, rhs, ((0,), (0,)))

    @pl.when(closes)
    def _():
        total = acc[...] + refs[0][...].astype(jnp.float32) if add \
            else acc[...]
        out_ref[...] = total.astype(out_ref.dtype)


def _tgmm(name, sched, lhs, rhs, existing, *, tm, tk, tn, interpret=False):
    """[held, K, N] in the operands' dtype: group g's rows of ``lhs``
    [rows, K], transposed, times its rows of ``rhs`` [rows, N], summed
    in float32; added in place to ``existing`` where that is given, else
    a group without rows reads zero."""
    rows, k = lhs.shape
    n, held = rhs.shape[1], sched.offsets.shape[0] - 1
    add = existing is not None

    def at(s, counts):
        return jnp.minimum(s, counts[0] - 1)

    def rows_of(width, axis):
        return pl.BlockSpec((tm, width), lambda i, j, s, *sched: (
            sched[3][at(s, sched[4])], (i, j)[axis]))

    block = pl.BlockSpec((None, tk, tn), lambda i, j, s, *sched: (
        sched[2 if add else 1][at(s, sched[4])], i, j))
    need = _tgmm_working_set(tm, tk, tn, lhs.dtype.itemsize, add)
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm, add=add),
        out_shape=jax.ShapeDtypeStruct((held, k, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(k // tk, n // tn, rows // tm + held),
            in_specs=[rows_of(tk, 0), rows_of(tn, 1)] + [block] * add,
            out_specs=block,
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        input_output_aliases={7: 0} if add else {},
        compiler_params=_params(need, 3),
        interpret=interpret,
        name=name,
    )(*sched, lhs, rhs, *([existing] * add))


# --------------------------------------------------------------------------
def _silu(x):
    return x * jax.nn.sigmoid(x)


def _d_silu(x):
    s = jax.nn.sigmoid(x)
    return s * (1.0 + x * (1.0 - s))


# an activation of the gate's half and its derivative
ACTIVATIONS = {
    "silu": (_silu, _d_silu),
    "relu": (lambda x: jnp.maximum(x, 0.0),
             lambda x: (x > 0).astype(jnp.float32)),
}


def _interpret():
    return fa._INTERPRET and not fa._on_tpu()


def forward(x_rows, row_weight, w_gate_up, w_down, sizes, activation,
            blocks):
    """The pass's rows out: ``x_rows`` [rows, D] the tokens of the sorted
    assignments (whole tiles of ``blocks.tm``), ``row_weight`` [rows]
    their routing weights, ``sizes`` [held] the rows of each group in the
    pass -> [rows, D] float32, row r = row_weight[r] * (act(x W_g) *
    x W_u) W_d of its group, zero past the last group. Jitted on the
    blocks, as ``backward`` is, so that a step's equal passes (every
    layer of a cell, each forward, recomputed and in the grad op) are
    traced once."""
    return _forward(x_rows, row_weight, w_gate_up, w_down, sizes,
                    activation=activation, blocks=blocks,
                    interpret=_interpret())


@functools.partial(jax.jit,
                   static_argnames=("activation", "blocks", "interpret"))
def _forward(x_rows, row_weight, w_gate_up, w_down, sizes, activation,
             blocks, interpret):
    tm, f = blocks.tm, w_down.shape[1]
    act = ACTIVATIONS[activation][0]
    sched = schedule(sizes, x_rows.shape[0], tm)
    hidden, = _gmm(
        "moe_gmm_gate_up", sched, x_rows, w_gate_up,
        lambda p, _: [act(p[0]) * p[1]], ((f, x_rows.dtype),),
        tm=tm, tn=blocks.gate_up, views=(0, f // blocks.gate_up),
        interpret=interpret)
    y, = _gmm(
        "moe_gmm_down", sched, hidden, w_down,
        lambda p, sides: [p[0] * sides[0]], ((w_down.shape[2], jnp.float32),),
        tm=tm, tn=blocks.down, sides=(row_weight[:, None],), zero_dead=True,
        interpret=interpret)
    return y


def backward(x_rows, row_weight, w_gate_up, w_down, sizes, activation,
             blocks, g, sums=None):
    """The pass's way back, from its operands and ``g`` [rows, D], the
    output's gradient at each row's token: (the gradients of x_rows
    [rows, D] and of row_weight [rows], float32, zero past the last
    group; those of W_gate_up and W_down, summed in float32 and kept as
    their operands are, which is what stays alive until the optimizer
    reads it: added in place to ``sums`` = (d_w_gate_up, d_w_down) where
    given, which then touches only the groups the pass holds)."""
    return _backward(x_rows, row_weight, w_gate_up, w_down, sizes, g, sums,
                     activation=activation, blocks=blocks,
                     interpret=_interpret())


@functools.partial(jax.jit,
                   static_argnames=("activation", "blocks", "interpret"))
def _backward(x_rows, row_weight, w_gate_up, w_down, sizes, g, sums,
              activation, blocks, interpret):
    tm, (_, f, d) = blocks.tm, w_down.shape
    act, d_act = ACTIVATIONS[activation]
    dtype = x_rows.dtype
    sched = schedule(sizes, x_rows.shape[0], tm)
    h, = _gmm("moe_gmm_project", sched, x_rows, w_gate_up,
              lambda p, _: p, ((2 * f, jnp.float32),),
              tm=tm, tn=blocks.project, interpret=interpret)

    def down_bwd(products, sides):
        h, weight = sides
        gate, up = h[:, :f], h[:, f:]
        hidden, back = act(gate) * up, products[0]  # back: g W_down^T
        d_hidden = back * weight
        d_h = jnp.concatenate([d_hidden * up * d_act(gate),
                               d_hidden * act(gate)], 1)
        return [d_h, hidden * weight,
                jnp.sum(hidden * back, 1, keepdims=True)]

    d_h, weighted, d_weight = _gmm(
        "moe_gmm_down_bwd", sched, g, w_down, down_bwd,
        ((2 * f, dtype), (f, dtype), (1, jnp.float32)),
        tm=tm, tn=f, sides=(h, row_weight[:, None]), transposed=True,
        interpret=interpret)
    d_x_rows, = _gmm("moe_gmm_rows_bwd", sched, d_h, w_gate_up,
                     lambda p, _: p, ((d, jnp.float32),),
                     tm=tm, tn=blocks.rows_bwd, transposed=True,
                     zero_dead=True, interpret=interpret)
    d_w_gate_up = _tgmm("moe_tgmm_gate_up", sched, x_rows, d_h,
                        sums and sums[0], tm=tm, tk=blocks.w_gate_up[0],
                        tn=blocks.w_gate_up[1], interpret=interpret)
    d_w_down = _tgmm("moe_tgmm_down", sched, weighted, g, sums and sums[1],
                     tm=tm, tk=blocks.w_down[0], tn=blocks.w_down[1],
                     interpret=interpret)
    # down_bwd leaves the tiles past the last routed row alone: of its
    # three results only this one is read there, and it is [rows] long
    routed = jnp.arange(d_weight.shape[0]) < sched.offsets[-1]
    return d_x_rows, jnp.where(routed, d_weight[:, 0], 0.0), d_w_gate_up, \
        d_w_down
