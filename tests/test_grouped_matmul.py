"""The expert pass's Pallas kernels (ops/pallas/grouped_matmul.py),
through the interpreter on the CPU, against the `lax.ragged_dot` lowering
of the same pass (ops/decoder_ops._window): values and every gradient
(the rows', both weights', the routing weights').

Tolerance: both sides compute in float32 here and differ in the order of
their sums alone (a tile's product against a group's, a gradient summed a
tile at a time): 2e-5 of a tensor's largest entry, as the op tests.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.fluid import telemetry
from paddle_tpu.ops import decoder_ops
from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import grouped_matmul as gm
from paddle_tpu.ops.registry import OPS

TOL = 2e-5


def normal(seed, *shape, scale=1.0):
    return jnp.asarray(np.random.default_rng(seed).normal(
        0.0, scale, shape).astype(np.float32))


def close(got, want, tol=TOL):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    scale = np.abs(want).max() + 1e-30
    assert np.abs(got - want).max() <= tol * scale, \
        (np.abs(got - want).max() / scale, tol)


def _pass(seed, sizes, *, tokens, k, d, f, rows=None):
    """The operands of one pass: the first sum(sizes) entries of ``order``
    are the groups' assignments side by side, the rest assignments no
    held expert was given; ``rows`` of them in all."""
    held = len(sizes)
    rng = np.random.default_rng(seed)
    order = jnp.asarray(rng.permutation(tokens * k)[:rows or tokens * k],
                        jnp.int32)
    weight = jnp.asarray(rng.uniform(0.1, 1.0, tokens * k), jnp.float32)
    return dict(x=normal(seed + 1, tokens, d), weight=weight,
                w_gate_up=normal(seed + 2, held, d, 2 * f, scale=0.2),
                w_down=normal(seed + 3, held, f, d, scale=0.2),
                order=order, sizes=jnp.asarray(sizes, jnp.int32), k=k)


def _window(p, lo, bound, activation, diff=None):
    """``decoder_ops._window`` over the sorted assignments lo .. lo +
    bound - 1 of the pass ``p``, its four differentiable operands
    replaced by ``diff`` where given."""
    x, weight, w_gate_up, w_down = diff or (
        p["x"], p["weight"], p["w_gate_up"], p["w_down"])
    return decoder_ops._window(
        jnp.zeros(x.shape, jnp.float32), x, weight, w_gate_up, w_down,
        p["order"][lo:lo + bound], lo, p["sizes"], p["k"], activation)


def _both(p, lo, bound, activation, tm):
    """(output, four gradients) of the window, by each lowering."""
    diff = (p["x"], p["weight"], p["w_gate_up"], p["w_down"])
    g = normal(99, *p["x"].shape)

    def run():
        out, vjp = jax.vjp(
            lambda *diff: _window(p, lo, bound, activation, diff), *diff)
        return (out,) + tuple(vjp(g))

    assert not gm.use_kernels()  # the CPU runs lax.ragged_dot
    want = run()
    with fa.interpret_guard(), gm.block_override(tm):
        assert gm.use_kernels()
        got = run()
    return got, want


# sizes, the window (lo, bound) and the row tile of each case
CASES = {
    "an_empty_group": ([5, 0, 7, 3], (0, 24), 8),
    "empty_groups_first_and_last": ([0, 0, 9, 4, 0], (0, 16), 8),
    "a_group_straddles_a_tile": ([5, 6, 2], (0, 16), 8),
    "a_group_spans_three_tiles": ([3, 20, 1], (0, 24), 8),
    "groups_fill_the_window": ([8, 8, 8], (0, 24), 8),
    "rows_past_the_last_group": ([4, 3], (0, 32), 8),
    "one_row_tile": ([2, 1, 3], (0, 8), 8),
    "rows_not_in_whole_tiles": ([4, 5, 3], (0, 20), 8),
    "a_window_that_starts_inside_a_group": ([10, 12, 4], (16, 16), 8),
    "a_window_that_holds_nothing": ([6, 5], (16, 16), 8),
    "no_assignment_routed": ([0, 0, 0], (0, 16), 8),
}


@pytest.mark.parametrize("activation", ["silu", "relu"])
@pytest.mark.parametrize("case", list(CASES))
def test_a_window_is_the_ragged_dot_lowerings_with_every_gradient(
        case, activation):
    sizes, (lo, bound), tm = CASES[case]
    p = _pass(7, sizes, tokens=12, k=3, d=32, f=12)
    got, want = _both(p, lo, bound, activation, tm)
    for a, b in zip(got, want):
        close(a, b)
    if case in ("a_window_that_holds_nothing", "no_assignment_routed"):
        assert not any(np.asarray(a).any() for a in got)


@pytest.mark.parametrize("activation", ["silu", "relu"])
def test_a_group_that_straddles_two_windows_is_summed_over_both(activation):
    """Group 1 holds the sorted rows 10 .. 21 and a window 16: the two
    windows' outputs and gradients add up to the one window's that holds
    all 32 rows, by either lowering."""
    p = _pass(11, [10, 12, 4], tokens=12, k=3, d=32, f=12)
    whole, _ = _both(p, 0, 32, activation, 8)
    first, first_want = _both(p, 0, 16, activation, 8)
    second, second_want = _both(p, 16, 16, activation, 8)
    for a, b, c, d, e in zip(first, second, whole, first_want, second_want):
        close(a + b, c)
        close(a, d)
        close(b, e)


def test_rows_past_the_last_group_come_back_zero_whatever_they_hold():
    """NaN in every operand's rows past the last group (and in the tokens
    only those rows read): the kernels give zero there and the values
    they give without the NaN elsewhere; the op's masks are not needed."""
    sizes, rows, tm, f = jnp.asarray([5, 0, 6], jnp.int32), 32, 8, 12
    x_rows, g = normal(1, rows, 32), normal(2, rows, 32)
    weight = jnp.abs(normal(3, rows)) + 0.1
    w_gate_up, w_down = normal(4, 3, 32, 2 * f), normal(5, 3, f, 32)
    blocks = gm._block_sizes(rows, 32, f, 3, 4)
    assert blocks is None  # 32 x 12 is not in whole lanes
    past = jnp.arange(rows) >= 11

    def run(x_rows, weight, g):
        y = gm.forward(x_rows, weight, w_gate_up, w_down, sizes, "silu",
                       blocks)
        return (y,) + gm.backward(x_rows, weight, w_gate_up, w_down, sizes,
                                  "silu", blocks, g)

    with fa.interpret_guard(), gm.block_override(tm):
        blocks = gm._block_sizes(rows, 32, f, 3, 4)
        clean = run(x_rows, weight, g)
        dirty = run(*(jnp.where(past.reshape((-1,) + (1,) * (a.ndim - 1)),
                                jnp.nan, a) for a in (x_rows, weight, g)))
    for a, b in zip(clean, dirty):
        assert np.isfinite(np.asarray(a)).all()
        assert (np.asarray(a) == np.asarray(b)).all()
    y, d_x, d_weight, d_w_gate_up, d_w_down = dirty
    for rows_of in (y, d_x, d_weight):
        assert not np.asarray(rows_of)[11:].any()
    assert np.abs(np.asarray(y)[:11]).min(-1).min() > 0
    assert not np.asarray(d_w_gate_up)[1].any()  # the empty group's


def test_nan_in_the_tokens_no_held_expert_reads_stays_out_of_the_op():
    """Through the op's `_window`: a token all of whose assignments lie
    past the last group holds NaN; output and gradients are the
    `lax.ragged_dot` lowering's, which masks, and finite."""
    p = _pass(13, [4, 3], tokens=12, k=3, d=32, f=12)
    routed = set(np.asarray(p["order"][:7] // 3).tolist())
    unread = next(t for t in range(12) if t not in routed)
    p["x"] = p["x"].at[unread].set(jnp.nan)
    got, want = _both(p, 0, 32, "silu", 8)
    for a, b in zip(got, want):
        assert np.isfinite(np.asarray(a)).all()
        close(a, b)


def test_the_weights_gradient_is_added_in_place_to_what_is_there():
    """``backward`` given ``sums``: the groups the pass holds add theirs,
    a group without rows keeps what it had (not zero, not rewritten)."""
    sizes, rows, f = jnp.asarray([5, 0, 6, 0], jnp.int32), 16, 12
    x_rows, g = normal(1, rows, 32), normal(2, rows, 32)
    weight = jnp.abs(normal(3, rows)) + 0.1
    w_gate_up, w_down = normal(4, 4, 32, 2 * f), normal(5, 4, f, 32)
    before = (normal(6, 4, 32, 2 * f), normal(7, 4, f, 32))
    with fa.interpret_guard(), gm.block_override(8):
        blocks = gm._block_sizes(rows, 32, f, 4, 4)
        args = (x_rows, weight, w_gate_up, w_down, sizes, "relu", blocks, g)
        alone, summed = gm.backward(*args), gm.backward(*args, before)
    for a, b, c in zip(alone[2:], summed[2:], before):
        close(b, a + c)
        for empty in (1, 3):
            assert not np.asarray(a)[empty].any()
            assert (np.asarray(b)[empty] == np.asarray(c)[empty]).all()


@pytest.mark.parametrize("seed", range(6))
def test_the_schedule_visits_every_tile_a_group_touches_once(seed):
    """``schedule`` against a plain enumeration: the live visits are each
    group's tiles in order (an empty group's one visit computes nothing),
    the tiles past the last routed row follow, the steps past those hold
    still, and ``filled`` names the nearest group with rows."""
    rng = np.random.default_rng(seed)
    held, tm, n_tiles = int(rng.integers(1, 7)), 8, int(rng.integers(1, 6))
    rows = n_tiles * tm
    cuts = np.sort(rng.integers(0, rng.integers(0, rows + 1) + 1, held + 1))
    sizes = np.diff(cuts) * rng.integers(0, 2, held)  # some groups empty
    s = gm.schedule(jnp.asarray(sizes, jnp.int32), rows, tm)
    offsets = np.concatenate([[0], np.cumsum(sizes)])
    assert (np.asarray(s.offsets) == offsets).all()
    visits = []
    for g in range(held):
        lo, hi = offsets[g], offsets[g + 1]
        tiles = range(lo // tm, (hi - 1) // tm + 1) if hi > lo else \
            [min(lo // tm, n_tiles - 1)]
        visits += [(g, t) for t in tiles]
    live, live_tiles = (int(c) for c in s.counts)
    assert live == len(visits) and live_tiles == -(-offsets[-1] // tm)
    assert len(s.groups) == n_tiles + held >= live + n_tiles - live_tiles
    assert list(zip(np.asarray(s.groups)[:live].tolist(),
                    np.asarray(s.tiles)[:live].tolist())) == visits
    dead = np.asarray(s.tiles)[live:]
    assert (dead == np.minimum(live_tiles + np.arange(len(dead)),
                               n_tiles - 1)).all()
    with_rows = [g for g in range(held) if sizes[g]] or [0]
    for step, g in enumerate(np.asarray(s.groups).tolist()):
        want = max([w for w in with_rows if w <= g] or with_rows[:1])
        assert int(s.filled[step]) == want


# D, F, the rows a held expert expects and the row tile of the three
# cells, all divided by 8: (2048, 512, 80, 128), (2048, 512, 256, 256),
# (2560, 768, 1536, 256)
SCALED = {"qwen3_next": (256, 64, 10, 16, "silu"),
          "laguna": (256, 64, 32, 32, "silu"),
          "smallthinker": (320, 96, 192, 32, "relu")}


@pytest.mark.parametrize("cell", list(SCALED))
def test_the_cells_shapes_an_eighth_the_size(cell):
    """Four held experts at the cell's D x F and rows an expert / 8,
    routed unevenly (one at twice its share, one at none) in a window of
    twice the expected rows, at the cell's row tile / 8."""
    d, f, expected, tm, activation = SCALED[cell]
    sizes = [2 * expected, expected // 2, 0, expected]
    bound = 2 * 4 * expected
    p = _pass(21, sizes, tokens=bound // 4, k=4, d=d, f=f, rows=bound)
    got, want = _both(p, 0, bound, activation, tm)
    for a, b in zip(got, want):
        close(a, b)


@pytest.mark.parametrize("rows,d,f,held,itemsize,tm", [
    (5120, 2048, 512, 32, 2, 128),     # Qwen3-Next's cell
    (16384, 2048, 512, 32, 2, 256),    # Laguna's
    (49152, 2560, 768, 16, 2, 256),    # SmallThinker's
    (5120, 2048, 512, 32, 4, 128),     # the float32 parity programs'
    (16384, 2048, 512, 32, 4, 256),
])
def test_the_chooser_takes_the_cells_shapes(rows, d, f, held, itemsize, tm):
    """The row tile from the rows a held expert expects (80 -> 128, 256
    and 1,536 -> 256); every block a divisor of its dimension in whole
    lanes, within the VMEM budget."""
    blocks = gm._block_sizes(rows, d, f, held, itemsize)
    assert blocks.tm == tm
    assert f % blocks.gate_up == 0 and (2 * f) % blocks.project == 0
    assert d % blocks.down == 0 and d % blocks.rows_bwd == 0
    for (tk, tn), (k, n) in ((blocks.w_gate_up, (d, 2 * f)),
                             (blocks.w_down, (f, d))):
        assert k % tk == 0 and n % tn == 0 and tn % 128 == 0
        assert gm._tgmm_working_set(tm, tk, tn, itemsize) <= fa.VMEM_BUDGET


@pytest.mark.parametrize("d,f", [(32, 12), (2048, 500), (2000, 512)])
def test_a_shape_the_chooser_declines_runs_the_ragged_dot_lowering(d, f):
    """D or F not in whole 128-lane tiles: `_block_sizes` gives None and
    the op, kernels allowed, lowers to `lax.ragged_dot` and sets neither
    of the kernels' gauges."""
    assert gm._block_sizes(4096, d, f, 4) is None
    if d > 32:
        return
    kernel = OPS.get("moe_expert_ffn").kernel
    site = f"t_declined_{d}_{f}"
    ins = {"X": [normal(1, 2, 8, d)],
           "TopkIdx": [jnp.zeros((2, 8, 2), jnp.int32).at[..., 1].set(1)],
           "TopkWeight": [jnp.full((2, 8, 2), 0.5)],
           "WGateUp": [normal(2, 4, d, 2 * f)], "WDown": [normal(3, 4, f, d)]}
    with fa.interpret_guard():
        assert gm.use_kernels()
        text = str(jax.make_jaxpr(
            lambda ins: kernel(ins, {"site": site})["Out"][0])(ins))
    assert "ragged_dot" in text and "pallas_call" not in text
    for name in ("moe_row_tile", "moe_grid_row_tiles_per_step"):
        family = telemetry.REGISTRY.get(name)
        assert not family or family.value(site=site) == 0
    assert telemetry.REGISTRY.get("moe_rows_per_step").value(site=site) == 32


def test_a_shape_the_chooser_takes_runs_the_kernels_and_says_so():
    """D = F = 128: no override needed; the op holds the seven kernels by
    name forward and back, no `ragged_dot`, and sets both gauges."""
    kernel = OPS.get("moe_expert_ffn").kernel
    d = f = 128
    ins = (normal(1, 2, 24, d),
           jnp.asarray(np.random.default_rng(0).integers(0, 8, (2, 24, 2)),
                       jnp.int32),
           jnp.full((2, 24, 2), 0.5), normal(2, 4, d, 2 * f, scale=0.1),
           normal(3, 4, f, d, scale=0.1))
    names = ("X", "TopkIdx", "TopkWeight", "WGateUp", "WDown")

    def op(x, idx, *rest):
        return kernel({n: [v] for n, v in zip(names, (x, idx) + rest)},
                      {"site": "t_taken", "num_experts": 8})["Out"][0]

    def loss(x, *rest):
        return jnp.sum(op(x, ins[1], *rest) ** 2)

    want = jax.grad(loss, (0, 1, 2, 3))(ins[0], *ins[2:])
    with fa.interpret_guard():
        text = str(jax.make_jaxpr(jax.grad(loss, (0, 1, 2, 3)))(
            ins[0], *ins[2:]))
        got = jax.grad(loss, (0, 1, 2, 3))(ins[0], *ins[2:])
    assert "ragged_dot" not in text
    for name in ("moe_gmm_gate_up", "moe_gmm_down", "moe_gmm_project",
                 "moe_gmm_down_bwd", "moe_gmm_rows_bwd", "moe_tgmm_gate_up",
                 "moe_tgmm_down"):
        assert name in text, name
    for a, b in zip(got, want):
        close(a, b)
    # 48 tokens x top 2, 4 of 8 experts held: 2 x 48 rows, one tile
    assert telemetry.REGISTRY.get("moe_row_tile").value(site="t_taken") == 96
    assert telemetry.REGISTRY.get("moe_grid_row_tiles_per_step").value(
        site="t_taken") == 1


def test_moe_paths_rehearsal_runs_each_lowering(capsys):
    """tools/moe_paths.py (the on-chip table of a pass's cost) on the
    CPU: the `ragged_dot` row and the `kernels` row compute the same
    output and gradients, the kernels' gate and the matmul flag are
    put back, and no device number is printed off the chip."""
    import json
    from paddle_tpu.fluid import core
    from tools import moe_paths
    moe_paths.main(["--tiny"])
    assert not core.globals_["FLAGS_use_bf16_matmul"]
    base, row = (json.loads(line)
                 for line in capsys.readouterr().out.splitlines())
    assert (base["path"], row["path"]) == ("ragged_dot", "kernels")
    assert base["share"] == row["share"] == 0.25
    assert max(row["max_diff_out_and_grads"]) < TOL
    assert not gm.use_kernels()
    for r in (base, row):
        assert r["temp_bytes"] > 0 and "fwd_device_ms" not in r
