"""Composed 3D-parallel lane (parallel/lm3d.py + the gpipe/MoE/ring
composition hooks + the executor's window×pipeline scan path) on the
virtual 8-device CPU mesh.

Oracle contract (parallel/lm3d.py's docstring): the dp×pp×sp(+MoE)
composed step must match the single-device oracle — bit-identically for
pp-only compositions (same fp ops in the same order; the gpipe psum
adds exact zeros), within documented fp32 tolerance (2e-5 rel on
per-step losses) when dp/sp partial-sum orders differ. The window scan
is bit-identical to the sequential per-step loop on EVERY path, the PR 2
window contract extended to mesh programs.

Marker: ``parallel3d`` (docs/ci.md). Small-shape units stay tier-1
non-slow; the bench-scale composition acceptance carries ``slow``.
"""
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import jax.tree_util as jtu

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import core, profiler
from paddle_tpu.parallel import lm3d
from paddle_tpu.parallel.mesh import build_mesh, mesh3d
from paddle_tpu.parallel.moe import expert_mesh, moe_ffn, moe_ffn_reference
from paddle_tpu.parallel.pipeline import (gpipe, pipeline_mesh,
                                          stack_stage_params)

pytestmark = pytest.mark.parallel3d

requires8 = pytest.mark.skipif(len(jax.devices()) < 8,
                               reason="needs the 8-device virtual mesh")


def _tree_equal(a, b):
    """Bit-equality over pytrees; NaN == NaN (a poisoned leaf carried
    through a discard must still compare equal)."""
    la, lb = jtu.tree_leaves(a), jtu.tree_leaves(b)
    if len(la) != len(lb):
        return False
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        eq_nan = np.issubdtype(x.dtype, np.floating)
        if not np.array_equal(x, y, equal_nan=eq_nan):
            return False
    return True


def _cfg(**kw):
    kw.setdefault("vocab", 32)
    kw.setdefault("d_model", 16)
    kw.setdefault("n_heads", 2)
    kw.setdefault("seq_len", 16)
    kw.setdefault("n_micro", 2)
    kw.setdefault("batch", 8)
    kw.setdefault("lr", 0.2)
    kw.setdefault("seed", 3)
    return lm3d.LMConfig(**kw)


def _run_pair(cfg, steps=3, poison=None, resync=False):
    """Run composed + oracle side by side on identical feeds/folds.
    ``resync``: the composed lane starts every step from the ORACLE's
    params, so each loss compares one forward pass and not a
    trajectory. Returns (losses_composed, losses_oracle, dropped_c,
    dropped_o, healths_c)."""
    mesh = cfg.mesh()
    params = lm3d.init_params(cfg)
    if poison is not None:
        params = poison(params)
    step = jax.jit(lm3d.make_train_step(cfg, mesh))
    ostep = jax.jit(lm3d.make_oracle_step(cfg))
    w = lm3d.sample_window(cfg, 0, steps)
    key = jax.random.PRNGKey(cfg.seed)
    p1 = lm3d.place_params(cfg, mesh, params)
    p2 = params
    a1, a2 = lm3d.init_amp_state(cfg, mesh), lm3d.init_amp_state(cfg)
    lc, lo, hc, dc, do = [], [], [], [], []
    for i in range(steps):
        xb, yb = jnp.asarray(w[i, ..., :-1]), jnp.asarray(w[i, ..., 1:])
        k = jax.random.fold_in(key, i)
        if resync:
            p1 = lm3d.place_params(cfg, mesh, p2)
        p1, a1, (l1, _, h1, d1) = step(p1, a1, xb, yb, k)
        p2, a2, (l2, _, h2, d2) = ostep(p2, a2, xb, yb, k)
        lc.append(float(l1))
        lo.append(float(l2))
        hc.append(bool(h1))
        dc.append(int(d1))
        do.append(int(d2))
    return lc, lo, dc, do, hc


# ------------------------------------------------------------ mesh + moe
@requires8
def test_mesh3d_axes_and_capacity_validation():
    mesh = mesh3d(2, 2, 2)
    assert mesh.axis_names == ("dp", "pp", "sp")
    assert dict(mesh.shape) == {"dp": 2, "pp": 2, "sp": 2}
    with pytest.raises(ValueError):
        mesh3d(4, 4, 4)  # 64 devices on an 8-device backend
    with pytest.raises(ValueError):
        lm3d.LMConfig(n_experts=3, dp=2)  # experts % dp
    with pytest.raises(ValueError):
        lm3d.LMConfig(seq_len=33, sp=2)


@requires8
# r19 fleet-PR buyback: lm3d-level drop accounting (~7s); test_moe::test_moe_capacity_drops_overflow pins the drop mechanics per-commit.
@pytest.mark.slow
def test_moe_counted_drops_match_zeroed_tokens():
    """return_dropped: the schedule-global drop count equals the number
    of tokens the capacity bound zeroed (cross-checked against the
    dense oracle), and is exactly 0 at ample capacity."""
    r = np.random.RandomState(4)
    x = jnp.asarray(r.normal(size=(8, 8, 16)), jnp.float32)
    gw = jnp.asarray(r.normal(size=(16, 8)) * 0.5, jnp.float32)
    w1 = jnp.asarray(r.normal(size=(8, 16, 32)) * 0.2, jnp.float32)
    b1 = jnp.asarray(r.normal(size=(8, 32)) * 0.1, jnp.float32)
    w2 = jnp.asarray(r.normal(size=(8, 32, 16)) * 0.2, jnp.float32)
    b2 = jnp.asarray(r.normal(size=(8, 16)) * 0.1, jnp.float32)
    mesh = expert_mesh(8)
    o, dropped = moe_ffn(x, gw, w1, b1, w2, b2, mesh,
                         capacity_factor=0.125, return_dropped=True)
    ref = moe_ffn_reference(x, gw, w1, b1, w2, b2)
    tok_o = np.asarray(o).reshape(-1, 16)
    tok_r = np.asarray(ref).reshape(-1, 16)
    is_dropped = np.isclose(tok_o, 0.0).all(axis=1) \
        & ~np.isclose(tok_r, 0.0).all(axis=1)
    assert int(dropped) == int(is_dropped.sum()) > 0
    o2, dropped2 = moe_ffn(x, gw, w1, b1, w2, b2, mesh,
                           capacity_factor=8.0, return_dropped=True)
    assert int(dropped2) == 0
    np.testing.assert_allclose(np.asarray(o2), tok_r.reshape(o2.shape),
                               rtol=2e-4, atol=2e-5)


@requires8
def test_gpipe_with_aux_counts_only_live_ticks():
    """Each (stage, microbatch) pair is live exactly once across the
    tick loop — bubbles contribute nothing — so a stage_fn emitting
    aux=1 totals n_stages * n_micro."""
    n_stages, n_micro, width = 4, 6, 8
    r = np.random.RandomState(0)
    per_stage = [{"w": jnp.asarray(r.normal(size=(width, width)) * 0.3,
                                   jnp.float32)} for _ in range(n_stages)]
    xs = jnp.asarray(r.normal(size=(n_micro, 2, width)), jnp.float32)
    mesh = pipeline_mesh(n_stages)

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"]), jnp.ones((), jnp.int32)

    ys, aux = gpipe(stage_fn, stack_stage_params(per_stage), xs,
                    mesh=mesh, with_aux=True)
    assert int(aux) == n_stages * n_micro

    def apply_all(x):
        for p in per_stage:
            x = jnp.tanh(x @ p["w"])
        return x
    ref = jax.vmap(apply_all)(xs)
    np.testing.assert_allclose(np.asarray(ys), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


@requires8
def test_gpipe_pass_micro_hands_each_tick_its_microbatch_index():
    """pass_micro: stage s's tick t computes microbatch t-s — adding
    the index to the activation must reproduce the sequential oracle
    that adds (stage-count × its python index)."""
    n_stages, n_micro, width = 2, 4, 4
    per_stage = [{"b": jnp.zeros((width,), jnp.float32)}
                 for _ in range(n_stages)]
    xs = jnp.asarray(np.random.RandomState(1).normal(
        size=(n_micro, 2, width)), jnp.float32)
    mesh = pipeline_mesh(n_stages)

    def stage_fn(p, x, micro):
        return x + micro.astype(x.dtype)

    ys = gpipe(stage_fn, stack_stage_params(per_stage), xs, mesh=mesh,
               pass_micro=True)
    ref = xs
    for _ in range(n_stages):  # one add per stage, same associativity
        ref = ref + jnp.arange(n_micro, dtype=xs.dtype)[:, None, None]
    np.testing.assert_array_equal(np.asarray(ys), np.asarray(ref))


# ------------------------------------------------------- lm3d lane parity
@requires8
# r19 fleet-PR buyback: full-3D+MoE oracle acceptance (~13s); the pp-only bit-identical parity below stays per-commit and the bench-scale slow acceptance re-proves the full composition.
@pytest.mark.slow
def test_lm3d_full_3d_moe_matches_oracle_and_guard_covers_it():
    """THE tentpole pin, one trace for the whole batch of claims: the
    full dp2×pp2×sp2 + 4-expert-MoE composition matches the oracle
    within documented tolerance with zero drops at ample capacity, and
    — same cfg, same compiled step — the guard composition: a NaN
    poisoned into a stage-1 weight (the fault surfaces inside the
    pipelined/sharded forward) flips the single per-step health scalar
    and the skip-mode discard reverts every param bit-exactly (PR 5
    semantics: pre-step state survives, poison included, for rollback
    to handle). The oracle reaches the same verdict from the same
    state. (The dense composition is pinned by the window-scan and
    pp-only tests plus the bench lane.)"""
    cfg = _cfg(dp=2, pp=2, sp=2, n_experts=4, capacity_factor=8.0)
    mesh = cfg.mesh()
    params = lm3d.init_params(cfg)
    step = jax.jit(lm3d.make_train_step(cfg, mesh))
    ostep = jax.jit(lm3d.make_oracle_step(cfg))
    w = lm3d.sample_window(cfg, 0, 3)
    key = jax.random.PRNGKey(cfg.seed)
    p1, p2 = lm3d.place_params(cfg, mesh, params), params
    for i in range(3):
        xb, yb = jnp.asarray(w[i, ..., :-1]), jnp.asarray(w[i, ..., 1:])
        k = jax.random.fold_in(key, i)
        p1, _, (l1, _, h1, d1) = step(p1, {}, xb, yb, k)
        p2, _, (l2, _, h2, d2) = ostep(p2, {}, xb, yb, k)
        assert bool(h1) and bool(h2)
        assert int(d1) == int(d2) == 0
        assert abs(float(l1) - float(l2)) / abs(float(l2)) < 2e-5

    poisoned = lm3d.init_params(cfg)
    wq = np.array(poisoned["stages"]["wq"])
    wq[1, 0, 0, 0] = np.nan  # stage 1, layer 0
    poisoned["stages"]["wq"] = jnp.asarray(wq)
    placed = lm3d.place_params(cfg, mesh, poisoned)
    xb, yb = jnp.asarray(w[0, ..., :-1]), jnp.asarray(w[0, ..., 1:])
    pg, _, (_, _, hg, _) = step(placed, {}, xb, yb, key)
    assert not bool(hg)
    assert _tree_equal(pg, placed)
    po, _, (_, _, ho, _) = ostep(poisoned, {}, xb, yb, key)
    assert not bool(ho)
    assert _tree_equal(po, poisoned)


@requires8
@pytest.mark.slow
def test_lm3d_moe_tight_capacity_counts_drops():
    """Switch-style capacity overflow: drops happen and are COUNTED on
    both the composed lane and the oracle (counts differ — capacity is
    per shard — but both must be nonzero and the lane keeps training)."""
    cfg = _cfg(dp=2, pp=2, sp=2, n_experts=4, capacity_factor=0.25,
               seed=5)
    lc, lo, dc, do, hc = _run_pair(cfg, steps=2)
    assert all(hc)
    assert all(d > 0 for d in dc) and all(d > 0 for d in do)
    assert all(np.isfinite(lc))


@requires8
def test_lm3d_pp_only_with_dropout_bit_identical_to_oracle():
    """pp-only composition: same forward fp ops in the same order (the
    gpipe output psum adds exact zeros) AND identical dropout masks via
    the (stage, layer, micro) rng-fold mirror — from the same params
    every step's loss is bit-equal. The free-running trajectory is held
    to a few ulp only: the installed XLA:CPU rounds the scan-transposed
    backward differently from the oracle's straight-line one (param
    grads differ in the last bit from step 0 on, dropout or not), and a
    wrong mask would show at 1e-2."""
    cfg = _cfg(dp=1, pp=2, sp=1, batch=4, dropout=0.2, seed=7)
    lc, lo, _, _, hc = _run_pair(cfg, resync=True)
    assert all(hc)
    assert lc == lo, (lc, lo)
    lc, lo, _, _, hc = _run_pair(cfg)
    assert all(hc)
    assert lc[0] == lo[0], (lc, lo)
    np.testing.assert_allclose(lc, lo, rtol=1e-6, atol=0)


@requires8
@pytest.mark.slow
# demoted r19 (suite-time buyback, 8s): the window×mesh scan contract
# keeps per-commit coverage via test_window_stack_through_gpipe_
# bit_identical_to_step_loop (the executor-level parity on the same
# mesh); the lm3d-lane window runner stays round-end full tier
def test_lm3d_window_scan_bit_identical_to_step_loop():
    """K steps as ONE scanned window == K sequential step() calls —
    losses AND final params bit-equal, dropout masks included (keys
    fold by global step index inside the scan)."""
    cfg = _cfg(dp=2, pp=2, sp=2, dropout=0.1)
    mesh = cfg.mesh()
    params = lm3d.place_params(cfg, mesh, lm3d.init_params(cfg))
    step = jax.jit(lm3d.make_train_step(cfg, mesh))
    win = jax.jit(lm3d.make_window_step(cfg, mesh))
    K = 4
    w = lm3d.sample_window(cfg, 0, K)
    key = jax.random.PRNGKey(cfg.seed)
    pw, aw, (lw, _, hw, _) = win(params, {}, lm3d.place_window(
        cfg, mesh, w), key, jnp.int32(0))
    p, a = params, {}
    ls = []
    for i in range(K):
        xb, yb = jnp.asarray(w[i, ..., :-1]), jnp.asarray(w[i, ..., 1:])
        p, a, (l, _, h, _) = step(p, a, xb, yb,
                                  jax.random.fold_in(key, i))
        ls.append(float(l))
    assert [float(x) for x in lw] == ls
    assert _tree_equal(pw, p)
    # steady state: a second window with fresh data retraces NOTHING
    # (params pre-placed at their steady-state shardings + the window's
    # post-scan output constraint — lm3d.place_params / _stage_specs)
    w2 = lm3d.sample_window(cfg, K, K)
    pw, aw, _ = win(pw, aw, lm3d.place_window(cfg, mesh, w2), key,
                    jnp.int32(K))
    assert win._cache_size() == 1


# --------------------------------------------------- guard + AMP epilogue
@requires8
# r19 fleet-PR buyback: amp trip transition (~6s); test_quant_amp pins the dynamic-scale transition per-commit.
@pytest.mark.slow
def test_lm3d_amp_trip_discards_and_halves_scale():
    """amp=True: a tripped step keeps params bit-exact and runs the
    PR 5 dynamic loss-scale transition (scale × decr_ratio) off the
    SAME health scalar; a following clean step trains and counts
    good."""
    cfg = _cfg(dp=2, pp=2, sp=2, amp=True)
    mesh = cfg.mesh()
    params = lm3d.init_params(cfg)
    head = np.array(params["head"])
    head[0, 0] = np.inf
    poisoned = dict(params, head=jnp.asarray(head))
    placed = lm3d.place_params(cfg, mesh, poisoned)
    amp = lm3d.init_amp_state(cfg, mesh)
    step = jax.jit(lm3d.make_train_step(cfg, mesh))
    w = lm3d.sample_window(cfg, 0, 1)
    xb, yb = jnp.asarray(w[0, ..., :-1]), jnp.asarray(w[0, ..., 1:])
    p1, amp1, (_, _, h1, _) = step(placed, amp, xb, yb,
                                   jax.random.PRNGKey(0))
    assert not bool(h1)
    assert _tree_equal(p1, placed)
    assert float(amp1["scale"][0]) == lm3d.INIT_LOSS_SCALE * 0.5
    assert int(amp1["bad"][0]) == 0  # decr fired, counter reset
    # clean params: trains, health True, good counter advances
    clean = lm3d.place_params(cfg, mesh, params)
    p2, amp2, (l2, _, h2, _) = step(clean, lm3d.init_amp_state(
        cfg, mesh), xb, yb, jax.random.PRNGKey(0))
    assert bool(h2) and np.isfinite(float(l2))
    assert int(amp2["good"][0]) == 1
    assert not _tree_equal(p2, clean)


# ------------------------------------- executor: window × GPipe programs
def _build_pipelined_mlp(n_stages=2, width=8, lr=0.1, n_micro=4):
    from paddle_tpu.fluid.framework import program_guard
    main, startup = fluid.Program(), fluid.Program()
    with program_guard(main, startup):
        x = fluid.data("x", shape=[width], dtype="float32")
        label = fluid.data("label", shape=[1], dtype="float32")
        h = fluid.layers.fc(x, width, act="tanh",
                            param_attr=fluid.ParamAttr(name="pre_w"))
        cuts = [h]
        for i in range(n_stages):
            h = fluid.layers.fc(
                h, width, act="tanh",
                param_attr=fluid.ParamAttr(name=f"s{i}_w"),
                bias_attr=fluid.ParamAttr(name=f"s{i}_b"))
            cuts.append(h)
        pred = fluid.layers.fc(h, 1,
                               param_attr=fluid.ParamAttr(name="head_w"))
        loss = fluid.layers.mean(fluid.layers.square(
            fluid.layers.elementwise_sub(pred, label)))
        opt = fluid.optimizer.PipelineOptimizer(
            fluid.optimizer.SGD(lr), cut_list=cuts, sync_steps=n_micro)
        opt.minimize(loss)
    return main, startup, loss


def _window_feeds(k=4, batch=8, width=8, seed=0):
    r = np.random.RandomState(seed)
    return (r.rand(k, batch, width).astype("float32"),
            r.rand(k, batch, 1).astype("float32"))


def _run_pipelined(mesh, windowed, k=4, n_stages=2, profile=False):
    main, startup, loss = _build_pipelined_mlp(n_stages=n_stages)
    exe = fluid.Executor()
    scope = core.Scope()
    X, Y = _window_feeds(k)
    with fluid.scope_guard(scope):
        exe.run(startup)
        if windowed:
            out = exe.run(main, feed={"x": X, "label": Y},
                          fetch_list=[loss], mesh=mesh, n_steps=k)
            losses = [float(v) for v in np.asarray(out[0]).ravel()]
        else:
            losses = []
            for i in range(k):
                (l,) = exe.run(main, feed={"x": X[i], "label": Y[i]},
                               fetch_list=[loss], mesh=mesh)
                losses.append(float(np.asarray(l).ravel()[0]))
        w = np.asarray(scope.find_var("s0_w").get_tensor().array).copy()
    return losses, w


@requires8
# r19 fleet-PR buyback: window-stack parity (~13s); the executor-level windowed-guard + dataloader-window twins below stay per-commit.
@pytest.mark.slow
def test_window_stack_through_gpipe_bit_identical_to_step_loop():
    """The tentpole executor contract: a K-window feed consumed by a
    PipelineOptimizer-sectioned program on the pp mesh scans as ONE
    dispatch (microbatch slices carved on-device) and is bit-identical
    to the K sequential per-step loop. Any gpipe-lowering fallback
    warning fails the test — the schedule must actually pipeline; the
    profiler must show ONE cat="window" realdata span (the scan), not a
    :fallback span wrapping K per-step re-feeds."""
    mesh = pipeline_mesh(2)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        profiler.start_profiler("All")
        try:
            lw, ww = _run_pipelined(mesh, windowed=True)
            events = [e for e in profiler.snapshot_events()
                      if e.get("cat") == "window"]
        finally:
            profiler.stop_profiler()
        ll, wl = _run_pipelined(mesh, windowed=False)
    assert lw == ll
    np.testing.assert_array_equal(ww, wl)
    assert len(events) == 1, events
    assert "realdata" in events[0]["name"]


@requires8
# r19 fleet-PR buyback: raise-mode fallback parity (~6s); the executor-level per-step fallback tests stay per-commit.
@pytest.mark.slow
def test_window_raise_mode_falls_back_per_step_and_matches():
    """raise is the debugging action: the mesh window takes the
    documented per-step fallback (the localizer needs per-step rng
    context) and stays bit-identical to the explicit loop."""
    mesh = pipeline_mesh(2)
    prev = (core.globals_["FLAGS_check_nan_inf"],
            core.globals_["FLAGS_nan_inf_action"])
    core.set_flag("FLAGS_check_nan_inf", True)
    core.set_flag("FLAGS_nan_inf_action", "raise")
    try:
        profiler.start_profiler("All")
        try:
            lw, ww = _run_pipelined(mesh, windowed=True)
            events = [e for e in profiler.snapshot_events()
                      if e.get("cat") == "window"]
        finally:
            profiler.stop_profiler()
        ll, wl = _run_pipelined(mesh, windowed=False)
    finally:
        core.set_flag("FLAGS_check_nan_inf", prev[0])
        core.set_flag("FLAGS_nan_inf_action", prev[1])
    assert lw == ll
    np.testing.assert_array_equal(ww, wl)
    assert any("fallback" in e["name"] for e in events)


@requires8
def test_windowed_guard_skip_on_mesh_matches_per_step_loop():
    """skip-mode guard composed with the mesh window scan: a poisoned
    slice trips that step's carried health flag, its update is
    discarded in-scan, and the whole trajectory stays bit-identical to
    the guarded per-step loop (healths ride the scan carry — PR 5's
    window contract, now on the mesh path)."""
    mesh = pipeline_mesh(2)
    prev = (core.globals_["FLAGS_check_nan_inf"],
            core.globals_["FLAGS_nan_inf_action"])
    core.set_flag("FLAGS_check_nan_inf", True)
    core.set_flag("FLAGS_nan_inf_action", "skip")
    try:
        k = 4
        X, Y = _window_feeds(k)
        X[1, 0, 0] = np.nan  # poison slice 1

        def run(windowed):
            main, startup, loss = _build_pipelined_mlp()
            exe = fluid.Executor()
            scope = core.Scope()
            with fluid.scope_guard(scope):
                exe.run(startup)
                if windowed:
                    out = exe.run(main, feed={"x": X, "label": Y},
                                  fetch_list=[loss], mesh=mesh,
                                  n_steps=k)
                    ls = [float(v) for v in np.asarray(out[0]).ravel()]
                else:
                    ls = []
                    for i in range(k):
                        (l,) = exe.run(main,
                                       feed={"x": X[i], "label": Y[i]},
                                       fetch_list=[loss], mesh=mesh)
                        ls.append(float(np.asarray(l).ravel()[0]))
                w = np.asarray(
                    scope.find_var("s0_w").get_tensor().array).copy()
            return ls, w

        lw, ww = run(True)
        ll, wl = run(False)
    finally:
        core.set_flag("FLAGS_check_nan_inf", prev[0])
        core.set_flag("FLAGS_nan_inf_action", prev[1])
    assert np.isnan(lw[1]) and np.isnan(ll[1])  # the fetch shows it
    assert np.isfinite(lw[3]) and lw[2:] == ll[2:] and lw[0] == ll[0]
    np.testing.assert_array_equal(ww, wl)  # discarded identically


@requires8
def test_window_stack_on_dp_mesh_shards_batch_dim():
    """A plain (non-pipelined) program's window stack on a dp mesh:
    dim 1 shards over "dp", the window scans in one dispatch, and the
    trajectory equals the per-step mesh loop bit-for-bit."""
    mesh = build_mesh(8)
    k, batch, width = 4, 16, 8

    def build():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.data("x", shape=[width], dtype="float32")
            y = fluid.data("y", shape=[1], dtype="float32")
            h = fluid.layers.fc(x, width, act="tanh",
                                param_attr=fluid.ParamAttr(name="w0"))
            p = fluid.layers.fc(h, 1,
                                param_attr=fluid.ParamAttr(name="w1"))
            loss = fluid.layers.mean(fluid.layers.square(
                fluid.layers.elementwise_sub(p, y)))
            fluid.optimizer.SGD(0.1).minimize(loss)
        return main, startup, loss

    def run(windowed):
        main, startup, loss = build()
        exe = fluid.Executor()
        scope = core.Scope()
        r = np.random.RandomState(0)
        X = r.rand(k, batch, width).astype("float32")
        Y = r.rand(k, batch, 1).astype("float32")
        with fluid.scope_guard(scope):
            exe.run(startup)
            if windowed:
                out = exe.run(main, feed={"x": X, "y": Y},
                              fetch_list=[loss], mesh=mesh, n_steps=k)
                ls = [float(v) for v in np.asarray(out[0]).ravel()]
            else:
                ls = []
                for i in range(k):
                    (l,) = exe.run(main, feed={"x": X[i], "y": Y[i]},
                                   fetch_list=[loss], mesh=mesh)
                    ls.append(float(np.asarray(l).ravel()[0]))
        return ls

    assert run(True) == run(False)


@requires8
def test_dataloader_window_batch_scans_on_mesh():
    """DataLoader.window(k) WindowBatch stacks feed the mesh scan path
    directly — one device_put per window, no per-step re-feed — and
    match the sequential per-step loop."""
    from paddle_tpu.fluid.reader import DataLoader
    mesh = pipeline_mesh(2)
    k, batch, width = 4, 8, 8
    r = np.random.RandomState(2)
    X = r.rand(k * batch, width).astype("float32")
    Y = r.rand(k * batch, 1).astype("float32")
    batches = [{"x": X[i * batch:(i + 1) * batch],
                "label": Y[i * batch:(i + 1) * batch]}
               for i in range(k)]

    main, startup, loss = _build_pipelined_mlp()
    exe = fluid.Executor()
    scope = core.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        loader = DataLoader.from_generator(capacity=4)
        loader.set_batch_generator(lambda: iter(batches))
        got = []
        for wb in loader.window(k):
            out = exe.run(main, feed=wb, fetch_list=[loss], mesh=mesh)
            got.extend(float(v) for v in np.asarray(out[0]).ravel())
        w_win = np.asarray(
            scope.find_var("s0_w").get_tensor().array).copy()

    main2, startup2, loss2 = _build_pipelined_mlp()
    exe2 = fluid.Executor()
    scope2 = core.Scope()
    with fluid.scope_guard(scope2):
        exe2.run(startup2)
        ref = []
        for i in range(k):
            (l,) = exe2.run(main2, feed={"x": X[i * batch:(i + 1) * batch],
                                         "label": Y[i * batch:(i + 1) * batch]},
                            fetch_list=[loss2], mesh=mesh)
            ref.append(float(np.asarray(l).ravel()[0]))
        w_ref = np.asarray(
            scope2.find_var("s0_w").get_tensor().array).copy()
    assert got == ref
    np.testing.assert_array_equal(w_win, w_ref)


# ------------------------------------------------------------ slow lane
@requires8
@pytest.mark.slow
def test_lm3d_bench_scale_composition_trains():
    """Bench-shape acceptance: the dp2×pp2×sp2 MoE lane trains (loss
    decreases over 48 steps), never retraces after the first window,
    and counts zero drops at ample capacity."""
    cfg = lm3d.LMConfig(vocab=128, d_model=64, n_heads=4, seq_len=64,
                        dp=2, pp=2, sp=2, n_micro=4, batch=16,
                        n_experts=4, capacity_factor=8.0, lr=0.1,
                        seed=1)
    mesh = cfg.mesh()
    p = lm3d.place_params(cfg, mesh, lm3d.init_params(cfg))
    win = jax.jit(lm3d.make_window_step(cfg, mesh))
    key = jax.random.PRNGKey(1)
    a = {}
    K = 8
    first = None
    for r in range(6):
        w = lm3d.place_window(cfg, mesh, lm3d.sample_window(cfg, r * K,
                                                            K))
        p, a, outs = win(p, a, w, key, jnp.int32(r * K))
        if first is None:
            first = float(outs[0][0])
    last = float(outs[0][-1])
    assert last < first
    assert int(outs[3][-1]) == 0
    assert win._cache_size() == 1
