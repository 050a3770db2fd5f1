"""Phi-4-mini-flash-reasoning (SambaY) at a tiny width on the CPU: each
new op against the plain reference
(benchmark/configs/phi4_mini_flash_reference.py), the windowed attention
dense and through the Pallas kernels in interpret mode, the whole
program's loss and gradients against the reference, and the recompute
lowering carrying the memory, the kept K / V and the tied embedding
across segments.
"""
import contextlib
import importlib.util
import os
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import core, layers, telemetry
from paddle_tpu.models import phi4_flash
from paddle_tpu.ops import attention_ops, decoder_ops
from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import selective_scan as ss

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    spec = importlib.util.spec_from_file_location(
        "_phi4_reference", os.path.join(
            REPO, "benchmark", "configs", "phi4_mini_flash_reference.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _reference()
CFG = dict(vocab_size=96, hidden=32, heads=8, kv_heads=4, head_dim=8,
           mlp_width=48, window=24, eps=1e-5, d_inner=64, d_state=4,
           d_conv=4, dt_rank=2,
           layer_kinds=["mamba", "sliding", "mamba_memory", "full", "gmu",
                        "cross"],
           published_index=[0, 1, 16, 17, 18, 19], init_std=0.02)
SEQ = 75  # not a multiple of the window, of the scan's chunk, of a block


def _normal(rng, *shape):
    return jnp.asarray(rng.normal(size=shape), jnp.float32)


def _close(a, b, tol):
    scale = max(float(np.abs(np.asarray(b)).max()), 1e-30)
    assert float(np.abs(np.asarray(a) - np.asarray(b)).max()) / scale <= tol


# ------------------------------------------------------- the release's rule
def test_layer_kinds_of_32_is_the_release_s_list():
    want = ["mamba", "sliding"] * 8 + ["mamba_memory", "full"] \
        + ["gmu", "cross"] * 7
    assert phi4_flash.layer_kinds(32) == want == REF.layer_kinds(32)
    assert phi4_flash.phi4_flash_config()["layer_kinds"] == want


def test_the_six_kind_cut_is_made_of_the_rule_s_periods():
    kinds, index = CFG["layer_kinds"], CFG["published_index"]
    full = phi4_flash.layer_kinds(32)
    assert [full[i] for i in index] == kinds
    # the first period of each decoder and the pair between them, whole
    assert index == [0, 1, 16, 17, 18, 19]
    assert sorted(set(full)) == sorted(set(kinds))


@pytest.mark.parametrize("index,want", [(1, 0.35550907), (17, 0.79634195),
                                        (19, 0.79799240)])
def test_lambda_init_follows_the_published_index(index, want):
    assert phi4_flash.lambda_init(index) == pytest.approx(want, abs=1e-7)
    assert REF.lambda_init(index) == phi4_flash.lambda_init(index)


# ------------------------------------------------------------ selective_scan
def _scan_case(seq, channels=12, states=4, seed=0):
    rng = np.random.default_rng(seed)
    a_log = jnp.asarray(np.log(np.tile(np.arange(1, states + 1),
                                       (channels, 1))), jnp.float32)
    return dict(
        X=_normal(rng, 2, seq, channels), Dt=_normal(rng, 2, seq, channels),
        B=_normal(rng, 2, seq, states), C=_normal(rng, 2, seq, states),
        ALog=a_log, D=jnp.ones((channels,), jnp.float32),
        DtBias=_normal(rng, channels) - 2.0)


def _scan_op(chunk):
    def run(X, Dt, B, C, ALog, D, DtBias):
        return decoder_ops._selective_scan(
            {k: [v] for k, v in dict(X=X, Dt=Dt, B=B, C=C, ALog=ALog, D=D,
                                     DtBias=DtBias).items()},
            {"chunk_size": chunk, "site": "test"})["Out"][0]
    return run


def _scan_reference(X, Dt, B, C, ALog, D, DtBias):
    return REF.selective_scan(X, jax.nn.softplus(Dt + DtBias),
                              -jnp.exp(ALog), B, C, D)


@pytest.mark.parametrize("seq,chunk", [(75, 16), (64, 64), (30, 64), (33, 1)])
def test_selective_scan_in_chunks_is_the_step_by_step_recurrence(seq, chunk):
    case = _scan_case(seq)
    _close(_scan_op(chunk)(**case), _scan_reference(**case), 2e-6)


@pytest.mark.parametrize("wrt", ["X", "Dt", "B", "C", "ALog", "D", "DtBias"])
def test_selective_scan_s_own_backward_matches_the_recurrence_s(wrt):
    case = _scan_case(75)
    weight = _normal(np.random.default_rng(1), 2, 75, 12)

    def grad(fn):
        return jax.grad(lambda v: jnp.sum(
            fn(**{**case, wrt: v}) * weight))(case[wrt])

    _close(grad(_scan_op(16)), grad(_scan_reference), 2e-5)


def test_selective_scan_keeps_one_state_a_chunk_and_says_so():
    case = _scan_case(75)
    _, residuals = decoder_ops._chunked_scan_fwd(
        *(jnp.zeros((5, 2, 16, n), jnp.float32) for n in (12, 12, 4, 4)),
        -jnp.exp(case["ALog"]))
    assert residuals[-1].shape == (5, 2, 12, 4)  # chunk starts, not S states
    _scan_op(16)(**case)
    assert telemetry.REGISTRY.get("ssm_chunks_per_step").value(
        site="test") == 2 * 5
    assert telemetry.REGISTRY.get("ssm_state_bytes").value(
        site="test") == 2 * 5 * 12 * 4 * 4



# ------------------------------------- the scan's Pallas kernels, interpreted
@pytest.mark.parametrize("seq,chunk,channels,blocks", [
    (75, 16, 12, (128, 16)), (64, 64, 12, None), (30, 64, 12, None),
    (33, 1, 12, (128, 8)), (40, 8, 300, (128, 16))],
    ids=["s75_c16", "s64_c64", "s30_c64", "s33_c1", "300_channels"])
def test_scan_kernels_are_the_step_by_step_recurrence(seq, chunk, channels,
                                                      blocks):
    """The four (seq, chunk) cases of the `lax.scan` lowering above, and
    300 channels: padded to 384, three blocks of 128 (``blocks`` None:
    the pair `_block_sizes` chooses)."""
    case = _scan_case(seq, channels)
    pinned = ss.block_override(*blocks) if blocks else contextlib.nullcontext()
    with fa.interpret_guard(), pinned:
        got = _scan_op(chunk)(**case)
    _close(got, _scan_reference(**case), 2e-6)


def _all_gradients(fn, case, weight):
    names = sorted(case)
    grads = jax.grad(lambda *vs: jnp.sum(fn(**dict(zip(names, vs))) * weight),
                     argnums=tuple(range(len(names))))(
        *(case[n] for n in names))
    return dict(zip(names, grads))


@pytest.fixture(scope="module")
def scan_kernel_gradients():
    """The seven gradients through the kernel pair, five position blocks
    of a chunk each, and the reference's."""
    case = _scan_case(75)
    weight = _normal(np.random.default_rng(1), 2, 75, 12)
    with fa.interpret_guard(), ss.block_override(128, 16):
        got = _all_gradients(_scan_op(16), case, weight)
    return got, _all_gradients(_scan_reference, case, weight)


@pytest.mark.parametrize("wrt", ["X", "Dt", "B", "C", "ALog", "D", "DtBias"])
def test_scan_kernels_backward_matches_the_recurrence_s(
        scan_kernel_gradients, wrt):
    got, want = scan_kernel_gradients
    _close(got[wrt], want[wrt], 2e-5)


def test_scan_kernels_and_lax_scan_agree_over_several_blocks():
    """Three channel blocks x five position blocks, the output and the
    seven gradients, one lowering against the other."""
    case = _scan_case(75, channels=300)
    weight = _normal(np.random.default_rng(2), 2, 75, 300)
    assert ss.grid_steps(2, 300, 80, 4, 16) == 2 * 1 * 2  # as chosen
    with fa.interpret_guard(), ss.block_override(128, 16):
        assert ss.grid_steps(2, 300, 80, 4, 16) == 2 * 3 * 5
        out = _scan_op(16)(**case)
        grads = _all_gradients(_scan_op(16), case, weight)
    _close(out, _scan_op(16)(**case), 2e-6)
    want = _all_gradients(_scan_op(16), case, weight)
    for name in want:
        _close(grads[name], want[name], 2e-5)


def test_scan_kernels_keep_one_state_a_chunk_too():
    case = _scan_case(75)
    chunks = [jnp.zeros((5, 2, 16, n), jnp.float32) for n in (12, 12, 4, 4)]
    with fa.interpret_guard(), ss.block_override(128, 16):
        _, residuals = decoder_ops._chunked_scan_fwd(
            *chunks, -jnp.exp(case["ALog"]), True)
        decoder_ops._selective_scan(
            {k: [v] for k, v in case.items()},
            {"chunk_size": 16, "site": "kept"})
    # [B, chunks, N, channels padded to a block]: chunk starts, not S states
    assert residuals[-1].shape == (2, 5, 4, 128)
    assert telemetry.REGISTRY.get("ssm_state_bytes").value(
        site="kept") == 2 * 5 * 12 * 4 * 4


def test_the_scan_says_which_lowering_ran():
    """`ssm_grid_steps_per_step` is set on the kernels' path alone; a
    step traced under a mesh, a backend without the kernels and a chunk
    whose states do not fit VMEM keep `lax.scan`."""
    case = {k: [v] for k, v in _scan_case(16).items()}
    sites = lambda: {c.labels_dict["site"] for c in telemetry.REGISTRY.get(
        "ssm_grid_steps_per_step").children()}
    assert not ss.use_kernels()
    with fa.interpret_guard():
        assert ss.use_kernels()
        with fa.mesh_guard(object()):
            assert not ss.use_kernels()
        decoder_ops._selective_scan(case, {"chunk_size": 8, "site": "on"})
    decoder_ops._selective_scan(case, {"chunk_size": 8, "site": "off"})
    assert "on" in sites() and "off" not in sites()
    assert telemetry.REGISTRY.get("ssm_grid_steps_per_step").value(
        site="on") == 2 * 1 * 1
    # the Phi cell's call: ten 128-lane tiles a block, one chunk a step
    assert ss._block_sizes(5120, 16, 4096, 64) == (1280, 64)
    assert ss.grid_steps(1, 5120, 4096, 16, 64) == 4 * 64
    assert ss._block_sizes(5120, 16, 8192, 8192) is None


# ---------------------------------------------------------- window attention
def _masked_reference(q, k, v, scale, window):
    """softmax over keys t - w < j <= t, written out."""
    s = q.shape[2]
    at, key = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = (key <= at) & ((key > at - window) if window else True)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
    return jnp.einsum("bhqk,bhkv->bhqv", p, v)


def _attention_case():
    rng = np.random.default_rng(2)
    return (_normal(rng, 1, 2, SEQ, 8), _normal(rng, 1, 2, SEQ, 8),
            _normal(rng, 1, 2, SEQ, 16), _normal(rng, 1, 2, SEQ, 16))


@pytest.mark.parametrize("window", [0, 16, 20, 33])
@pytest.mark.parametrize("path", ["dense", "kernels"])
def test_window_attention_matches_the_masked_reference(path, window):
    """Forward and the three gradients, V twice as wide as Q and K, at a
    length that is no multiple of the window or of the 16-row blocks."""
    q, k, v, weight = _attention_case()
    mask = fa.Mask(True, window)

    def run(q, k, v):
        if path == "dense":
            return attention_ops._dense_attention(q, k, v, 0.3, mask)
        return fa.flash_attention(q, k, v, 0.3, mask)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * weight)

    def want(q, k, v):
        return _masked_reference(q, k, v, 0.3, window)

    with fa.interpret_guard(), fa.block_override(16, 16):
        got = run(q, k, v)
        grads = jax.grad(loss(run), argnums=(0, 1, 2))(q, k, v)
    _close(got, want(q, k, v), 2e-6)
    for g, w in zip(grads, jax.grad(loss(want), argnums=(0, 1, 2))(q, k, v)):
        _close(g, w, 1e-5)


@pytest.mark.parametrize("window,pairs", [(0, 528), (512, 150), (128, 63),
                                          (4096, 528)])
def test_the_window_s_kernels_visit_no_block_outside_it(window, pairs):
    """Block pairs a head's forward kernel computes at s4096 in 128-row
    blocks: the causal 32 x 33 / 2, and under window 512 the 5 blocks
    that hold a key of some row's window (1 + 2 + 3 + 4 + 28 x 5)."""
    assert fa.visited_blocks(4096, 4096, 128, 128,
                             fa.Mask(True, window)) == pairs
    if window:
        assert fa._window_count(4096, 4096, 128, 128, window, True) \
            == min(32, (window - 2) // 128 + 2)


def test_the_attention_op_counts_its_block_pairs_a_site():
    rng = np.random.default_rng(3)
    ins = {"Q": [_normal(rng, 1, 40, 4 * 8)],
           "K": [_normal(rng, 1, 40, 2 * 8)],
           "V": [_normal(rng, 1, 40, 1 * 16)]}
    attrs = {"num_heads": 4, "num_kv_heads": 2, "num_v_heads": 1,
             "causal": True, "window": 10, "site": "w10"}
    old = attention_ops.DENSE_MAX_SEQ
    attention_ops.DENSE_MAX_SEQ = 16
    try:
        with fa.interpret_guard(), fa.block_override(8, 8):
            out = attention_ops._fused_attention_qkv(ins, attrs)["Out"][0]
    finally:
        attention_ops.DENSE_MAX_SEQ = old
    assert out.shape == (1, 40, 4 * 16)
    # 5 row blocks of 8: 1 + 2 + 3 x 3 blocks hold a key of a row's window
    assert telemetry.REGISTRY.get("attn_kv_blocks_per_step").value(
        site="w10") == 4 * fa.visited_blocks(40, 40, 8, 8, fa.Mask(True, 10))
    assert fa.visited_blocks(40, 40, 8, 8, fa.Mask(True, 10)) == 1 + 2 + 3 * 3


# ------------------------------------------------ differential pieces, GMU
def _layer_case(seed=4):
    rng = np.random.default_rng(seed)
    return _normal(rng, 2, SEQ, CFG["hidden"])


def _run_layer(build, feed):
    """(fetched outputs, {parameter: value}) of a forward-only program."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    with fluid.program_guard(main, startup):
        fetches = build()
    exe, scope = fluid.Executor(fluid.CPUPlace()), core.Scope()
    exe.run(startup, scope=scope)
    got = exe.run(main, feed=feed, scope=scope,
                  fetch_list=[f.name for f in fetches])
    params = {p.name: jnp.asarray(scope.find_var(p.name).get_tensor().array)
              for p in main.global_block().all_parameters()}
    return got, params


def test_differential_combine_is_the_two_maps_difference():
    rng = np.random.default_rng(5)
    x = _normal(rng, 2, 9, 2 * 2 * 2 * 16)  # [group, map, head, dv]
    vectors = [_normal(rng, 8) * 0.3 for _ in range(4)]
    got = decoder_ops._differential_combine(
        {"X": [x], "LambdaQ1": [vectors[0]], "LambdaK1": [vectors[1]],
         "LambdaQ2": [vectors[2]], "LambdaK2": [vectors[3]]},
        {"num_groups": 2, "lambda_init": 0.36})["Out"][0]
    lam = jnp.exp(vectors[0] @ vectors[1]) - jnp.exp(vectors[2] @ vectors[3]) \
        + 0.36
    maps = x.reshape(2, 9, 2, 2, 2, 16)
    _close(got, (maps[:, :, :, 0] - lam * maps[:, :, :, 1]).reshape(2, 9, -1),
           1e-6)


@pytest.mark.parametrize("kind", ["sliding", "full"])
def test_self_attention_layer_matches_the_reference(kind):
    x = _layer_case()
    window = CFG["window"] if kind == "sliding" else 0

    def build():
        h = fluid.data("h", shape=[SEQ, CFG["hidden"]], dtype="float32")
        return phi4_flash.self_attention(h, "attn.", CFG, window, 0.36)

    (y, k, v), params = _run_layer(build, {"h": np.asarray(x)})
    want, k_ref, v_ref = REF.self_attention(
        {n[len("attn."):]: w for n, w in params.items()}, x, CFG, window,
        0.36)
    _close(y, want, 1e-5)
    _close(k, k_ref.reshape(2, SEQ, -1), 1e-6)
    _close(v, v_ref.reshape(2, SEQ, -1), 1e-6)


def test_gated_memory_unit_matches_the_reference():
    x, m = _layer_case(6), _normal(np.random.default_rng(7), 2, SEQ,
                                   CFG["d_inner"])

    def build():
        h = fluid.data("h", shape=[SEQ, CFG["hidden"]], dtype="float32")
        mem = fluid.data("m", shape=[SEQ, CFG["d_inner"]], dtype="float32")
        return [phi4_flash.gated_memory_unit(h, mem, "gmu.", CFG)]

    (y,), params = _run_layer(build, {"h": np.asarray(x), "m": np.asarray(m)})
    _close(y, REF.gmu({n[len("gmu."):]: w for n, w in params.items()}, x, m),
           1e-5)


def test_mamba_layer_matches_the_reference_and_hands_on_its_scan():
    x = _layer_case(8)

    def build():
        h = fluid.data("h", shape=[SEQ, CFG["hidden"]], dtype="float32")
        return phi4_flash.mamba(h, "mamba.", CFG)

    (y, m), params = _run_layer(build, {"h": np.asarray(x)})
    want, m_ref = REF.mamba({n[len("mamba."):]: w for n, w in params.items()},
                            x, CFG)
    _close(y, want, 1e-5)
    _close(m, m_ref, 1e-5)


# ------------------------------------------------------------ the whole model
@pytest.fixture(scope="module")
def trained_once():
    """{recompute: (loss, {parameter: gradient}, the compiled step)} of
    one step of the six-layer program on one batch, and the reference's
    (loss, gradients) at the same weights."""
    out = {}
    feed = phi4_flash.synthetic_pretrain_batch(CFG, 2, SEQ, 3)
    for recompute in (False, True):
        with warnings.catch_warnings():
            warnings.simplefilter("error")  # a fallback warning fails
            main, startup, _, fetches = \
                phi4_flash.build_phi4_flash_pretrain_program(
                    CFG, seq_len=SEQ, lr=1e-3, recompute=recompute)
            main.random_seed = startup.random_seed = 7
            exe, scope = fluid.Executor(fluid.CPUPlace()), core.Scope()
            exe.run(startup, scope=scope)
            names = [p.name for p in main.global_block().all_parameters()]
            # copied out before the step donates the scope's arrays
            weights = {n: jnp.asarray(np.array(
                scope.find_var(n).get_tensor().array)) for n in names}
            got = exe.run(main, feed=feed, scope=scope,
                          fetch_list=[fetches[0].name]
                          + [n + "@GRAD" for n in names])
        step = list(exe._compiled_cache.values())[-1]
        out[recompute] = (float(np.asarray(got[0]).ravel()[0]),
                          dict(zip(names, got[1:])), step)
    out["weights"], out["feed"] = weights, feed
    out["reference"] = REF.loss_and_grads(
        weights, jnp.asarray(feed["ids"]), jnp.asarray(feed["labels"][..., 0]),
        CFG)
    return out


@pytest.mark.parametrize("recompute", [False, True])
def test_the_program_s_loss_is_the_reference_s(trained_once, recompute):
    assert trained_once[recompute][0] == pytest.approx(
        float(trained_once["reference"][0]), abs=2e-6)


@pytest.mark.parametrize("name", [
    "embed_tokens",                 # tied: the lookup's part + the head's
    "layers.0.mamba.w_in", "layers.0.mamba.conv_w", "layers.0.mamba.conv_b",
    "layers.0.mamba.w_x", "layers.0.mamba.w_dt", "layers.0.mamba.dt_bias",
    "layers.0.mamba.a_log", "layers.0.mamba.d", "layers.0.mamba.w_out",
    "layers.1.attn.w_qkv", "layers.1.attn.lambda_q1", "layers.1.attn.subln",
    "layers.1.attn.b_o",
    "layers.2.mamba.w_x",           # the memory layer: the GMU's part too
    "layers.3.attn.w_qkv",          # K, V: cross-attention's part too
    "layers.3.attn.b_qkv",
    "layers.4.gmu.w_in", "layers.4.gmu.w_out",
    "layers.5.cross.w_q", "layers.5.cross.lambda_k2", "layers.5.cross.w_o",
    "layers.5.mlp.w_gate_up", "layers.5.ln2.b", "final_norm.w"])
@pytest.mark.parametrize("recompute", [False, True])
def test_the_program_s_gradients_are_the_reference_s(trained_once, recompute,
                                                     name):
    _close(trained_once[recompute][1][name],
           trained_once["reference"][1][name], 2e-4)


def test_cross_attention_s_gradient_reaches_the_full_layer_s_wqkv(
        trained_once, monkeypatch):
    """Layer 3's K and V feed two readers, its own attention and layer
    5's cross-attention, so the K and V columns of its Wqkv get the sum
    of two parts: the program's gradient is the reference's whole one,
    and is NOT what the reference gives with the cross layer's part cut
    (its k, v under stop_gradient), which moves the K, V columns and
    leaves the Q columns alone."""
    name, q_width = "layers.3.attn.w_qkv", CFG["heads"] * CFG["head_dim"]
    weights, feed = trained_once["weights"], trained_once["feed"]
    args = (weights, jnp.asarray(feed["ids"]),
            jnp.asarray(feed["labels"][..., 0]), CFG, [name])
    whole = np.asarray(trained_once["reference"][1][name])
    cross = REF.cross_attention
    monkeypatch.setattr(
        REF, "cross_attention", lambda p, x, k, v, *rest: cross(
            p, x, jax.lax.stop_gradient(k), jax.lax.stop_gradient(v), *rest))
    own = np.asarray(REF.loss_and_grads(*args)[1][name])
    _close(own[:, :q_width], whole[:, :q_width], 1e-6)
    part = np.abs(whole[:, q_width:] - own[:, q_width:]).max()
    assert part > 0.05 * np.abs(whole[:, q_width:]).max()
    for recompute in (False, True):
        _close(np.asarray(trained_once[recompute][1][name])[:, q_width:],
               whole[:, q_width:], 2e-4)


def test_the_recompute_plan_is_not_the_fallback(trained_once):
    """Seven segments (six layers and the head); the memory, K and V
    leave their layers' segments as outputs; the tied embedding is read
    before the first segment and by the last."""
    plain, remat = trained_once[False][2], trained_once[True][2]
    assert plain._remat_plan is None and remat._remat_plan is not None
    plan = remat._remat_plan
    assert len(plan.segments) == 7
    assert "embed_tokens" in plan.segments[-1].ins
    assert [len(s.outs) for s in plan.segments] == [1, 1, 2, 3, 1, 1, 1]
    # each reader's part of m's gradient is summed into what layer 2 gets
    memory = next(n for n in plan.segments[2].outs if "selective_scan" in n)
    assert memory in plan.segments[4].ins
    assert plan.cot_sources[2][memory]


@pytest.mark.parametrize("segment", [2, 3])
def test_a_segment_hands_out_its_results_in_the_order_it_wrote_them(
        trained_once, segment):
    """Not in a set's order, which follows the process's hash seed: the
    order is the traced step's, so another order is another module and
    another entry in the compile cache (a cold compile every run)."""
    seg = trained_once[True][2]._remat_plan.segments[segment]
    wrote = [n for op in seg.ops for n in op.output_arg_names]
    assert len(seg.outs) > 1
    assert seg.outs == sorted(seg.outs, key=wrote.index)


@pytest.mark.parametrize("name", [
    "embed_tokens", "layers.2.mamba.w_in", "layers.3.attn.w_qkv",
    "layers.0.mlp.w_down"])
def test_segmented_and_unsegmented_gradients_agree(trained_once, name):
    assert trained_once[True][0] == pytest.approx(trained_once[False][0],
                                                  abs=1e-6)
    _close(trained_once[True][1][name], trained_once[False][1][name], 2e-5)


def test_layers_selective_scan_creates_the_family_s_parameters():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", shape=[8, 6], dtype="float32")
        b = fluid.data("b", shape=[8, 3], dtype="float32")
        y = layers.selective_scan(x, x, b, b)
    assert tuple(y.shape[1:]) == (8, 6)
    op = next(o for o in main.global_block().ops
              if o.type == "selective_scan")
    shapes = {slot: tuple(main.global_block().var(op.input(slot)[0]).shape)
              for slot in ("ALog", "D", "DtBias")}
    assert shapes == {"ALog": (6, 3), "D": (6,), "DtBias": (6,)}
    assert op.attr("chunk_size") == 64 and op.attr("site")
