"""Ling-3.0-flash-VL's language model at a tiny width on the CPU: the
`kda` op (the delta rule with a decay a key channel) against the token
recurrence, forward and backward, at bounds that give one and several
blocks a chunk, its scalar case against the gated delta rule's core, a
chunk of gates at the bound; the router's group stage against the
reference and the routers without one bit for bit; rotate-half rotary in
`latent_qkv`; the share test of the expert layer; the whole program's
loss, logits, gradients and biases against the plain reference
(benchmark/configs/ling3_flash_vl_reference.py) with and without
recomputation, and the three controls that must fail its limits; the
published configuration's parameter count.

Tolerances: both sides compute in float32 here and differ in the order
of their sums (chunks in the WY form against a token loop, a grouped
product against a loop over experts, blocks of attention rows against
their like); the parity limits are the chip's own (a loss 1e-5
relative, a gradient 1e-3 of its scale).
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
from paddle_tpu.models import ling3_flash
from paddle_tpu.ops import decoder_ops
from paddle_tpu.ops.pallas import delta_rule as dr
from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.registry import OPS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOSS_TOL, GRAD_TOL, OP_TOL = 1e-5, 1e-3, 2e-5


def _by_path(name, *parts):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, *parts))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _by_path("_ling3_reference", "benchmark", "configs",
               "ling3_flash_vl_reference.py")
JOYAI_REF = _by_path("_joyai_reference_for_ling", "benchmark", "configs",
                     "joyai_llm_flash_reference.py")
PUBLISHED = ling3_flash.ling3_flash_config()
CFG = dict(PUBLISHED, vocab_size=96, hidden=32, heads=4, kv_latent=16,
           nope_dim=8, rope_dim=4, v_dim=8, kda_head_dim=8,
           layer_types=ling3_flash.layer_types(6, 3),
           mlp_types=["dense"] + ["sparse"] * 2, mlp_width=40,
           num_experts=16, experts_per_tok=4, n_group=4, topk_group=2,
           expert_width=12, shared_width=12, experts_held=4, expert_start=4,
           rope_theta=100.0, bias_update_rate=0.01, kda_chunk=16)
SEQ = 37  # not a multiple of a chunk or the reference's blocks


def kernel(op_type, attrs=None, **ins):
    """The op's registered kernel on arrays: {slot: array} -> outputs."""
    outs = OPS.get(op_type).kernel({k: [v] for k, v in ins.items()},
                                   dict(attrs or {}))
    return {k: v[0] for k, v in outs.items()}


def normal(seed, *shape, scale=1.0):
    return jnp.asarray(np.random.default_rng(seed).normal(
        0.0, scale, shape).astype(np.float32))


def error(got, want):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    return float(np.abs(got - want).max() / (np.abs(want).max() + 1e-30))


def close(got, want, tol):
    assert error(got, want) <= tol, (error(got, want), tol)


# ------------------------------------------------------------ the kda op
H, DK = 3, 8


def _kda_inputs(seed, s, a_shift=0.0):
    return dict(Q=normal(seed, 2, s, H * DK), K=normal(seed + 1, 2, s, H * DK),
                V=normal(seed + 2, 2, s, H * DK),
                A=normal(seed + 3, 2, s, H * DK, scale=2.0) + a_shift,
                B=normal(seed + 4, 2, s, H),
                ALog=jnp.log(jnp.asarray([1.0, 4.0, 16.0])),
                DtBias=normal(seed + 5, H * DK, scale=0.5))


def _kda_by_tokens(ins, bound):
    """The op's equations through the reference's token recurrence."""
    b, s, _ = ins["Q"].shape

    def heads(t):
        return t.reshape(b, s, H, -1)

    q, k = (t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)
            for t in (heads(ins["Q"]), heads(ins["K"])))
    g = bound * jax.nn.sigmoid(jnp.exp(ins["ALog"])[:, None]
                               * heads(ins["A"] + ins["DtBias"]))
    o = REF.kda_recurrence(q * DK ** -0.5, k, heads(ins["V"]), g,
                           jax.nn.sigmoid(ins["B"]))
    return o.reshape(b, s, -1)


@pytest.mark.parametrize("bound,chunk,s,block", [
    (-5.0, 16, 37, 16),     # one block a chunk
    (-5.0, 64, 150, 16),    # the cell's chunk: four blocks of 16
    (-20.0, 32, 70, 4)],    # a steeper bound: eight blocks of 4
    ids=["bound5_chunk16", "bound5_chunk64", "bound20_chunk32"])
def test_kda_is_the_token_recurrence_forward_and_backward(bound, chunk, s,
                                                          block):
    assert decoder_ops.kda_block(bound, chunk) == block
    ins = _kda_inputs(10, s)
    attrs = {"lower_bound": bound, "chunk_size": chunk, "site": "kda_t"}
    names, args = list(ins), tuple(ins.values())
    mix = normal(20, 2, s, H * DK)

    def op(*xs):
        return kernel("kda", attrs, **dict(zip(names, xs)))["Out"]

    def ref(*xs):
        return _kda_by_tokens(dict(zip(names, xs)), bound)

    def both(fn):
        return jax.jit(lambda *xs: (fn(*xs), jax.grad(
            lambda *ys: jnp.sum(fn(*ys) * mix), range(7))(*xs)))

    with jax.default_matmul_precision("highest"):
        got, got_grads = both(op)(*args)
        want, want_grads = both(ref)(*args)
    close(got, want, OP_TOL)
    for g, r in zip(got_grads, want_grads):
        close(g, r, 1e-4)
    assert telemetry.REGISTRY.get("kda_chunks_per_step").value(
        site="kda_t") == 2 * -(-s // chunk)


@pytest.mark.parametrize("bound,chunk,s,pinned", [
    (-5.0, 64, 150, None),     # the cell's chunk: padded, three chunks
    (-5.0, 64, 150, (2, 1)),   # the state and its gradient across steps
    (-20.0, 32, 70, (3, 3))],  # eight blocks of 4 factored, one merge
    ids=["bound5_chunk64", "bound5_chunk64_steps", "bound20_chunk32"])
def test_kda_kernels_are_the_token_recurrence_and_the_scan(bound, chunk, s,
                                                           pinned):
    """The Pallas pair of the chunk recurrence (interpreted), handed the
    factored decay's L, qk and the channel decays, against the token
    recurrence and the `lax.scan` lowering, forward and backward; the
    kernels' grid steps counted at the op's site."""
    ins = _kda_inputs(11, s)
    attrs = {"lower_bound": bound, "chunk_size": chunk, "site": "kda_k"}
    names, args = list(ins), tuple(ins.values())
    mix = normal(21, 2, s, H * DK)

    def both(fn):
        return jax.jit(lambda *xs: (fn(*xs), jax.grad(
            lambda *ys: jnp.sum(fn(*ys) * mix), range(7))(*xs)))

    def op(*xs):
        return kernel("kda", attrs, **dict(zip(names, xs)))["Out"]

    with jax.default_matmul_precision("highest"):
        with fa.interpret_guard(), (dr.block_override(*pinned) if pinned
                                    else contextlib.nullcontext()):
            got, got_grads = both(op)(*args)
        scan, scan_grads = both(op)(*args)
        want, want_grads = both(lambda *xs: _kda_by_tokens(
            dict(zip(names, xs)), bound))(*args)
    close(got, want, OP_TOL)
    close(got, scan, OP_TOL)
    for g, r, sc in zip(got_grads, want_grads, scan_grads):
        close(g, r, 1e-4)
        close(g, sc, 1e-4)
    hb, nc = pinned or dr.block_sizes(2 * H, -(-s // chunk), chunk, DK, DK,
                                      4)
    assert telemetry.REGISTRY.get("delta_grid_steps_per_step").value(
        site="kda_k") == 2 * H // hb * (-(-s // chunk) // nc)


def test_one_decay_a_head_is_the_gated_delta_rule_s_core():
    """Every channel of a head given the head's one log decay: the
    factored blocks give what the scalar form's [C, C] decay gives."""
    b, h, s = 1, 2, 100
    q, k = (t / jnp.linalg.norm(t, axis=-1, keepdims=True)
            for t in (normal(30, b, h, s, DK), normal(31, b, h, s, DK)))
    v, beta = normal(32, b, h, s, DK), jax.nn.sigmoid(normal(33, b, h, s))
    g = -5.0 * jax.nn.sigmoid(normal(34, b, h, s, scale=3.0))
    with jax.default_matmul_precision("highest"):
        scalar = decoder_ops._chunked_delta_rule(q, k, v, g, beta, 64)
        channels = decoder_ops._chunked_delta_rule(
            q, k, v, jnp.broadcast_to(g[..., None], q.shape), beta, 64,
            decoder_ops.kda_block(-5.0, 64))
    close(channels, scalar, OP_TOL)


def test_gates_at_the_bound_over_a_whole_chunk_stay_finite():
    """A decay of e^-5 every step in every channel for 64 positions: the
    blocks' factors reach e^75 and stay inside float32; forward and
    gradients finite and the recurrence's."""
    ins = _kda_inputs(40, 128, a_shift=80.0)
    attrs = {"lower_bound": -5.0, "chunk_size": 64}
    g = -5.0 * jax.nn.sigmoid(jnp.exp(ins["ALog"])[:, None] * (
        ins["A"] + ins["DtBias"]).reshape(2, 128, H, DK))
    assert float(jnp.max(g)) == -5.0
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda ins: kernel("kda", attrs, **ins)["Out"])(ins)
        close(got, jax.jit(_kda_by_tokens, static_argnums=1)(ins, -5.0),
              OP_TOL)
        grads = jax.jit(jax.grad(lambda q, k, v: jnp.sum(kernel(
            "kda", attrs, **dict(ins, Q=q, K=k, V=v))["Out"] ** 2),
            (0, 1, 2)))(ins["Q"], ins["K"], ins["V"])
    assert all(np.isfinite(np.asarray(x)).all() for x in (got,) + grads)


def test_kda_block_needs_a_bound():
    assert [decoder_ops.kda_block(b, 64) for b in (-1.0, -5.0, -10.0)] \
        == [64, 16, 8]
    with pytest.raises(ValueError, match="lower_bound"):
        decoder_ops.kda_block(0.0, 64)


def test_layers_kda_creates_a_log_and_dt_bias():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        q = fluid.data("q", shape=[5, 24], dtype="float32")
        a = fluid.data("a", shape=[5, 24], dtype="float32")
        b = fluid.data("b", shape=[5, 3], dtype="float32")
        o = layers.kda(q, q, q, a, b, -5.0, chunk_size=32)
    op = next(x for x in main.global_block().ops if x.type == "kda")
    assert sorted(tuple(p.shape) for p in
                  main.global_block().all_parameters()) == [(3,), (24,)]
    assert (op.attr("lower_bound"), op.attr("chunk_size")) == (-5.0, 32)
    assert tuple(o.shape) == tuple(q.shape)


# ----------------------------------------------------------- the router
E, K_TOP, D, F = 32, 8, 32, 12
GROUPED = {"top_k": K_TOP, "scoring": "sigmoid", "scale": 2.5,
           "bias_update_rate": 0.001, "n_group": 4, "topk_group": 2}
ROUTE_CFG = {"n_group": 4, "topk_group": 2, "experts_per_tok": K_TOP,
             "routed_scale": 2.5}


def test_the_group_stage_is_the_reference_s_and_keeps_its_groups():
    x, w = normal(50, 2, 10, D), normal(51, D, E)
    bias = normal(52, E, scale=0.1)
    got = kernel("moe_router", GROUPED, X=x, W=w, Bias=bias)
    ref_i, ref_w = REF.route(x, w, bias, ROUTE_CFG)
    idx = np.asarray(got["TopkIdx"])
    assert (idx == np.asarray(ref_i)).all()
    close(got["TopkWeight"], ref_w, OP_TOL)
    # every token's eight lie in two groups of eight, and the plain
    # choice over all 32 would have left them for a few tokens
    assert all(len(set(row // 8)) <= 2 for row in idx.reshape(-1, K_TOP))
    flat = kernel("moe_router", dict(GROUPED, n_group=1, topk_group=1),
                  X=x, W=w, Bias=bias)
    assert (np.sort(np.asarray(flat["TopkIdx"]), -1)
            != np.sort(idx, -1)).any()
    np.testing.assert_allclose(
        got["BiasOut"], REF.bias_after(bias, got["TopkIdx"],
                                       {"bias_update_rate": 0.001}),
        rtol=1e-6)
    assert [telemetry.REGISTRY.get(n).value(site="")
            for n in ("moe_router_groups", "moe_router_groups_kept")] \
        == [4, 2]


def test_the_group_stage_s_gradient_is_the_reference_s():
    x, w = normal(53, 2, 10, D), normal(54, D, E)
    bias = normal(55, E, scale=0.05)
    mix = normal(56, 2, 10, K_TOP)
    g_x, g_w = jax.grad(lambda x, w: jnp.sum(kernel(
        "moe_router", GROUPED, X=x, W=w, Bias=bias)["TopkWeight"] * mix),
        (0, 1))(x, w)
    ref = jax.grad(lambda x, w: jnp.sum(
        REF.route(x, w, bias, ROUTE_CFG)[1] * mix), (0, 1))(x, w)
    close(g_x, ref[0], OP_TOL)
    close(g_w, ref[1], OP_TOL)


@pytest.mark.parametrize("bias", [False, True], ids=["plain", "biased"])
def test_one_group_is_today_s_choice_bit_for_bit(bias):
    """``n_group`` 1 is no group stage: the op's outputs with the attrs
    left out and given as 1 / 1 are the same bits (JoyAI's and the
    other cells' routers)."""
    x, w = normal(57, 2, 10, D), normal(58, D, E)
    extra = {"Bias": normal(59, E, scale=0.1)} if bias else {}
    attrs = {"top_k": K_TOP, "scoring": "sigmoid", "scale": 2.5,
             "bias_update_rate": 0.001 if bias else 0.0}
    a = kernel("moe_router", attrs, X=x, W=w, **extra)
    b = kernel("moe_router", dict(attrs, n_group=1, topk_group=1), X=x, W=w,
               **extra)
    assert a.keys() == b.keys()
    for name in a:
        assert (np.asarray(a[name]) == np.asarray(b[name])).all(), name
    if bias:
        ref_i, _ = JOYAI_REF.route(x, w, extra["Bias"], K_TOP, 2.5)
        assert (np.asarray(a["TopkIdx"]) == np.asarray(ref_i)).all()


def test_layers_moe_router_checks_its_groups():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", shape=[6, 8], dtype="float32")
        layers.moe_router(x, 16, 2, scoring="sigmoid", n_group=4,
                          topk_group=2, bias_update_rate=0.001)
        with pytest.raises(ValueError, match="groups"):
            layers.moe_router(x, 16, 2, n_group=3, topk_group=1)
    op = next(o for o in main.global_block().ops if o.type == "moe_router")
    assert (op.attr("n_group"), op.attr("topk_group")) == (4, 2)


# ------------------------------------------------------- latent attention
LATENT = {"num_heads": 3, "nope_dim": 8, "rope_dim": 4, "v_dim": 6,
          "theta": 100.0, "rope_interleave": False, "site": "half"}


def test_latent_qkv_with_half_split_rotary_is_the_reference_s():
    """The query from the layer's input (no latent), rotate-half rotary on
    the query's and the shared key's rotary dims: Q, K, V and their
    gradients are the reference's."""
    ins = dict(CQ=normal(60, 2, 9, 10), CKV=normal(61, 2, 9, 7),
               KRope=normal(62, 2, 9, 4), WUQ=normal(63, 10, 36, scale=0.3),
               WUKV=normal(64, 7, 42, scale=0.3))

    def reference(cq, ckv, kr, wq, wkv):
        q = (cq @ wq).reshape(2, 9, 3, 12)
        kv = (ckv @ wkv).reshape(2, 9, 3, 14)
        q = jnp.concatenate([q[..., :8], REF.rope(q[..., 8:], 100.0)], -1)
        k_r = jnp.broadcast_to(REF.rope(kr, 100.0)[:, :, None], (2, 9, 3, 4))
        k = jnp.concatenate([kv[..., :8], k_r], -1)
        return (q.reshape(2, 9, -1), k.reshape(2, 9, -1),
                kv[..., 8:].reshape(2, 9, -1))

    def op(*xs):
        got = kernel("latent_qkv", LATENT, **dict(zip(ins, xs)))
        return tuple(got[n] for n in "QKV")

    mix = [normal(65 + i, 2, 9, n) for i, n in enumerate((36, 36, 18))]

    def both(fn):
        return jax.jit(lambda *xs: (fn(*xs), jax.grad(lambda *ys: sum(
            jnp.sum(o * m) for o, m in zip(fn(*ys), mix)), range(5))(*xs)))

    with jax.default_matmul_precision("highest"):
        got, want = both(op)(*ins.values()), both(reference)(*ins.values())
    for g, w in zip(got[0] + got[1], want[0] + want[1]):
        close(g, w, OP_TOL)
    # the two pairings differ, and interleaved is the default
    interleaved = kernel("latent_qkv", dict(LATENT, rope_interleave=True),
                         **ins)
    assert error(interleaved["Q"], op(*ins.values())[0]) > 0.01
    assert OPS.get("latent_qkv").attr_defaults["rope_interleave"] is True
    assert telemetry.REGISTRY.get("mla_rope_interleaved").value(
        site="half") == 0


# --------------------------------------------------------- the expert layer
def test_the_four_ranks_shares_add_up_to_the_uncut_layer(expert_lowering):
    """THE SHARE TEST (32 experts in 4 groups, 2 kept, top 8, 4 ranks of
    8): the routed parts the four ranks compute under the grouped,
    biased router, plus the shared expert counted ONCE, are what the
    reference gives for the whole layer with every expert held; one
    rank's part alone is not."""
    x = normal(70, 2, 10, D)
    p = {"w_router": normal(71, D, E),
         "w_gate_up": normal(72, E, D, 2 * F, scale=0.2),
         "w_down": normal(73, E, F, D, scale=0.2),
         "shared_w_gate_up": normal(74, D, 2 * F, scale=0.2),
         "shared_w_down": normal(75, F, D, scale=0.2)}
    bias = normal(76, E, scale=0.1)
    cfg = dict(ROUTE_CFG, expert_start=0)
    r = kernel("moe_router", GROUPED, X=x, W=p["w_router"], Bias=bias)
    parts = [kernel("moe_expert_ffn",
                    {"expert_start": start, "num_experts": E},
                    X=x, TopkIdx=r["TopkIdx"], TopkWeight=r["TopkWeight"],
                    WGateUp=p["w_gate_up"][start:start + 8],
                    WDown=p["w_down"][start:start + 8])["Out"]
             for start in range(0, E, 8)]
    with jax.default_matmul_precision("highest"):
        whole, _ = REF.moe(p, x, cfg, bias)
        shared = REF.gated_ffn(x, p["shared_w_gate_up"], p["shared_w_down"])
        close(sum(parts) + shared, whole, OP_TOL)
        assert error(parts[0] + shared, whole) > 0.05
        cut = dict(p, w_gate_up=p["w_gate_up"][8:16],
                   w_down=p["w_down"][8:16])
        close(parts[1] + shared,
              REF.moe(cut, x, dict(cfg, expert_start=8), bias)[0], OP_TOL)


# ------------------------------------------------------------ the whole model
PARAMETERS = [
    "embed_tokens", "lm_head", "final_norm",
    "layers.0.attn.w_qkva", "layers.0.attn.conv_w", "layers.0.attn.w_b",
    "layers.0.attn.a_log", "layers.0.attn.dt_bias", "layers.0.attn.norm",
    "layers.0.attn.w_g", "layers.0.attn.w_o", "layers.0.input_norm",
    "layers.0.mlp.w_gate_up", "layers.0.mlp.w_down",
    "layers.1.attn.w_qkva", "layers.1.moe.w_router",
    "layers.1.moe.w_gate_up", "layers.1.moe.w_down",
    "layers.1.moe.shared_w_gate_up", "layers.1.post_norm",
    "layers.2.attn.w_q", "layers.2.attn.w_kv_a", "layers.2.attn.kv_a_norm",
    "layers.2.attn.w_kv_b", "layers.2.attn.w_g", "layers.2.attn.w_o",
    "layers.2.moe.w_router", "layers.2.moe.shared_w_down"]


def _step(recompute, feed):
    """(fetched, weights after start-up, the compiled step, names, the
    routers' biases) of a fresh program's one step; a fallback warning
    fails."""
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        main, startup, _, fetches = \
            ling3_flash.build_ling3_flash_pretrain_program(
                CFG, seq_len=SEQ, lr=1e-3, recompute=recompute)
        main.random_seed = startup.random_seed = 7
        exe, scope = fluid.Executor(fluid.CPUPlace()), core.Scope()
        exe.run(startup, scope=scope)
        names = [p.name for p in main.global_block().all_parameters()]
        weights = {n: jnp.asarray(np.array(
            scope.find_var(n).get_tensor().array)) for n in names}
        biases = list(ling3_flash.router_biases(main).values())
        wanted = ["loss", "logits"] + biases + [n + "@GRAD" for n in names]
        got = exe.run(main, feed=feed, scope=scope,
                      fetch_list=[fetches[0].name,
                                  ling3_flash.head_logits(main)]
                      + wanted[2:])
    step = list(exe._compiled_cache.values())[-1]
    got = dict(zip(wanted, got))
    got["logits"] = got["logits"][:, :REF.LOGIT_ROWS]
    return got, weights, step, names, biases


@pytest.fixture(scope="module")
def trained_once():
    """{recompute: (fetched after one step, the compiled step)}, the
    names, and the reference's readings at those weights: plain, with
    every activation rounded to bf16, and under each control."""
    feed = ling3_flash.synthetic_pretrain_batch(CFG, 2, SEQ, 3)
    out = {}
    for recompute in (False, True):
        got, weights, step, names, biases = _step(recompute, feed)
        out[recompute] = (got, step)
    wanted = ["loss", "logits"] + biases + [n + "@GRAD" for n in names]

    @jax.jit
    def read(rounded, flags):  # traced flags: the readings share a compile
        return REF.readings(weights, feed, CFG, wanted, lambda x: jnp.where(
            rounded, jax.lax.reduce_precision(x, 8, 7), x),
            dict(zip(REF.CONTROLS, flags)))

    off = (False,) * len(REF.CONTROLS)
    out.update(reference=read(False, off), bf16_activations=read(True, off),
               controls={c: read(False, tuple(n == c for n in REF.CONTROLS))
                         for c in REF.CONTROLS},
               names=names, biases=biases)
    return out


def test_the_program_s_parameters_are_the_reference_s(trained_once):
    names = trained_once["names"]
    # embedding, head, final norm; KDA 8 + 2 norms, MLA 6 + 2 norms; dense
    # 2, sparse 5
    assert len(names) == 3 + 2 * 10 + 8 + 2 + 2 * 5
    assert set(PARAMETERS) <= set(names)
    shapes = {n[:-5]: tuple(g.shape) for n, g in trained_once[True][0].items()
              if n.endswith("@GRAD")}
    assert shapes["layers.0.attn.w_qkva"] == (32, 4 * 32)
    assert shapes["layers.0.attn.conv_w"] == (96, 4)
    assert shapes["layers.0.attn.dt_bias"] == (32,)
    assert shapes["layers.0.attn.a_log"] == (4,)
    assert shapes["layers.0.attn.norm"] == (32,)
    assert shapes["layers.2.attn.w_q"] == (32, 4 * 12)
    assert shapes["layers.2.attn.w_kv_b"] == (16, 4 * 16)
    assert trained_once["biases"] == ["layers.1.moe.router_bias",
                                      "layers.2.moe.router_bias"]


@pytest.mark.parametrize("recompute", [False, True])
def test_the_loss_and_logits_are_the_reference_s(trained_once, recompute):
    got, want = trained_once[recompute][0], trained_once["reference"]
    loss = float(np.asarray(got["loss"]).ravel()[0])
    assert abs(loss - float(want["loss"])) <= LOSS_TOL * float(want["loss"])
    close(got["logits"], want["logits"], 1e-5)


@pytest.mark.parametrize("name", PARAMETERS)
@pytest.mark.parametrize("recompute", [False, True])
def test_the_program_s_gradients_are_the_reference_s(trained_once, recompute,
                                                     name):
    close(trained_once[recompute][0][name + "@GRAD"],
          trained_once["reference"][name + "@GRAD"], GRAD_TOL)


@pytest.mark.parametrize("recompute", [False, True])
def test_the_biases_after_a_step_are_the_reference_s(trained_once,
                                                     recompute):
    for name in trained_once["biases"]:
        got = np.asarray(trained_once[recompute][0][name])
        np.testing.assert_allclose(got, trained_once["reference"][name],
                                   atol=1e-7)
        assert np.abs(got).max() == pytest.approx(0.01)


def test_every_gradient_is_within_a_tenth_of_the_limit(trained_once):
    worst = max(error(trained_once[True][0][n + "@GRAD"],
                      trained_once["reference"][n + "@GRAD"])
                for n in trained_once["names"])
    assert worst <= GRAD_TOL / 10, worst  # float32 against float32, in fact


@pytest.mark.parametrize("control", ["bf16_activations", "kda_state_bf16",
                                     "kda_gate_head_mean"])
def test_each_control_fails_the_parity_limits(trained_once, control):
    """The nearest precision below the configuration's (bf16 activations),
    the KDA state kept in bf16, and each head's decays replaced by their
    mean: each is outside the loss's or a gradient's limit, so the
    limits tell the program from each."""
    low = trained_once["bf16_activations"] if control == "bf16_activations" \
        else trained_once["controls"][control]
    want = trained_once["reference"]
    over = [n for n in PARAMETERS
            if error(low[n + "@GRAD"], want[n + "@GRAD"]) > GRAD_TOL]
    assert over or abs(float(low["loss"]) - float(want["loss"])) \
        > LOSS_TOL * float(want["loss"])
    kda = [n for n in over if n.startswith("layers.0.attn.")]
    assert kda, over


def test_the_recompute_plan_is_a_layer_a_segment(trained_once):
    plain, remat = trained_once[False][1], trained_once[True][1]
    assert plain._remat_plan is None and remat._remat_plan is not None
    assert len(remat._remat_plan.segments) == 4  # three layers, the head


def test_the_layer_kinds_follow_the_group_size():
    assert PUBLISHED["layer_types"][:6] == ["kda"] * 5 + ["mla"]
    assert PUBLISHED["layer_types"].count("mla") == 7
    assert len(PUBLISHED["layer_types"]) == len(PUBLISHED["mlp_types"]) == 42
    assert PUBLISHED["mlp_types"][:3] == ["dense", "dense", "sparse"]


# ------------------------------------------------------------- the counts
def test_published_config_counts_the_issue_s_parameters():
    """The cell's cut of the published sizes, counted from the program's
    own parameter shapes, without running it: 707,798,176."""
    cfg = dict(PUBLISHED, vocab_size=19648, experts_held=8,
               mlp_types=PUBLISHED["mlp_types"][:6])
    main, _, _, _ = ling3_flash.build_ling3_flash_pretrain_program(
        cfg, seq_len=4096)
    got = {p.name: int(np.prod(p.shape))
           for p in main.global_block().all_parameters()}
    assert sum(got.values()) == 707798176

    def part(prefix):
        return sum(v for n, v in got.items() if n.startswith(prefix))
    assert [part(f"layers.{i}.attn.") for i in range(6)] \
        == [52650016] * 5 + [31965696]
    assert part("layers.0.mlp.") == part("layers.1.mlp.") == 47185920
    assert [part(f"layers.{i}.moe.") for i in range(2, 6)] == [54394880] * 4
    assert got["embed_tokens"] + got["lm_head"] == 100597760
    biases = [v for v in main.global_block().vars.values()
              if v.name.endswith("router_bias")]
    assert len(biases) == 4 and all(tuple(v.shape) == (512,)
                                    for v in biases)
