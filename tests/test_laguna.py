"""Laguna-XS.2 at a tiny width on the CPU: the router's sigmoid form and
the YaRN frequencies against counts written out here, their defaults
against what the ops computed before they gained the forms, each layer
kind and the whole program's loss and gradients against the plain
reference (benchmark/configs/laguna_xs2_reference.py), and the share
test of the expert layer.

Tolerances: both sides compute in float32 here and differ in the order
of their sums (a grouped product against a loop over experts, blocks of
attention rows against their like); the parity limits are the chip's
own (loss 1e-5 relative, a gradient 1e-3 of its scale), and the
reference with bf16 ACTIVATIONS has to fail them.
"""
import importlib.util
import math
import os
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import core, layers, telemetry
from paddle_tpu.models import laguna
from paddle_tpu.ops import decoder_ops
from paddle_tpu.ops.registry import OPS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOSS_TOL, GRAD_TOL, OP_TOL = 1e-5, 1e-3, 2e-5


def _reference():
    spec = importlib.util.spec_from_file_location(
        "_laguna_reference", os.path.join(
            REPO, "benchmark", "configs", "laguna_xs2_reference.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _reference()
PUBLISHED = laguna.laguna_config()
CFG = dict(PUBLISHED, vocab_size=96, hidden=32, kv_heads=2, head_dim=8,
           layer_types=["full", "sliding", "sliding", "sliding", "full"],
           heads_per_layer=[4, 8, 8, 8, 4],
           mlp_types=["dense"] + ["sparse"] * 4, window=24, mlp_width=48,
           num_experts=16, experts_per_tok=3, expert_width=12,
           shared_width=12, experts_held=4, expert_start=4,
           rope={"full": dict(PUBLISHED["rope"]["full"], rotary_dim=4),
                 "sliding": dict(PUBLISHED["rope"]["sliding"],
                                 rotary_dim=8)})
SEQ = 75  # not a multiple of the window, of a block, of the reference's


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


# ------------------------------------------------------------- the router
def test_sigmoid_router_against_a_hand_count():
    """Four experts, top 2, scale 2.5, logits chosen so that the
    sigmoids are 0.5, 0.881, 0.119, 0.731: experts 1 and 3 are taken,
    w = 2.5 s / (s_1 + s_3)."""
    x = jnp.asarray([[1.0, 0.0], [0.0, 1.0]])
    w = jnp.asarray([[0.0, 2.0, -2.0, 1.0], [3.0, -3.0, 0.5, 0.0]])
    got = kernel("moe_router", {"top_k": 2, "scoring": "sigmoid",
                                "scale": 2.5, "site": "hand"}, X=x, W=w)
    s = 1.0 / (1.0 + np.exp(-np.asarray(w)))
    assert np.asarray(got["TopkIdx"]).tolist() == [[1, 3], [0, 2]]
    want = [[2.5 * s[0, 1] / (s[0, 1] + s[0, 3]),
             2.5 * s[0, 3] / (s[0, 1] + s[0, 3])],
            [2.5 * s[1, 0] / (s[1, 0] + s[1, 2]),
             2.5 * s[1, 2] / (s[1, 0] + s[1, 2])]]
    np.testing.assert_allclose(got["TopkWeight"], want, rtol=1e-6)
    np.testing.assert_allclose(np.sum(got["TopkWeight"], -1), [2.5, 2.5],
                               rtol=1e-6)
    # the auxiliary loss's p: the sigmoids over their sum
    p = s / s.sum(-1, keepdims=True)
    share = np.asarray([1, 1, 1, 1]) / 2
    np.testing.assert_allclose(got["AuxLoss"][0],
                               4 * np.sum(share * p.mean(0)), rtol=1e-6)
    assert telemetry.REGISTRY.get("moe_router_width").value(site="hand") == 4


def test_sigmoid_router_is_the_references_and_its_backward_too():
    x, w = normal(1, 2, 10, 32), normal(2, 32, 16)
    attrs = {"top_k": 3, "scoring": "sigmoid", "scale": 2.5}
    got = kernel("moe_router", attrs, X=x, W=w)
    idx, weight = REF.route(x, w, 3, 2.5)
    assert (np.asarray(got["TopkIdx"]) == np.asarray(idx)).all()
    close(got["TopkWeight"], weight, OP_TOL)
    mix = normal(3, 2, 10, 3)

    def through(route):
        return jax.grad(lambda x, w: jnp.sum(route(x, w) * mix), (0, 1))(x, w)

    for g, r in zip(
            through(lambda x, w: kernel("moe_router", attrs, X=x,
                                        W=w)["TopkWeight"]),
            through(lambda x, w: REF.route(x, w, 3, 2.5)[1])):
        close(g, r, OP_TOL)


def test_softmax_router_is_unchanged_bit_for_bit():
    """The default form against the expression the op was before it
    gained a second one, and a scale of 1 adds no multiply."""
    x, w = normal(4, 2, 10, 32), normal(5, 32, 16)
    got = kernel("moe_router", {"top_k": 3}, X=x, W=w)
    probs = jax.nn.softmax(jnp.matmul(
        x, w, precision=jax.lax.Precision.HIGHEST), -1)
    top_p, top_i = jax.lax.top_k(probs, 3)
    top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    share = jnp.zeros((16,), jnp.float32).at[top_i.reshape(-1)].add(1.0) / 20
    aux = 16 * jnp.sum(share * jnp.mean(probs.reshape(20, 16), 0))
    assert (np.asarray(got["TopkIdx"]) == np.asarray(top_i)).all()
    assert (np.asarray(got["TopkWeight"]) == np.asarray(top_p)).all()
    assert float(got["AuxLoss"][0]) == float(aux)
    same = kernel("moe_router", {"top_k": 3, "scoring": "softmax",
                                 "scale": 1.0}, X=x, W=w)
    assert (np.asarray(same["TopkWeight"]) == np.asarray(top_p)).all()
    with pytest.raises(ValueError):
        kernel("moe_router", {"top_k": 3, "scoring": "tanh"}, X=x, W=w)


def test_layers_moe_router_passes_the_form_and_defaults_to_softmax():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", shape=[6, 8], dtype="float32")
        layers.moe_router(x, 16, 2)
        layers.moe_router(x, 16, 2, scoring="sigmoid", scale=2.5)
    plain, scored = (op for op in main.global_block().ops
                     if op.type == "moe_router")
    assert (plain.attr("scoring"), plain.attr("scale")) == ("softmax", 1.0)
    assert (scored.attr("scoring"), scored.attr("scale")) == ("sigmoid", 2.5)
    assert plain.attr("site") != scored.attr("site")


# ------------------------------------------------------------------ rotary
def test_yarn_frequencies_at_the_published_numbers():
    """64 rotary dims, theta 500,000, factor 64 over 4,096 positions,
    beta 64 / 1: the ramp runs from dim 5 to dim 16 of the 32
    frequencies; below it a frequency is kept, above it divided by 64."""
    yarn = PUBLISHED["rope"]["full"]["yarn"]
    assert (PUBLISHED["rope"]["full"]["rotary_dim"],
            PUBLISHED["rope"]["sliding"]["rotary_dim"]) == (64, 128)
    low, high = decoder_ops.yarn_correction_range(
        64, 500000.0, 4096, 64.0, 1.0)
    assert (low, high) == (5, 16) and isinstance(low, int) \
        and isinstance(high, int)
    assert REF.yarn_range(64, 500000.0, 4096, 64.0, 1.0) == (5, 16)
    # c(n) = r ln(4096 / (2 pi n)) / (2 ln theta)
    assert 64 * math.log(4096 / (2 * math.pi * 64)) \
        / (2 * math.log(5e5)) == pytest.approx(5.660, abs=1e-3)
    assert 64 * math.log(4096 / (2 * math.pi)) \
        / (2 * math.log(5e5)) == pytest.approx(15.802, abs=1e-3)
    got = np.asarray(decoder_ops.yarn_inv_freq(
        64, 500000.0, yarn["factor"], yarn["original_max_position"],
        yarn["beta_fast"], yarn["beta_slow"]), np.float64)
    i = np.arange(32)
    pos = 500000.0 ** (2 * i / 64)
    ramp = np.clip((i - 5) / (16 - 5), 0, 1)
    want = (1 - ramp) / pos + ramp / (64 * pos)
    np.testing.assert_allclose(got, want, rtol=1e-7)
    np.testing.assert_allclose(got[:6], 1 / pos[:6], rtol=1e-7)
    np.testing.assert_allclose(got[16:], 1 / (64 * pos[16:]), rtol=1e-7)
    assert (np.asarray(REF.inv_freq(PUBLISHED["rope"]["full"])) == np.asarray(
        got, np.float32)).all()
    # 0.1 ln(factor) + 1
    assert PUBLISHED["rope"]["full"]["cos_sin_scale"] == pytest.approx(
        0.1 * math.log(64) + 1, abs=1e-12)


@pytest.mark.parametrize("kind,heads", [("full", 3), ("sliding", 2)])
def test_rotary_embedding_of_each_layer_kind_is_the_references(kind, heads):
    rope = PUBLISHED["rope"][kind]
    x = normal(6, 2, 50, heads * 128)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        data = fluid.data("x", shape=[50, heads * 128], dtype="float32")
        y = layers.rotary_embedding(
            data, heads, rope["rotary_dim"], rope["theta"],
            yarn=rope.get("yarn"),
            cos_sin_scale=rope.get("cos_sin_scale", 1.0))
    got, = fluid.Executor(fluid.CPUPlace()).run(
        main, feed={"x": np.asarray(x)}, fetch_list=[y.name])
    want = REF.rotary(x.reshape(2, 50, heads, 128), rope)
    close(got, want.reshape(2, 50, -1), OP_TOL)
    if kind == "full":  # the other half of a head passes through
        assert (np.asarray(got).reshape(2, 50, heads, 128)[..., 64:]
                == np.asarray(x).reshape(2, 50, heads, 128)[..., 64:]).all()
        op = next(o for o in main.global_block().ops
                  if o.type == "rotary_embedding")
        assert (op.attr("yarn_factor"), op.attr("original_max_position"),
                op.attr("beta_fast"), op.attr("beta_slow")) \
            == (64.0, 4096, 64.0, 1.0)


def test_plain_rotary_is_unchanged_bit_for_bit():
    """The default attrs against the expression the op was before it
    gained YaRN's: no scale multiplies cos or sin."""
    x = normal(7, 2, 9, 4 * 16)
    got = kernel("rotary_embedding", {"num_heads": 4, "rotary_dim": 8,
                                      "theta": 1e7}, X=x)["Out"]
    xh = x.reshape(2, 9, 4, 16)
    inv = 1e7 ** (-jnp.arange(0, 8, 2, dtype=jnp.float32) / 8)
    angle = jnp.arange(9, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.tile(jnp.cos(angle), (1, 2))[None, :, None, :]
    sin = jnp.tile(jnp.sin(angle), (1, 2))[None, :, None, :]
    rot, rest = xh[..., :8], xh[..., 8:]
    half = jnp.concatenate([-rot[..., 4:], rot[..., :4]], -1)
    want = jnp.concatenate([rot * cos + half * sin, rest], -1).reshape(
        x.shape)
    assert (np.asarray(got) == np.asarray(want)).all()


# ------------------------------------------------------------------ layers
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
    return got, params, main


def test_a_window_and_a_full_layer_of_different_head_counts_in_one_program():
    """Layers 3 (window 24, 8 query heads, plain rotary on the whole
    head) and 4 (full, 4 heads, YaRN on half of it) over the same 2 KV
    heads, one program: each is the reference's layer, the gate is one
    scalar a token and head, and each attention op says its head count."""
    x = normal(8, 2, SEQ, CFG["hidden"])

    def build():
        h = fluid.data("h", shape=[SEQ, CFG["hidden"]], dtype="float32")
        return [laguna.gated_attention(h, f"{kind}.", CFG, heads, kind)
                for kind, heads in (("sliding", 8), ("full", 4))]

    (window, full), params, main = _run_layer(build, {"h": np.asarray(x)})
    for got, kind, heads in ((window, "sliding", 8), (full, "full", 4)):
        p = {n[len(kind) + 1:]: w for n, w in params.items()
             if n.startswith(kind + ".")}
        assert p["w_q"].shape == (32, heads * 8)
        assert p["w_g"].shape == (32, heads)       # one gate a head
        assert p["w_k"].shape == p["w_v"].shape == (32, 2 * 8)
        close(got, REF.gated_attention(p, x, CFG, heads, kind), OP_TOL)
    sites = laguna.attention_sites(main)
    assert list(sites.values()) == [(8, 24), (4, 0)]
    heads = telemetry.REGISTRY.get("attn_query_heads")
    assert [heads.value(site=s) for s in sites] == [8, 4]


def test_a_window_layer_sees_no_key_outside_its_window():
    """Moving the keys and values 24 or more positions behind the last
    query leaves its output alone; moving a nearer one does not."""
    p = {"w_q": normal(20, 32, 64, scale=0.3),
         "w_k": normal(21, 32, 16, scale=0.3),
         "w_v": normal(22, 32, 16, scale=0.3),
         "w_g": normal(23, 32, 8), "w_o": normal(24, 64, 32, scale=0.3)}
    x = normal(25, 1, 40, 32)
    base = REF.gated_attention(p, x, CFG, 8, "sliding")[0, -1]
    far = REF.gated_attention(p, x.at[0, :16].add(1.0), CFG, 8,
                              "sliding")[0, -1]
    near = REF.gated_attention(p, x.at[0, 16].add(1.0), CFG, 8,
                               "sliding")[0, -1]
    assert (np.asarray(far) == np.asarray(base)).all()
    assert error(near, base) > 1e-3


# --------------------------------------------------------- the expert layer
E, K_TOP, D, F = 32, 8, 32, 12


def _moe_weights(seed):
    return {"w_router": normal(seed, D, E),
            "w_gate_up": normal(seed + 1, E, D, 2 * F, scale=0.2),
            "w_down": normal(seed + 2, E, F, D, scale=0.2),
            "shared_w_gate_up": normal(seed + 3, D, 2 * F, scale=0.2),
            "shared_w_down": normal(seed + 4, F, D, scale=0.2)}


def _routed(x, p, start, held):
    r = kernel("moe_router", {"top_k": K_TOP, "scoring": "sigmoid",
                              "scale": 2.5}, X=x, W=p["w_router"])
    return kernel("moe_expert_ffn",
                  {"expert_start": start, "num_experts": E},
                  X=x, TopkIdx=r["TopkIdx"], TopkWeight=r["TopkWeight"],
                  WGateUp=p["w_gate_up"][start:start + held],
                  WDown=p["w_down"][start:start + held])["Out"]


def test_the_eight_ranks_shares_add_up_to_the_uncut_layer(expert_lowering):
    """THE SHARE TEST, at the cell's ratios (32 experts, top 8, 8 ranks
    of 4): the routed parts the eight ranks compute under the sigmoid
    router (`expert_start` 0, 4, ..., 28), plus the shared expert
    counted ONCE, are what the reference gives for the whole layer with
    every expert held; and one rank's part alone is not."""
    x, p = normal(60, 2, 10, D), _moe_weights(61)
    cfg = {"experts_per_tok": K_TOP, "expert_start": 0, "routed_scale": 2.5}
    parts = [_routed(x, p, rank * 4, 4) for rank in range(8)]
    whole = REF.moe(p, x, cfg)
    shared = REF.gated_ffn(x, p["shared_w_gate_up"], p["shared_w_down"])
    close(sum(parts) + shared, whole, OP_TOL)
    assert error(parts[0] + shared, whole) > 0.05
    # each rank against the reference given the same share
    for rank in (0, 5):
        cut = dict(p, w_gate_up=p["w_gate_up"][rank * 4:rank * 4 + 4],
                   w_down=p["w_down"][rank * 4:rank * 4 + 4])
        close(parts[rank] + shared,
              REF.moe(cut, x, dict(cfg, expert_start=rank * 4)), OP_TOL)


def test_the_sparse_layer_is_the_references_with_its_share():
    x = normal(9, 2, SEQ, CFG["hidden"])

    def build():
        h = fluid.data("h", shape=[SEQ, CFG["hidden"]], dtype="float32")
        return [laguna.sparse_moe(h, "moe.", CFG)]

    (y,), params, main = _run_layer(build, {"h": np.asarray(x)})
    p = {n[len("moe."):]: w for n, w in params.items()}
    assert p["w_router"].shape == (32, 16)
    assert p["w_gate_up"].shape == (4, 32, 24)     # experts 4-7 of 16
    assert "shared_gate" not in p                  # the shared one is ungated
    close(y, REF.moe(p, x, CFG), OP_TOL)
    router = next(o for o in main.global_block().ops
                  if o.type == "moe_router")
    assert (router.attr("scoring"), router.attr("scale")) == ("sigmoid", 2.5)


# ------------------------------------------------------------ the whole model
@pytest.fixture(scope="module")
def trained_once():
    """{recompute: (loss, {parameter: gradient}, the compiled step)} of
    one step of the five-layer program on one batch, and the reference's
    (loss, gradients) at the same weights, plain and with every
    activation rounded to bf16."""
    out = {}
    feed = laguna.synthetic_pretrain_batch(CFG, 2, SEQ, 3)
    for recompute in (False, True):
        with warnings.catch_warnings():
            warnings.simplefilter("error")  # a fallback warning fails
            main, startup, _, fetches = laguna.build_laguna_pretrain_program(
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
    ids, labels = jnp.asarray(feed["ids"]), jnp.asarray(feed["labels"][..., 0])

    @jax.jit
    def read(rounded):  # a traced flag: both readings share one compile
        return REF.loss_and_grads(
            weights, ids, labels, CFG, round_to=lambda x: jnp.where(
                rounded, jax.lax.reduce_precision(x, 8, 7), x))

    out["reference"] = read(False)
    out["bf16_activations"] = read(True)
    out["names"] = names
    return out


PARAMETERS = [
    "embed_tokens", "lm_head", "final_norm",
    "layers.0.attn.w_q", "layers.0.attn.w_k", "layers.0.attn.w_v",
    "layers.0.attn.w_g", "layers.0.attn.w_o", "layers.0.input_norm",
    "layers.0.mlp.w_gate_up", "layers.0.mlp.w_down",
    "layers.1.attn.w_q", "layers.1.attn.w_g", "layers.1.moe.w_router",
    "layers.1.moe.w_gate_up", "layers.1.moe.w_down",
    "layers.1.moe.shared_w_gate_up", "layers.1.moe.shared_w_down",
    "layers.3.attn.w_k", "layers.3.post_norm", "layers.4.attn.w_q",
    "layers.4.attn.w_g", "layers.4.moe.w_router", "layers.4.moe.w_down"]


def test_the_program_s_parameters_are_the_reference_s(trained_once):
    names = trained_once["names"]
    assert len(names) == 3 + 9 + 4 * 12 and set(PARAMETERS) <= set(names)
    shapes = {n: tuple(g.shape) for n, g in trained_once[True][1].items()}
    assert shapes["layers.0.attn.w_q"] == (32, 4 * 8)
    assert shapes["layers.1.attn.w_q"] == (32, 8 * 8)
    assert shapes["embed_tokens"] == (96, 32) and shapes["lm_head"] == (32, 96)


@pytest.mark.parametrize("recompute", [False, True])
def test_the_program_s_loss_is_the_reference_s(trained_once, recompute):
    want = float(trained_once["reference"][0])
    assert abs(trained_once[recompute][0] - want) <= LOSS_TOL * want


@pytest.mark.parametrize("name", PARAMETERS)
@pytest.mark.parametrize("recompute", [False, True])
def test_the_program_s_gradients_are_the_reference_s(trained_once, recompute,
                                                     name):
    close(trained_once[recompute][1][name],
          trained_once["reference"][1][name], GRAD_TOL)


def test_every_gradient_is_within_the_limit(trained_once):
    worst = max(error(trained_once[True][1][n],
                      trained_once["reference"][1][n])
                for n in trained_once["names"])
    assert worst <= GRAD_TOL / 10, worst  # float32 against float32, in fact


def test_bf16_activations_fail_the_parity_limits(trained_once):
    """The nearest precision below the one the configuration states (f32
    activations): the reference with every activation rounded to bf16
    misses the loss limit or a gradient's, so the limits tell the two
    apart."""
    loss, grads = trained_once["bf16_activations"]
    want, want_grads = trained_once["reference"]
    over = [n for n in PARAMETERS
            if error(grads[n], want_grads[n]) > GRAD_TOL]
    assert abs(float(loss) - float(want)) > LOSS_TOL * float(want) or over
    assert len(over) >= len(PARAMETERS) // 2, over


def test_the_recompute_plan_is_a_layer_a_segment(trained_once):
    plain, remat = trained_once[False][2], trained_once[True][2]
    assert plain._remat_plan is None and remat._remat_plan is not None
    plan = remat._remat_plan
    assert len(plan.segments) == 6          # five layers and the head
    assert [len(s.outs) for s in plan.segments] == [1] * 6


def test_published_config_counts_the_issue_s_parameters():
    """The cell's cut of the published sizes, counted from the
    program's own parameter shapes, without running it: 691,623,936;
    and the whole model with the per-head gate 33.44e9."""
    cfg = dict(PUBLISHED, vocab_size=12544, experts_held=32,
               layer_types=PUBLISHED["layer_types"][:5],
               heads_per_layer=PUBLISHED["heads_per_layer"][:5],
               mlp_types=PUBLISHED["mlp_types"][:5])
    main, _, _, _ = laguna.build_laguna_pretrain_program(cfg, seq_len=8192)
    sizes = {p.name: int(np.prod(p.shape))
             for p in main.global_block().all_parameters()}
    assert sum(sizes.values()) == 691623936

    def layer(i):
        return sum(v for n, v in sizes.items()
                   if n.startswith(f"layers.{i}."))
    assert (layer(0), layer(1), layer(4)) == (79794176, 142217216, 133795840)
    assert layer(1) == layer(2) == layer(3)
    assert sizes["embed_tokens"] + sizes["lm_head"] == 51380224
    h = 2048
    whole = sum(
        2 * h * n * 128 + 2 * h * 1024 + h * n + 2 * h
        + (3 * h * 8192 if kind == "dense"
           else h * 256 + 3 * h * 512 + 256 * 3 * h * 512)
        for n, kind in zip(PUBLISHED["heads_per_layer"],
                           PUBLISHED["mlp_types"])) + 2 * h * 100352 + h
    assert abs(whole / 33.44e9 - 1) < 1e-3
