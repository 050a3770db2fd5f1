"""SmallThinker-21BA3B at a tiny width on the CPU: the expert op's
`activation` against plain expressions (ReLU new, SiLU unchanged bit for
bit, forward and through the op's own vjp), the router fed the LAYER'S
INPUT (a test that fails if it is fed the normed or the post-attention
state), each layer kind and the whole program's loss and gradients
against the plain reference
(benchmark/configs/smallthinker_21b_a3b_reference.py) with
recomputation on and off, and the share test of the expert layer.

Tolerances: both sides compute in float32 here and differ in the order
of their sums (a grouped product against a loop over experts, blocks of
attention rows against their like); the parity limits are the chip's
own (loss 1e-5 relative, a gradient 1e-3 of its scale), and the
reference with bf16 ACTIVATIONS has to fail them.
"""
import importlib.util
import os
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import core, layers, telemetry
from paddle_tpu.models import (_decoder_parts, laguna, phi4_flash, qwen3_next,
                               smallthinker)
from paddle_tpu.ops import decoder_ops
from paddle_tpu.ops.registry import OPS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOSS_TOL, GRAD_TOL, OP_TOL = 1e-5, 1e-3, 2e-5


def _reference():
    spec = importlib.util.spec_from_file_location(
        "_smallthinker_reference", os.path.join(
            REPO, "benchmark", "configs",
            "smallthinker_21b_a3b_reference.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _reference()
PUBLISHED = smallthinker.smallthinker_config()
# 14 query heads over 2: the published repeat of 7, no power of two
CFG = dict(PUBLISHED, vocab_size=96, hidden=32, heads=14, kv_heads=2,
           head_dim=8, rope_layout=[0, 1, 1, 1], window_layout=[0, 1, 1, 1],
           window=24, num_experts=16, experts_per_tok=3, expert_width=12,
           experts_held=4, expert_start=4)
SEQ = 75  # longer than the window; no multiple of it, of a block


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


# ------------------------------------------------- the experts' activation
E, K_TOP, D, F = 16, 3, 32, 12


def _moe_weights(seed):
    return {"w_router": normal(seed, D, E),
            "w_gate_up": normal(seed + 1, E, D, 2 * F, scale=0.2),
            "w_down": normal(seed + 2, E, F, D, scale=0.2)}


def _plain_experts(x, idx, weight, w_gate_up, w_down, act, start=0):
    """Every held expert over every token, weighted by what the router
    gave it: no sort, no gather, no grouped product."""
    y = jnp.zeros_like(x)
    for e in range(w_gate_up.shape[0]):
        h = x @ w_gate_up[e]
        w = jnp.sum(jnp.where(idx == start + e, weight, 0.0), -1)
        y = y + w[..., None] * ((act(h[..., :F]) * h[..., F:]) @ w_down[e])
    return y


def _expert_op(attrs, x, idx, weight, w_gate_up, w_down):
    return kernel("moe_expert_ffn", attrs, X=x, TopkIdx=idx,
                  TopkWeight=weight, WGateUp=w_gate_up, WDown=w_down)["Out"]


@pytest.mark.parametrize("activation,act", [
    ("relu", jax.nn.relu), ("silu", lambda h: h * jax.nn.sigmoid(h))])
@pytest.mark.parametrize("tile", [512, 8], ids=["one_pass", "three_passes"])
def test_activation_against_a_plain_expression_and_its_gradients(
        monkeypatch, activation, act, tile):
    """Forward and every gradient, through JAX's own differentiation of
    one pass and through the op's vjp of three (`_windows_bwd`: a tile
    of 8 bounds a pass at 32 rows)."""
    monkeypatch.setattr(decoder_ops, "ROW_TILE", tile)
    x, p = normal(1, 2, 15, D), _moe_weights(2)
    # a router that sends EVERY assignment to the held experts 4-7: 90
    # rows, over the bound of 32 a tile of 8 gives a 32-wide router
    r = kernel("moe_router", {"top_k": K_TOP}, X=x, W=p["w_router"][:, 4:8])
    idx = r["TopkIdx"] + 4
    held = (p["w_gate_up"][4:8], p["w_down"][4:8])
    attrs = {"expert_start": 4, "num_experts": 2 * E,
             "activation": activation}
    passes = kernel("moe_expert_ffn", attrs, X=x, TopkIdx=idx,
                    TopkWeight=r["TopkWeight"], WGateUp=held[0],
                    WDown=held[1])["Passes"]
    assert (int(passes[0]) > 1) == (tile == 8)
    mix = normal(3, 2, 15, D)

    def through(f):
        return jax.value_and_grad(
            lambda x, w, gu, dn: jnp.sum(f(x, w, gu, dn) * mix),
            (0, 1, 2, 3))(x, r["TopkWeight"], *held)

    got = through(lambda x, w, gu, dn: _expert_op(attrs, x, idx, w, gu, dn))
    want = through(lambda x, w, gu, dn: _plain_experts(x, idx, w, gu, dn,
                                                       act, start=4))
    close(got[0], want[0], OP_TOL)
    for g, w in zip(got[1], want[1]):
        close(g, w, OP_TOL)


def test_relu_and_silu_experts_differ():
    x, p = normal(4, 2, 15, D), _moe_weights(5)
    r = kernel("moe_router", {"top_k": K_TOP}, X=x, W=p["w_router"])
    args = (x, r["TopkIdx"], r["TopkWeight"], p["w_gate_up"], p["w_down"])
    assert error(_expert_op({"activation": "relu"}, *args),
                 _expert_op({"activation": "silu"}, *args)) > 0.05


@pytest.mark.parametrize("tile", [512, 8], ids=["one_pass", "windows"])
def test_silu_is_the_default_and_lowers_as_before(monkeypatch, tile):
    """No `activation`, and "silu", lower to the text of the expression
    the op was before it gained the attr (`_silu(h[:, :f]) * h[:, f:]`,
    put back in `_window`'s place by `_window_before_the_attr`), forward
    and backward: the Qwen and Laguna cells' compiled steps do not move.
    "relu" does lower to another text."""
    monkeypatch.setattr(decoder_ops, "ROW_TILE", tile)
    x, p = normal(6, 2, 15, D), _moe_weights(7)
    r = kernel("moe_router", {"top_k": K_TOP}, X=x, W=p["w_router"])
    idx, held = r["TopkIdx"], (p["w_gate_up"][:4], p["w_down"][:4])

    def text(attrs):
        def f(x, w, gu, dn):
            return jnp.sum(_expert_op(dict(attrs, num_experts=E), x, idx,
                                      w, gu, dn) ** 2)
        return jax.jit(jax.value_and_grad(f, (0, 1, 2, 3))).lower(
            x, r["TopkWeight"], *held).as_text()

    default, silu, relu = text({}), text({"activation": "silu"}), \
        text({"activation": "relu"})
    assert default == silu != relu
    monkeypatch.setattr(decoder_ops, "_window", _window_before_the_attr)
    assert text({}) == default
    before = _expert_op({"num_experts": E}, x, idx, r["TopkWeight"], *held)
    monkeypatch.undo()
    monkeypatch.setattr(decoder_ops, "ROW_TILE", tile)
    for attrs in ({}, {"activation": "silu"}):
        after = _expert_op(dict(attrs, num_experts=E), x, idx,
                           r["TopkWeight"], *held)
        assert (np.asarray(before) == np.asarray(after)).all()


def _window_before_the_attr(o, x, weight, w_gate_up, w_down, order, lo,
                            sizes, k, activation=None):
    """`decoder_ops._window` as PR 31 left it, SiLU written in."""
    bound, f = order.shape[0], w_down.shape[1]
    ends = jnp.cumsum(sizes)
    valid = (lo + jnp.arange(bound) < ends[-1])[:, None]
    in_window = jnp.clip(jnp.minimum(ends, lo + bound)
                         - jnp.maximum(ends - sizes, lo), 0)
    token = order // k
    x_rows = jnp.where(valid, x[token], 0)
    h = jnp.where(valid, decoder_ops._ragged(x_rows, w_gate_up, in_window),
                  0.0)
    act = decoder_ops._silu(h[:, :f]) * h[:, f:]
    y = jnp.where(valid, decoder_ops._ragged(act, w_down, in_window), 0.0) \
        * weight[order][:, None]
    return o.at[token].add(y)


def test_an_unknown_activation_raises():
    x, p = normal(8, 2, 5, D), _moe_weights(9)
    r = kernel("moe_router", {"top_k": K_TOP}, X=x, W=p["w_router"])
    with pytest.raises(ValueError, match="gelu"):
        _expert_op({"activation": "gelu"}, x, r["TopkIdx"], r["TopkWeight"],
                   p["w_gate_up"], p["w_down"])


def test_layers_moe_expert_ffn_passes_the_activation_and_sets_its_gauge():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", shape=[6, 8], dtype="float32")
        idx, weight, _ = layers.moe_router(x, 16, 2)
        outs = [layers.moe_expert_ffn(x, idx, weight, 4, 12),
                layers.moe_expert_ffn(x, idx, weight, 4, 12,
                                      activation="relu")]
    plain, relu = (op for op in main.global_block().ops
                   if op.type == "moe_expert_ffn")
    assert plain.attr("activation") == "silu"
    assert relu.attr("activation") == "relu"
    exe, scope = fluid.Executor(fluid.CPUPlace()), core.Scope()
    exe.run(startup, scope=scope)
    exe.run(main, feed={"x": np.zeros((2, 6, 8), np.float32)}, scope=scope,
            fetch_list=[o.name for o in outs])
    gauge = telemetry.REGISTRY.get("moe_activation_relu")
    assert [gauge.value(site=op.attr("site")) for op in (plain, relu)] \
        == [0, 1]
    assert "activation" in layers.moe_expert_ffn.__doc__
    assert "relu" in OPS.get("moe_expert_ffn").kernel.__doc__


# ---------------------------------------------------------------- attention
def _run_layer(build, feed):
    """(fetched outputs, {parameter: value}, program) of a forward-only
    program."""
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


def test_a_full_layer_without_rotary_and_a_window_layer_with_it():
    """One program, both kinds, 14 query heads over 2 (a repeat of 7):
    each is the reference's, the full layer has NO rotary op, and the
    ops say their window and their repeat."""
    x = normal(10, 2, SEQ, CFG["hidden"])

    def build():
        h = fluid.data("h", shape=[SEQ, CFG["hidden"]], dtype="float32")
        return [smallthinker.attention(h, "full.", CFG, 0, 0),
                smallthinker.attention(h, "window.", CFG, 1, 1)]

    (full, window), params, main = _run_layer(build, {"h": np.asarray(x)})
    for got, kind, flag in ((full, "full", 0), (window, "window", 1)):
        p = {n[len(kind) + 1:]: w for n, w in params.items()
             if n.startswith(kind + ".")}
        assert set(p) == {"w_q", "w_k", "w_v", "w_o"}  # no bias, no gate
        assert p["w_q"].shape == (32, 14 * 8) and p["w_o"].shape == (112, 32)
        assert p["w_k"].shape == p["w_v"].shape == (32, 2 * 8)
        close(got, REF.attention(p, x, CFG, flag, flag), OP_TOL)
    ops = [op.type for op in main.global_block().ops]
    assert ops.count("rotary_embedding") == 2    # q and k of ONE layer
    sites = smallthinker.attention_sites(main)
    assert list(sites.values()) == [(14, 0), (14, 24)]
    for name, want in (("attn_window", [0, 24]), ("attn_kv_repeat", [7, 7]),
                       ("attn_query_heads", [14, 14])):
        gauge = telemetry.REGISTRY.get(name)
        assert [gauge.value(site=s) for s in sites] == want


def test_query_head_j_reads_kv_head_j_over_seven():
    """Changing one of KV head 1's keys moves query heads 7-13 and leaves
    heads 0-6 alone, bit for bit."""
    q, k, v = normal(11, 1, 20, 14, 8), normal(12, 1, 20, 2, 8), \
        normal(13, 1, 20, 2, 8)
    base = REF.masked_attention(q, k, v, 0)
    moved = REF.masked_attention(q, k.at[:, 5, 1].add(1.0), v, 0)
    assert (np.asarray(moved[:, :, :7]) == np.asarray(base[:, :, :7])).all()
    assert error(moved[:, :, 7:], base[:, :, 7:]) > 1e-3
    got = kernel("fused_attention_qkv",
                 {"num_heads": 14, "num_kv_heads": 2, "causal": True},
                 Q=q.reshape(1, 20, 112), K=k.reshape(1, 20, 16),
                 V=v.reshape(1, 20, 16))["Out"]
    close(got, base.reshape(1, 20, 112), OP_TOL)


def test_a_full_layer_has_no_position_and_a_window_layer_has_one():
    """Without rotary and without a window, attention is a function of
    the SET of earlier tokens: swapping two early tokens leaves the last
    query's output alone. Under rotary it moves."""
    p = {"w_q": normal(20, 32, 112, scale=0.3),
         "w_k": normal(21, 32, 16, scale=0.3),
         "w_v": normal(22, 32, 16, scale=0.3),
         "w_o": normal(24, 112, 32, scale=0.3)}
    x = normal(25, 1, 20, 32)
    swapped = x.at[0, 3].set(x[0, 9]).at[0, 9].set(x[0, 3])
    wide = dict(CFG, window=64)
    close(REF.attention(p, swapped, wide, 0, 0)[0, -1],
          REF.attention(p, x, wide, 0, 0)[0, -1], OP_TOL)
    assert error(REF.attention(p, swapped, wide, 1, 1)[0, -1],
                 REF.attention(p, x, wide, 1, 1)[0, -1]) > 1e-3


def test_a_window_layer_sees_no_key_outside_its_window():
    """Moving the keys and values 24 or more positions behind the last
    query leaves its output alone; moving a nearer one does not."""
    p = {"w_q": normal(30, 32, 112, scale=0.3),
         "w_k": normal(31, 32, 16, scale=0.3),
         "w_v": normal(32, 32, 16, scale=0.3),
         "w_o": normal(34, 112, 32, scale=0.3)}
    x = normal(35, 1, 40, 32)
    base = REF.attention(p, x, CFG, 1, 1)[0, -1]
    far = REF.attention(p, x.at[0, :16].add(1.0), CFG, 1, 1)[0, -1]
    near = REF.attention(p, x.at[0, 16].add(1.0), CFG, 1, 1)[0, -1]
    assert (np.asarray(far) == np.asarray(base)).all()
    assert error(near, base) > 1e-3


def test_rotary_is_theta_1p5e6_over_the_whole_head():
    x = normal(36, 2, 50, 3 * 128)
    got = kernel("rotary_embedding", {"num_heads": 3, "rotary_dim": 128,
                                      "theta": 1500000.0}, X=x)["Out"]
    want = REF.rotary(x.reshape(2, 50, 3, 128), 1500000.0)
    close(got, want.reshape(2, 50, -1), OP_TOL)
    assert (PUBLISHED["rope_theta"], PUBLISHED["head_dim"]) == (1.5e6, 128)


# ------------------------------------------------------------- the router
def test_router_is_softmax_over_the_chosen_six():
    """Top 6 by logit, w = softmax over the 64 renormalised among the
    chosen = softmax over the 6 chosen logits: the op's and the
    reference's, index for index."""
    x, w = normal(40, 2, 10, 32), normal(41, 32, 64)
    got = kernel("moe_router", {"top_k": 6, "scoring": "softmax"}, X=x, W=w)
    idx, weight = REF.route(x, w, 6)
    assert (np.asarray(got["TopkIdx"]) == np.asarray(idx)).all()
    close(got["TopkWeight"], weight, OP_TOL)
    logits = np.asarray(x @ w)
    chosen = np.take_along_axis(logits, np.asarray(idx), -1)
    want = np.exp(chosen) / np.exp(chosen).sum(-1, keepdims=True)
    np.testing.assert_allclose(weight, want, rtol=1e-5)
    np.testing.assert_allclose(np.sum(got["TopkWeight"], -1), 1.0, rtol=1e-6)


def _layer_with_the_router_on(where, p, x, cfg):
    """Layer 1 (window, rotary) in the reference's parts, the router fed
    "input" (the reference's own layer), "normed" (the input norm's
    output) or "post_attention" (the experts' own input, where the three
    accepted builders route)."""
    h = REF.rms_norm(x, p["input_norm"], cfg["eps"])
    y = x + REF.attention(REF._sub(p, "attn."), h, cfg, 1, 1)
    g = REF.rms_norm(y, p["post_norm"], cfg["eps"])
    r = {"input": x, "normed": h, "post_attention": g}[where]
    return y + REF.moe(REF._sub(p, "moe."), r, g, cfg)


def test_the_router_reads_the_layers_input_above_attention():
    """THE ROUTER-INPUT TEST. The builder's layer is the reference's
    layer, whose router reads the un-normed input; the same weights with
    the router fed the normed input, or the post-attention normed state
    the experts read, give another output by far more than the limit:
    this fails if `decoder_layer` moves the router. The weights are ten
    times the init's and the input's dims differ in scale, so that the
    router's choice and its weights matter."""
    cfg = dict(CFG, init_std=0.2)
    x = normal(42, 2, SEQ, cfg["hidden"]) * jnp.linspace(0.2, 3.0,
                                                         cfg["hidden"])

    def build():
        data = fluid.data("x", shape=[SEQ, cfg["hidden"]], dtype="float32")
        return [smallthinker.decoder_layer(data, 1, cfg)]

    (y,), params, main = _run_layer(build, {"x": np.asarray(x)})
    p = {n[len("layers.1."):]: w for n, w in params.items()}
    want = REF.decoder_layer(p, x, cfg, 1)
    close(want, _layer_with_the_router_on("input", p, x, cfg), 1e-6)
    close(y, want, OP_TOL)
    for where in ("normed", "post_attention"):
        moved = _layer_with_the_router_on(where, p, x, cfg)
        assert error(moved, want) > 1e-2, where
        assert error(y, moved) > 1e-2, where
    # in the program: the router's X is the layer's input, the experts'
    # X is not, and the router stands before the norm and the attention
    ops = main.global_block().ops
    router = next(o for o in ops if o.type == "moe_router")
    experts = next(o for o in ops if o.type == "moe_expert_ffn")
    assert router.input("X") == ["x"] != experts.input("X")
    types = [o.type for o in ops]
    assert types.index("moe_router") < types.index("rms_norm") \
        < types.index("fused_attention_qkv") < types.index("moe_expert_ffn")
    assert experts.input("TopkIdx") == router.output("TopkIdx")
    assert experts.attr("activation") == "relu"


def test_the_routers_gradient_reaches_the_stream_above_attention():
    """d loss / d x through the router alone (the attention and expert
    weights held, the experts' input cut): it is not zero, and it is the
    reference's."""
    x, p = normal(43, 2, 20, D), _moe_weights(44)
    g = normal(45, 2, 20, D)
    cfg = {"experts_per_tok": K_TOP, "expert_start": 0}

    def ref(x):
        return jnp.sum(REF.moe(p, x, g, cfg) ** 2)

    def op(x):
        r = kernel("moe_router", {"top_k": K_TOP}, X=x, W=p["w_router"])
        return jnp.sum(_expert_op(
            {"activation": "relu", "num_experts": E}, g, r["TopkIdx"],
            r["TopkWeight"], p["w_gate_up"], p["w_down"]) ** 2)

    want = jax.grad(ref)(x)
    assert float(jnp.abs(want).max()) > 1e-3
    close(jax.grad(op)(x), want, OP_TOL)


# --------------------------------------------------------- the expert layer
def _routed(r, g, p, start, held):
    chosen = kernel("moe_router", {"top_k": 6}, X=r, W=p["w_router"])
    return kernel("moe_expert_ffn",
                  {"expert_start": start, "num_experts": 64,
                   "activation": "relu"},
                  X=g, TopkIdx=chosen["TopkIdx"],
                  TopkWeight=chosen["TopkWeight"],
                  WGateUp=p["w_gate_up"][start:start + held],
                  WDown=p["w_down"][start:start + held])["Out"]


def test_the_four_ranks_shares_add_up_to_the_uncut_layer(expert_lowering):
    """THE SHARE TEST, at the cell's counts (64 experts, top 6, 4 ranks
    of 16): the parts the four ranks compute (`expert_start` 0, 16, 32,
    48) from the router's input r and the experts' input g add up to
    what the reference gives for the whole layer with every expert
    held; there is no shared expert, so nothing is counted once; and one
    rank's part alone is not the layer."""
    r, g = normal(60, 2, 10, D), normal(61, 2, 10, D)
    p = {"w_router": normal(62, D, 64),
         "w_gate_up": normal(63, 64, D, 2 * F, scale=0.2),
         "w_down": normal(64, 64, F, D, scale=0.2)}
    cfg = {"experts_per_tok": 6, "expert_start": 0}
    parts = [_routed(r, g, p, rank * 16, 16) for rank in range(4)]
    whole = REF.moe(p, r, g, cfg)
    close(sum(parts), whole, OP_TOL)
    assert error(parts[0], whole) > 0.05
    # each rank against the reference given the same share
    for rank in (0, 3):
        cut = dict(p, w_gate_up=p["w_gate_up"][rank * 16:rank * 16 + 16],
                   w_down=p["w_down"][rank * 16:rank * 16 + 16])
        close(parts[rank],
              REF.moe(cut, r, g, dict(cfg, expert_start=rank * 16)), OP_TOL)
    # every assignment lands on exactly one rank
    idx = np.asarray(REF.route(r, p["w_router"], 6)[0])
    assert sum(((idx >= s) & (idx < s + 16)).sum()
               for s in (0, 16, 32, 48)) == idx.size == 2 * 10 * 6


def test_the_cells_layer_is_bound_at_twice_its_expected_rows():
    """1,536 rows an expert are expected; twice that, 49,152 rows, is
    a pass, HALF the 98,304 the layer could be sent (ISSUE 39's bound:
    two windows at most, the second only where the router sends the held
    experts more than half of everything). Qwen's and Laguna's cells
    keep twice their expected rows too (1/8 and 1/4 of their most)."""
    assert 16384 * 6 * 16 // 64 == 24576 == 16 * 1536
    assert decoder_ops.row_bound(16384, 6, 16, 64) == 49152 == 16384 * 6 // 2
    assert decoder_ops.row_bound(16384, 6, 8, 64) == 24576   # the fallback
    assert decoder_ops.row_bound(4096, 10, 32, 512) == 5120
    assert decoder_ops.row_bound(8192, 8, 32, 256) == 16384


# ------------------------------------------------------------ the whole model
@pytest.fixture(scope="module")
def trained_once():
    """{recompute: (loss, {parameter: gradient}, the compiled step)} of
    one step of the four-layer program on one batch, and the reference's
    (loss, gradients) at the same weights, plain and with every
    activation rounded to bf16."""
    out = {}
    feed = smallthinker.synthetic_pretrain_batch(CFG, 2, SEQ, 3)
    for recompute in (False, True):
        with warnings.catch_warnings():
            warnings.simplefilter("error")  # a fallback warning fails
            main, startup, _, fetches = \
                smallthinker.build_smallthinker_pretrain_program(
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


# every parameter of the program: 3 + 4 layers x 9
PARAMETERS = ["embed_tokens", "lm_head", "final_norm"] + [
    f"layers.{i}.{n}" for i in range(4)
    for n in ("input_norm", "post_norm", "attn.w_q", "attn.w_k", "attn.w_v",
              "attn.w_o", "moe.w_router", "moe.w_gate_up", "moe.w_down")]


def test_the_program_s_parameters_are_the_reference_s(trained_once):
    names = trained_once["names"]
    assert sorted(names) == sorted(PARAMETERS) and len(names) == 3 + 4 * 9
    shapes = {n: tuple(g.shape) for n, g in trained_once[True][1].items()}
    assert shapes["layers.0.attn.w_q"] == (32, 14 * 8)
    assert shapes["layers.0.moe.w_router"] == (32, 16)
    assert shapes["layers.2.moe.w_gate_up"] == (4, 32, 24)   # experts 4-7
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


def test_every_gradient_is_within_the_limit_and_recompute_changes_none(
        trained_once):
    worst = max(error(trained_once[True][1][n],
                      trained_once["reference"][1][n])
                for n in trained_once["names"])
    assert worst <= GRAD_TOL / 10, worst  # float32 against float32, in fact
    assert abs(trained_once[True][0] - trained_once[False][0]) \
        <= LOSS_TOL * trained_once[False][0]
    for n in trained_once["names"]:
        close(trained_once[True][1][n], trained_once[False][1][n],
              GRAD_TOL / 10)


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
    """The router's choice (an int32 index and a weight) crosses the
    attention block INSIDE its layer's segment: a segment hands on the
    residual stream alone, and the plan is not the fallback."""
    plain, remat = trained_once[False][2], trained_once[True][2]
    assert plain._remat_plan is None and remat._remat_plan is not None
    plan = remat._remat_plan
    assert len(plan.segments) == 5          # four layers and the head
    assert [len(s.outs) for s in plan.segments] == [1] * 5


def test_published_config_counts_the_issue_s_parameters():
    """The cell's cut of the published sizes, counted from the
    program's own parameter shapes, without running it: 559,290,880
    (and the fallback's 370,547,200 at 8 held); the whole model 21.5e9."""
    def sizes(held):
        cfg = dict(PUBLISHED, vocab_size=18992, experts_held=held,
                   rope_layout=PUBLISHED["rope_layout"][:4],
                   window_layout=PUBLISHED["window_layout"][:4])
        main, _, _, _ = smallthinker.build_smallthinker_pretrain_program(
            cfg, seq_len=16384)
        return {p.name: int(np.prod(p.shape))
                for p in main.global_block().all_parameters()}

    got = sizes(16)
    assert sum(got.values()) == 559290880
    assert sum(sizes(8).values()) == 370547200

    def layer(i):
        return sum(v for n, v in got.items() if n.startswith(f"layers.{i}."))
    assert [layer(i) for i in range(4)] == [115512320] * 4
    assert sum(v for n, v in got.items() if ".attn." in n) == 4 * 20971520
    assert got["layers.0.moe.w_router"] == 163840
    assert got["layers.0.moe.w_gate_up"] + got["layers.0.moe.w_down"] \
        == 16 * 5898240 == 94371840
    assert got["embed_tokens"] + got["lm_head"] == 97239040
    assert got["final_norm"] == 2560
    h = 2560
    whole = 52 * (2 * h * 3584 + 2 * h * 512 + h * 64 + 2 * h
                  + 64 * 3 * h * 768) + 2 * h * 151936 + h
    assert abs(whole / 21.5e9 - 1) < 5e-3
    assert (PUBLISHED["rope_layout"], PUBLISHED["window_layout"]) \
        == ([0, 1, 1, 1] * 13,) * 2


# ----------------------------------------------------------------- the hoist
def test_the_builders_share_one_expert_passes_and_one_attention_sites():
    for module in (qwen3_next, laguna, smallthinker):
        assert module.expert_passes is _decoder_parts.expert_passes
    for module in (phi4_flash, laguna, smallthinker):
        assert module.attention_sites is _decoder_parts.attention_sites
    assert "expert_passes" in qwen3_next.__all__
    assert "attention_sites" in phi4_flash.__all__
    from paddle_tpu import models
    assert models.smallthinker is smallthinker


# ------------------------------------------------- the embedding's own scale
@pytest.mark.parametrize("embed_std", [1.0, 0.25])
def test_the_embedding_is_drawn_at_its_own_scale(embed_std):
    """`embed_init_std` is the embedding's alone: every other matrix,
    the head included, keeps `init_std`, the norms start from 1."""
    cfg = dict(CFG, vocab_size=512, embed_init_std=embed_std)
    main, startup, _, _ = smallthinker.build_smallthinker_pretrain_program(
        cfg, seq_len=SEQ)
    main.random_seed = startup.random_seed = 5
    exe, scope = fluid.Executor(fluid.CPUPlace()), core.Scope()
    exe.run(startup, scope=scope)
    std = {p.name: float(np.std(np.asarray(
        scope.find_var(p.name).get_tensor().array)))
        for p in main.global_block().all_parameters()}
    assert std.pop("embed_tokens") == pytest.approx(embed_std, rel=0.03)
    norms = [n for n in std if n.endswith("_norm")]
    assert len(norms) == 9 and all(std.pop(n) == 0.0 for n in norms)
    assert all(s == pytest.approx(cfg["init_std"], rel=0.1)
               for s in std.values()), std
    assert PUBLISHED["embed_init_std"] == 1.0 and PUBLISHED["init_std"] == 0.02


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_a_unit_embedding_keeps_the_routers_input_from_collapsing(seed):
    """WHY the embedding has a scale of its own. The routers read the
    stream un-normed. With the embedding at the other matrices' scale a
    token's own vector is 1/50 of a normed block input, attention over
    uniform random tokens is a running mean, and its output, one common
    vector, takes the stream over: by the last layer the router's
    inputs point one way (mean pairwise cosine over 0.3) and the held
    experts' share of a layer is the luck of a batch. At 50 x that scale
    (what 1.0 is to 0.02) the tokens stay apart in every layer and every
    layer sends the held quarter its quarter. The reference's forward at
    a small width whose init stands to it as 0.02 to 2560."""
    s, d, heads, hkv, hd, f, e, held, k, vocab = \
        384, 128, 4, 2, 32, 32, 16, 4, 3, 512
    cfg = dict(heads=heads, kv_heads=hkv, head_dim=hd, rope_theta=1.5e6,
               window=4096, eps=1e-6, experts_per_tok=k, expert_start=0,
               rope_layout=[0, 1, 1, 1], window_layout=[0, 1, 1, 1])
    std = 1.13 / np.sqrt(d)

    def stream(embed_ratio):
        rng = np.random.default_rng(seed)

        def draw(*shape, scale=std):
            return jnp.asarray(
                rng.standard_normal(shape, dtype=np.float32) * scale)

        x = draw(vocab, d, scale=std * embed_ratio)[
            rng.integers(0, vocab, (1, s))]
        out = []
        for i in range(4):
            unit = np.asarray(x[0])
            unit = unit / np.linalg.norm(unit, axis=-1, keepdims=True)
            p = {"input_norm": jnp.ones(d), "post_norm": jnp.ones(d),
                 "attn.w_q": draw(d, heads * hd),
                 "attn.w_k": draw(d, hkv * hd), "attn.w_v": draw(d, hkv * hd),
                 "attn.w_o": draw(heads * hd, d),
                 "moe.w_router": draw(d, e),
                 "moe.w_gate_up": draw(held, d, 2 * f),
                 "moe.w_down": draw(held, f, d)}
            top_i, _ = REF.route(x, p["moe.w_router"], k)
            # |mean unit vector|^2 = the mean pairwise cosine
            out.append((float(np.sum(unit.mean(0) ** 2)),
                        float(jnp.mean(top_i < held))))
            x = REF.decoder_layer(p, x, cfg, i)
        return out

    collapsed, apart = stream(1.0), stream(50.0)
    assert collapsed[0][0] < 0.05 < 0.3 < collapsed[3][0]
    assert all(cosine < 0.05 for cosine, _ in apart)
    assert all(abs(share - held / e) < 0.06 for _, share in apart)
