"""Qwen3-Next's ops, layers and program against the plain float32
reference (benchmark/configs/qwen3_next_80b_a3b_reference.py), at toy
sizes on the CPU with seeded random weights.

Tolerances: both sides compute in float32 here (no MXU, so no bf16
operand), and differ only in the order of their sums: the chunked delta
rule against the token-by-token recurrence, a grouped product against a
loop over experts. 2e-5 of a tensor's largest entry is ~100 float32
roundings, what a sum over a few hundred terms in another order may
move; the whole model crosses four layers and gets 1e-4.
"""
import importlib.util
import os
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import core, telemetry
from paddle_tpu.models import qwen3_next as qn
from paddle_tpu.ops import decoder_ops
from paddle_tpu.ops.registry import OPS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OP_TOL, MODEL_TOL = 2e-5, 1e-4


def _load(path):
    spec = importlib.util.spec_from_file_location(
        "_q3n_" + os.path.basename(path)[:-3], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load(os.path.join(REPO, "benchmark", "configs",
                         "qwen3_next_80b_a3b_reference.py"))

TOY = dict(qn.qwen3_next_config(), vocab_size=96, hidden=32, layers=4,
           heads=4, kv_heads=2, head_dim=16, linear_key_heads=2,
           linear_value_heads=4, linear_key_dim=8, linear_value_dim=8,
           num_experts=16, experts_per_tok=3, expert_width=12,
           shared_width=12, experts_held=4, expert_start=4)


def kernel(op_type, attrs=None, **ins):
    """The op's registered kernel on arrays: {slot: array} -> outputs."""
    outs = OPS.get(op_type).kernel({k: [v] for k, v in ins.items()},
                                   dict(attrs or {}))
    return {k: v[0] for k, v in outs.items()}


def close(got, want, tol):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    scale = np.abs(want).max() + 1e-30
    assert np.abs(got - want).max() <= tol * scale, \
        (np.abs(got - want).max() / scale, tol)


def normal(seed, *shape, scale=1.0):
    return jnp.asarray(np.random.default_rng(seed).normal(
        0.0, scale, shape).astype(np.float32))


# ------------------------------------------------------------- small ops
@pytest.mark.parametrize("zero_centered", [False, True])
@pytest.mark.parametrize("group,gated", [(None, False), (8, False),
                                         (8, True)])
def test_rms_norm_forms(zero_centered, group, gated):
    x, w = normal(0, 2, 5, 32), normal(1, group or 32, scale=0.3)
    gate = normal(2, 2, 5, 32) if gated else None

    def program(x, w, gate):
        ins = dict(X=x, Scale=w, **({"Gate": gate} if gated else {}))
        return kernel("rms_norm", {"epsilon": 1e-6,
                                   "zero_centered": zero_centered},
                      **ins)["Out"]

    def reference(x, w, gate):
        xr = x.reshape(2, 5, -1, group or 32)
        y = ref.rms_norm(xr, w, 1e-6, zero_centered).reshape(x.shape)
        return y * ref.silu(gate) if gated else y

    close(program(x, w, gate), reference(x, w, gate), OP_TOL)
    args = (0, 1, 2) if gated else (0, 1)
    for got, want in zip(
            jax.grad(lambda *a: jnp.sum(program(*a) ** 2), args)(x, w, gate),
            jax.grad(lambda *a: jnp.sum(reference(*a) ** 2), args)(x, w,
                                                                   gate)):
        close(got, want, OP_TOL)


@pytest.mark.parametrize("rotary_dim", [4, 16])
def test_rotary_embedding_rotates_the_first_dims_of_each_head(rotary_dim):
    x = normal(3, 2, 7, 4 * 16)
    got = kernel("rotary_embedding", {"num_heads": 4, "theta": 1e7,
                                      "rotary_dim": rotary_dim}, X=x)["Out"]
    want = ref.rotary(x.reshape(2, 7, 4, 16), 1e7, rotary_dim)
    close(got, want.reshape(x.shape), OP_TOL)
    # position 0 is not rotated, and the dims past rotary_dim never are
    close(got[:, 0], x[:, 0], 0)
    assert (np.asarray(got.reshape(2, 7, 4, 16)[..., rotary_dim:])
            == np.asarray(x.reshape(2, 7, 4, 16)[..., rotary_dim:])).all()


def test_causal_conv1d_is_depthwise_and_sees_no_future():
    x, w = normal(4, 2, 9, 6), normal(5, 6, 4)
    got = np.asarray(kernel("causal_conv1d", X=x, Filter=w)["Out"])
    want = np.zeros_like(got)
    for t in range(9):
        for j in range(4):
            if t - 3 + j >= 0:
                want[:, t] += np.asarray(w)[:, j] * np.asarray(x)[:, t - 3 + j]
    close(got, want, OP_TOL)
    close(got, ref.causal_conv(x, w), OP_TOL)
    later = x.at[:, 5:].add(1.0)
    close(kernel("causal_conv1d", X=later, Filter=w)["Out"][:, :5],
          got[:, :5], 0)


# ------------------------------------------------------- the delta rule
def _gdn_inputs(seed, s, hk=2, hv=4, dk=8, dv=8):
    return dict(Q=normal(seed, 2, s, hk * dk), K=normal(seed + 1, 2, s, hk * dk),
                V=normal(seed + 2, 2, s, hv * dv), A=normal(seed + 3, 2, s, hv),
                B=normal(seed + 4, 2, s, hv),
                ALog=jnp.log(jnp.linspace(1.0, 16.0, hv)),
                DtBias=jnp.ones((hv,)))


def _gdn_reference(Q, K, V, A, B, ALog, DtBias, hk=2, hv=4):
    b, s, _ = Q.shape
    q, k = (jnp.repeat(t.reshape(b, s, hk, -1), hv // hk, axis=2)
            for t in (Q, K))
    q, k = (t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)
            for t in (q, k))
    alpha = jnp.exp(-jnp.exp(ALog) * jax.nn.softplus(A + DtBias))
    o = ref.delta_rule(q * q.shape[-1] ** -0.5, k, V.reshape(b, s, hv, -1),
                       alpha, jax.nn.sigmoid(B))
    return o.reshape(b, s, -1)


@pytest.mark.parametrize("s,chunk", [(64, 64), (80, 64), (130, 64), (37, 16),
                                     (5, 64)])
def test_chunked_delta_rule_is_the_token_by_token_recurrence(s, chunk):
    ins = _gdn_inputs(10, s)
    attrs = {"num_key_heads": 2, "num_value_heads": 4, "chunk_size": chunk}

    def program(ins):
        return kernel("gated_delta_rule", attrs, **ins)["Out"]

    close(program(ins), _gdn_reference(**ins), OP_TOL)
    cot = normal(99, 2, s, 32)
    got = jax.grad(lambda i: jnp.sum(program(i) * cot))(ins)
    want = jax.grad(lambda i: jnp.sum(_gdn_reference(**i) * cot))(ins)
    # A up to 16 is a log-decay of -21 a token: its running sum reaches
    # -1300 inside a chunk, where a float32 ulp is 1.2e-4, and the
    # chunked form takes DIFFERENCES of such sums where the recurrence
    # multiplies token by token (the release's chunked code does the
    # same): 1e-3 for the gradients, which sum those errors
    for name in ins:
        close(got[name], want[name], 1e-3)


def test_delta_rule_counts_its_chunks_by_site():
    ins = _gdn_inputs(20, 80)
    kernel("gated_delta_rule", {"num_key_heads": 2, "num_value_heads": 4,
                                "chunk_size": 64, "site": "t_site"}, **ins)
    assert telemetry.REGISTRY.get("gdn_chunks_per_step").value(
        site="t_site") == 2 * 2  # batch 2 x ceil(80 / 64)


# ---------------------------------------------------- grouped attention
@pytest.mark.parametrize("heads,kv_heads", [(4, 2), (4, 1), (4, 4)])
def test_grouped_query_attention_serves_consecutive_query_heads(heads,
                                                                kv_heads):
    d, s = 16, 24
    q = normal(30, 2, s, heads * d)
    k, v = normal(31, 2, s, kv_heads * d), normal(32, 2, s, kv_heads * d)

    def program(q, k, v):
        return kernel("fused_attention_qkv",
                      {"num_heads": heads, "num_kv_heads": kv_heads,
                       "causal": True, "dropout_rate": 0.0,
                       "_rng": jax.random.key(0)}, Q=q, K=k, V=v)["Out"]

    def reference(q, k, v):
        return ref.causal_attention(
            q.reshape(2, s, heads, d), k.reshape(2, s, kv_heads, d),
            v.reshape(2, s, kv_heads, d)).reshape(2, s, -1)

    close(program(q, k, v), reference(q, k, v), OP_TOL)
    for got, want in zip(
            jax.grad(lambda *a: jnp.sum(program(*a) ** 2), (0, 1, 2))(q, k, v),
            jax.grad(lambda *a: jnp.sum(reference(*a) ** 2), (0, 1, 2))(
                q, k, v)):
        close(got, want, OP_TOL)


# ------------------------------------------------------- router, experts
E, K_TOP, D, F = 16, 3, 32, 12


def _moe_weights(seed, held=E):
    return dict(w_router=normal(seed, D, E),
                w_gate_up=normal(seed + 1, held, D, 2 * F, scale=0.2),
                w_down=normal(seed + 2, held, F, D, scale=0.2),
                shared_w_gate=normal(seed + 3, D, F, scale=0.2),
                shared_w_up=normal(seed + 4, D, F, scale=0.2),
                shared_w_down=normal(seed + 5, F, D, scale=0.2),
                shared_gate=normal(seed + 6, D, 1))


def _expert_layer(x, p, start, held, **attrs):
    """(the router's outputs, the expert op's) for experts start ..
    start + held - 1; ``attrs``: the op's further attributes."""
    r = kernel("moe_router", {"top_k": K_TOP}, X=x, W=p["w_router"])
    return r, kernel("moe_expert_ffn", dict(attrs, expert_start=start),
                     X=x, TopkIdx=r["TopkIdx"], TopkWeight=r["TopkWeight"],
                     WGateUp=p["w_gate_up"][start:start + held],
                     WDown=p["w_down"][start:start + held])


def _routed(x, p, start, held, **attrs):
    r, o = _expert_layer(x, p, start, held, **attrs)
    return o["Out"], r["AuxLoss"]


def _gauge(name, site):
    """The gauge an op set at ``site``; 0 where none did."""
    family = telemetry.REGISTRY.get(name)
    return family.value(site=site) if family else 0


def _scalar(f):
    """A layer's (output, auxiliary loss) as one number to differentiate."""
    return lambda x, p: (lambda y, aux: jnp.sum(y ** 2) + aux)(*f(x, p))


def _shared(x, p):
    both = jnp.concatenate([p["shared_w_gate"], p["shared_w_up"]], 1)
    return jax.nn.sigmoid(x @ p["shared_gate"]) \
        * ref.expert(x, both, p["shared_w_down"])


def test_router_is_the_references():
    x, p = normal(40, 2, 10, D), _moe_weights(41)
    got = kernel("moe_router", {"top_k": K_TOP}, X=x, W=p["w_router"])
    idx, weight, aux = ref.route(x, p["w_router"], K_TOP)
    assert (np.asarray(got["TopkIdx"]) == np.asarray(idx)).all()
    assert got["TopkIdx"].dtype == jnp.int32
    close(got["TopkWeight"], weight, OP_TOL)
    close(got["AuxLoss"][0], aux, OP_TOL)
    close(jnp.sum(got["TopkWeight"], -1), jnp.ones((2, 10)), OP_TOL)


@pytest.mark.parametrize("start,held", [(0, 16), (4, 4), (12, 4), (5, 1)])
def test_held_experts_part_and_its_gradients(start, held):
    """The op, holding experts start..start+held-1, against the
    reference given the same held set (shared expert included there,
    added here)."""
    x, p = normal(50, 2, 10, D), _moe_weights(51)
    cfg = {"experts_per_tok": K_TOP, "expert_start": start}

    def cut(p):
        return dict(p, w_gate_up=p["w_gate_up"][start:start + held],
                    w_down=p["w_down"][start:start + held])

    def program(x, p):
        y, aux = _routed(x, p, start, held)
        return y + _shared(x, p), aux[0]

    def reference(x, p):
        return ref.moe(cut(p), x, cfg)

    for got, want in zip(program(x, p), reference(x, p)):
        close(got, want, OP_TOL)

    got = jax.grad(_scalar(program), (0, 1))(x, p)
    want = jax.grad(_scalar(reference), (0, 1))(x, p)
    close(got[0], want[0], OP_TOL)
    for name in p:
        close(got[1][name], want[1][name], OP_TOL)


@pytest.mark.parametrize("num_experts", [0, E],
                         ids=["width_unknown", "bounded"])
@pytest.mark.parametrize("ranks", [16, 4, 1])
def test_the_shares_of_all_ranks_add_up_to_the_uncut_layer(
        ranks, num_experts, monkeypatch, expert_lowering):
    """THE SHARE TEST: the routed parts that `ranks` expert-parallel
    ranks compute, each holding E / ranks experts, plus the shared
    expert counted once, are what the reference gives for the whole
    layer with every expert held; also where each rank knows the
    router's width and bounds its rows (at a tile of 4 rows: 8 of 20, 32
    of 60, and, for the one rank that holds everything, all 60)."""
    monkeypatch.setattr(decoder_ops, "ROW_TILE", 4)
    x, p = normal(60, 2, 10, D), _moe_weights(61)
    held = E // ranks
    site = "t_share_" + expert_lowering
    parts = [_routed(x, p, r * held, held, num_experts=num_experts,
                     site=site)[0] for r in range(ranks)]
    whole, _ = ref.moe(p, x, {"experts_per_tok": K_TOP, "expert_start": 0})
    close(sum(parts) + _shared(x, p), whole, OP_TOL)
    full = 20 * min(K_TOP, held)
    rows = {16: 8, 4: 32, 1: full}[ranks] if num_experts else full
    passes = {16: 3, 4: 2, 1: 1}[ranks] if num_experts else 1
    assert _gauge("moe_rows_per_step", site) == rows
    assert _gauge("moe_row_passes_max", site) == passes
    # the kernels' gauges say which lowering ran: a tile of 8 rows, the
    # tiles all the passes span (rows padded to whole tiles); else unset
    kernels = expert_lowering == "kernels"
    assert _gauge("moe_row_tile", site) == 8 * kernels
    assert _gauge("moe_grid_row_tiles_per_step", site) == \
        passes * -(-rows // 8) * kernels
    # and a rank whose experts nobody chose adds exactly nothing
    x = x.at[..., 0].set(1.0)  # a constant feature: a bias on the logits
    nobody = dict(p, w_router=p["w_router"].at[:, :4].set(0.0)
                  .at[0, :4].set(-50.0))
    assert not np.asarray(_routed(x, nobody, 0, 4)[0]).any()


@pytest.mark.parametrize("held", [1, 4])
def test_a_router_forced_onto_one_expert_loses_no_assignment(
        held, expert_lowering):
    """Every token sent to expert 5 (and to two more): the layer that
    holds it computes all 64 of them, far past 64 x 3 / 16 a fair
    share: no capacity, no drop."""
    # a constant feature makes a bias: expert 5's logit is 50 for every
    # token, every other logit 0
    x = normal(70, 4, 16, D).at[..., 0].set(1.0)
    p = dict(_moe_weights(71),
             w_router=jnp.zeros((D, E)).at[0, 5].set(50.0))
    r = kernel("moe_router", {"top_k": K_TOP}, X=x, W=p["w_router"])
    assert (np.asarray(r["TopkIdx"])[..., 0] == 5).all()
    got, _ = _routed(x, p, 5, held)
    mine = (np.asarray(r["TopkIdx"]) >= 5) & (np.asarray(r["TopkIdx"])
                                              < 5 + held)
    want = sum(
        jnp.sum(jnp.where(r["TopkIdx"] == e, r["TopkWeight"], 0.0), -1,
                keepdims=True)
        * ref.expert(x, p["w_gate_up"][e], p["w_down"][e])
        for e in range(5, 5 + held))
    close(got, want, OP_TOL)
    assert mine[..., 0].all() and np.abs(np.asarray(got)).min(-1).min() > 0


@pytest.mark.parametrize("tokens,k,held,num_experts,want", [
    (4096, 10, 32, 512, 5120),    # the cell: 2 x 2,560 expected, of 40,960
    (4096, 10, 32, 0, 40960),     # width unknown: the most, as before
    (4096, 10, 512, 512, 40960),  # one rank holds every expert: the most
    (4096, 10, 128, 512, 20480),  # an EP4 rank
    (4096, 10, 4, 512, 1024),     # 2 x 320 = 640: two tiles
    (4096, 10, 2, 512, 512),      # 2 x 160 = 320: one tile
    (20, 3, 2, 16, 40),           # a tile is more than the most: the most
    (4000, 10, 32, 512, 5120),    # 2 x 2,500 = 5,000, rounded up to a tile
])
def test_row_bound_is_twice_the_held_share_in_whole_tiles(
        tokens, k, held, num_experts, want):
    assert decoder_ops.ROW_TILE == 512
    assert decoder_ops.row_bound(tokens, k, held, num_experts) == want


@pytest.mark.parametrize("checkpoint", [False, True],
                         ids=["plain", "checkpoint"])
@pytest.mark.parametrize("held,tile,ran,most", [(1, 8, 3, 3), (2, 16, 2, 3),
                                                (4, 32, 2, 2)])
def test_a_forced_overflow_runs_further_passes_and_loses_no_assignment(
        held, tile, ran, most, checkpoint, monkeypatch, expert_lowering):
    """Every token sent to expert 5, the layer told the router's width:
    64 rows and more where 12 a held expert are expected and twice that
    is the bound. The windows past the first run (three of 24 rows for
    one expert; two of 48 and a skipped third for two; 96 and the 5 rows
    left over for four), and output and every gradient, the router's
    too, are the reference's, also under jax.checkpoint."""
    monkeypatch.setattr(decoder_ops, "ROW_TILE", tile)
    x = normal(70, 4, 16, D).at[..., 0].set(1.0)
    p = dict(_moe_weights(71),
             w_router=normal(72, D, E, scale=0.01).at[0, 5].set(50.0))
    cfg = {"experts_per_tok": K_TOP, "expert_start": 5}

    def cut(p):
        return dict(p, w_gate_up=p["w_gate_up"][5:5 + held],
                    w_down=p["w_down"][5:5 + held])

    def program(x, p):
        r, o = _expert_layer(x, p, 5, held, num_experts=E,
                             site="t_forced_" + expert_lowering)
        return o["Out"] + _shared(x, p), r["AuxLoss"][0]

    def reference(x, p):
        return ref.moe(cut(p), x, cfg)

    r, o = _expert_layer(x, p, 5, held, num_experts=E)
    idx = np.asarray(r["TopkIdx"])
    routed = int(((idx >= 5) & (idx < 5 + held)).sum())
    bound = decoder_ops.row_bound(64, K_TOP, held, E)
    assert (idx[..., 0] == 5).all() and bound == 12 * held * 2
    assert -(-routed // bound) == ran and -(-64 * min(K_TOP, held)
                                            // bound) == most
    assert o["Passes"].dtype == jnp.int32 and o["Passes"].shape == (1,)
    assert int(o["Passes"][0]) == ran
    assert _gauge("moe_row_passes_max", "") == most

    if checkpoint:
        program = jax.checkpoint(program)
    for got, want in zip(program(x, p), reference(x, p)):
        close(got, want, OP_TOL)

    got = jax.jit(jax.grad(_scalar(program), (0, 1)))(x, p)
    want = jax.grad(_scalar(reference), (0, 1))(x, p)
    close(got[0], want[0], OP_TOL)
    for name in p:
        close(got[1][name], want[1][name], OP_TOL)
    assert _gauge("moe_row_tile", "t_forced_" + expert_lowering) == \
        8 * (expert_lowering == "kernels")


@pytest.mark.parametrize("tile", [4, 512])
def test_a_uniform_router_fits_one_pass(tile, monkeypatch):
    """At a router that spreads its tokens, twice the share holds what
    is routed: Passes reads 1, whether the bound is below the most (a
    tile of 4: 32 rows of 60) or the most itself."""
    monkeypatch.setattr(decoder_ops, "ROW_TILE", tile)
    x, p = normal(60, 2, 10, D), _moe_weights(61)
    for start in range(0, E, 4):
        _, o = _expert_layer(x, p, start, 4, num_experts=E, site="t_fit")
        assert int(o["Passes"][0]) == 1
    assert _gauge("moe_rows_per_step", "t_fit") == (32 if tile == 4 else 60)


@pytest.mark.parametrize("num_experts,rows,most", [(0, 40, 1), (16, 16, 3)],
                         ids=["width_unknown", "bounded"])
def test_expert_layer_counts_its_rows_and_experts_by_site(num_experts, rows,
                                                         most, monkeypatch):
    """20 tokens, top-3, experts 4 and 5 of 16 held. Without the
    router's width: 20 x min(3, 2) = 40 rows, one pass, as before. With
    it, at a tile of 8 rows: 7.5 assignments expected, 15 doubled, 16 in
    whole tiles, and the 40 a no-drop layer could be sent are three
    passes of them."""
    monkeypatch.setattr(decoder_ops, "ROW_TILE", 8)
    x, p = normal(80, 2, 10, D), _moe_weights(81)
    site = f"t_rows_{num_experts}"
    _expert_layer(x, p, 4, 2, num_experts=num_experts, site=site)
    assert _gauge("moe_rows_per_step", site) == rows
    assert _gauge("moe_row_passes_max", site) == most
    assert _gauge("moe_experts_held", site) == 2


# ------------------------------------------------------ the whole model
def _toy_step(recompute, seq_len=80, batch=2):
    main, startup, _, fetches = qn.build_qwen3_next_pretrain_program(
        TOY, seq_len=seq_len, lr=1e-3, recompute=recompute)
    main.random_seed = startup.random_seed = 7
    exe, scope = fluid.Executor(), core.Scope()
    exe.run(startup, scope=scope)
    names = [p.name for p in main.global_block().all_parameters()]
    params = {n: jnp.asarray(np.asarray(
        scope.find_var(n).get_tensor().array)) for n in names}
    feed = qn.synthetic_pretrain_batch(TOY, batch, seq_len, seed=3)
    return main, exe, scope, fetches, names, params, feed


@pytest.mark.parametrize("row_tile", [512, 64], ids=["one_pass", "windows"])
@pytest.mark.parametrize("recompute", [True, False],
                         ids=["recompute", "stored"])
def test_toy_model_loss_and_every_gradient_against_the_reference(
        recompute, row_tile, monkeypatch):
    """Four layers (three Gated DeltaNet, one gated attention), experts
    4-7 of 16 held, S = 80 (not a multiple of the chunk): the fetched
    loss and the gradient of EVERY parameter, fetched as @GRAD from the
    one `exe.run` that also applies Adam. Under recomputation the
    checkpoints must lower onto jax.checkpoint segments with no
    fallback (a warning is an error here), although the fetched loss
    (cross entropy) is not the trained one. At the kernel's row tile
    the 480 rows a layer could be sent are one pass; at a tile of 64
    they are two windows of 256 (120 expected), the second skipped:
    each layer's fetched `Passes` reads 1."""
    monkeypatch.setattr(decoder_ops, "ROW_TILE", row_tile)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        main, exe, scope, fetches, names, params, feed = _toy_step(recompute)
        passes = qn.expert_passes(main)
        out = exe.run(main, feed=feed, scope=scope,
                      fetch_list=[fetches[0].name]
                      + [n + "@GRAD" for n in names]
                      + list(passes.values()))
    assert len(passes) == 4
    for site, ran in zip(passes, out[1 + len(names):]):
        assert ran.dtype == np.int32 and ran.tolist() == [1]
        assert _gauge("moe_rows_per_step", site) == (
            480 if row_tile == 512 else 256)
        assert _gauge("moe_row_passes_max", site) == (
            1 if row_tile == 512 else 2)
    out = out[:1 + len(names)]
    from tools.mfu_report import compiled_step_of
    assert (compiled_step_of(exe)._remat_plan is not None) == recompute
    ce, grads = ref.loss_and_grads(params, jnp.asarray(feed["ids"]),
                                   jnp.asarray(feed["labels"][..., 0]), TOY)
    close(out[0].ravel()[0], ce, 1e-6)
    assert abs(float(ce) - np.log(96)) < 0.05  # ln(vocabulary) + v / 2
    assert len(names) == 66
    for name, got in zip(names, out[1:]):
        assert np.abs(np.asarray(grads[name])).max() > 0, name
        close(got, grads[name], MODEL_TOL)
    # the step trained: the same batch again reads a lower loss
    again = exe.run(main, feed=feed, fetch_list=fetches, scope=scope)
    assert again[0].ravel()[0] < out[0].ravel()[0]


def test_toy_model_parameters_are_the_references_names_and_shapes():
    main, _, _, _, names, params, _ = _toy_step(True)
    want = {"embed_tokens": (96, 32), "lm_head": (32, 96),
            "final_norm": (32,),
            "layers.0.gdn.w_qkvz": (32, 2 * 16 + 2 * 32),
            "layers.0.gdn.w_ba": (32, 8), "layers.0.gdn.conv_w": (64, 4),
            "layers.0.gdn.a_log": (4,), "layers.0.gdn.dt_bias": (4,),
            "layers.0.gdn.norm": (8,), "layers.0.gdn.w_o": (32, 32),
            "layers.3.attn.w_q": (32, 4 * 2 * 16),
            "layers.3.attn.w_k": (32, 32), "layers.3.attn.q_norm": (16,),
            "layers.3.attn.w_o": (64, 32),
            "layers.2.moe.w_router": (32, 16),
            "layers.2.moe.w_gate_up": (4, 32, 24),
            "layers.2.moe.w_down": (4, 12, 32),
            "layers.2.moe.shared_gate": (32, 1)}
    for name, shape in want.items():
        assert params[name].shape == shape, name
    assert not [n for n in names if n.startswith("layers.3.gdn.")
                or n.startswith("layers.0.attn.")]
    # one op type a mechanism, so that the device trace's scopes split them
    types = [op.type for op in main.global_block().ops]
    for op_type, count in (("gated_delta_rule", 3), ("moe_router", 4),
                           ("moe_expert_ffn", 4), ("fused_attention_qkv", 1),
                           ("causal_conv1d", 3), ("rotary_embedding", 2)):
        assert types.count(op_type) == count, op_type


def test_published_config_counts_the_issue_s_parameters():
    """The published widths give the per-layer counts the cut was sized
    by: 33.72M a DeltaNet mixer, 27.26M an attention mixer, 4.20M of
    router + shared expert, 3.146M an expert."""
    c = qn.qwen3_next_config()
    h = c["hidden"]
    key = c["linear_key_heads"] * c["linear_key_dim"]
    value = c["linear_value_heads"] * c["linear_value_dim"]
    gdn = h * (2 * key + 2 * value) + h * 2 * c["linear_value_heads"] \
        + (2 * key + value) * c["conv_kernel"] + 2 * c["linear_value_heads"] \
        + c["linear_value_dim"] + value * h
    q = c["heads"] * c["head_dim"]
    attn = h * 2 * q + 2 * h * c["kv_heads"] * c["head_dim"] \
        + 2 * c["head_dim"] + q * h
    assert round(gdn / 1e6, 2) == 33.72 and round(attn / 1e6, 2) == 27.26
    assert 3 * h * c["expert_width"] == 3145728
    assert h * c["num_experts"] + 3 * h * c["shared_width"] + h == 4196352
