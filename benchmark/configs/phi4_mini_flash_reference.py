"""Phi-4-mini-flash-reasoning (the SambaY decoder-hybrid-decoder of
arXiv:2507.06607), the plain reference: forward, loss and gradients in
`jax.numpy`, float32, `jax.default_matmul_precision("highest")`. No
kernel, no chunk of the mathematics, no cache; it imports nothing from
`paddle_tpu`.

Layer equations, from the published `config.json` and the family's
released modelling code (`x` [B, S, hidden], one layer; LN = LayerNorm
with weight and bias, eps `layer_norm_eps`; no positional encoding):

    x <- x + Mixer(LN1 x);  [g | u] = LN2(x) W_gate_up;
    x <- x + (SiLU(g) * u) W_down
    logits = LN_f(x) E^T, E the input embedding (tied)

The kind of layer i of L (`layer_kinds`): even layers are the SSM side,
odd the attention side; i < L/2: Mamba / window attention; i = L/2: Mamba
whose scan output is the memory m; i = L/2 + 1: full attention whose K,
V are kept; later: Gated Memory Unit / cross-attention.

Mamba: `[u | z] = h W_in`; `u <- SiLU(conv1d_causal(u) + b_c)` (depthwise,
`d_conv` taps); `[delta | B | C] = u W_x`; `Delta = softplus(delta W_dt +
b_dt)`; `A = -exp(A_log)`; a channel c's state in R^{d_state}, s_0 = 0,
TOKEN BY TOKEN:

    s_t = exp(Delta_t A) * s_{t-1} + (Delta_t u_t) B_t
    y_t = s_t . C_t + D u_t

out = (y * SiLU(z)) W_out; the memory layer also hands on m = y.
Differential attention (self; causal, under a window w or in full):
`[q | k | v] = h W_qkv + b_qkv`; a KV group g of `kv_heads / 2` holds
two key heads k_1, k_2, the value [v_1 | v_2] (2 d wide) and two
differential heads j, each with two query heads q_1, q_2:

    A_c = softmax(q_c k_c^T d^-1/2 + mask),  c = 1, 2
    o = (A_1 - lambda A_2) [v_1 | v_2];  o <- RMSNorm_2d(o) (1 - lambda_init)
    lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init
    lambda_init = 0.8 - 0.6 exp(-0.3 i),  i the layer's PUBLISHED index

out = concat(o) W_o + b_o. The window keeps keys t - w < j <= t.
GMU: out = (SiLU(h W_in) * m) W_out, m at the same positions.
Cross-attention: the differential form with its own W_q, lambdas,
sub-norm and W_o, causal, unwindowed, over the full-attention layer's
k and v.

Departures from the release and sizes it leaves open are in the
configuration's JSON under `assumed`; the one that shows here is the
packing of the heads: q is laid out [group, c, j, d] and k [group, c, d],
so that query head h reads key head h // 2 and value group h // 4.

`round_to` rounds every activation that crosses from one operation to
the next (identity in the reference proper): `chip_smoke.py` uses it to
read what bf16 activations would give, the nearest precision below the
one the configuration states, which the parity limits must refuse.
"""
import math

import jax
import jax.numpy as jnp

SCAN_BLOCK = 64      # positions a rematerialised block of the recurrence
QUERY_BLOCK = 512    # query rows a block of the S x S attention


def _same(x):
    return x


def layer_kinds(n):
    """The release's rule for `n` layers (a multiple of 4)."""
    kinds = []
    for i in range(n):
        if i % 2 == 0:
            kinds.append("mamba" if i < n // 2 else
                         "mamba_memory" if i == n // 2 else "gmu")
        else:
            kinds.append("sliding" if i < n // 2 else
                         "full" if i == n // 2 + 1 else "cross")
    return kinds


def lambda_init(published_index):
    return 0.8 - 0.6 * math.exp(-0.3 * published_index)


def layer_norm(x, w, b, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def silu(x):
    return x * jax.nn.sigmoid(x)


def causal_conv(x, w):
    """Depthwise: y[t, c] = sum_j w[c, j] x[t - (K-1) + j, c]; x [B, S, C],
    w [C, K]; positions before the sequence are zero."""
    k = w.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    s = x.shape[1]
    return sum(padded[:, j:j + s] * w[:, j] for j in range(k))


def selective_scan(u, delta, a, b, c, d):
    """The recurrence above, one position a step. u, delta [B, S, C],
    a [C, N], b, c [B, S, N], d [C] -> y [B, S, C]. The scan is cut into
    blocks of SCAN_BLOCK positions, each rematerialised in the backward,
    so that the gradient keeps S / SCAN_BLOCK states and not S of them;
    the arithmetic is the token's, block or none."""
    batch, s, ch = u.shape

    def token(state, xs):
        u_t, dt_t, b_t, c_t = xs
        state = jnp.exp(dt_t[..., None] * a) * state \
            + (dt_t * u_t)[..., None] * b_t[:, None, :]
        return state, jnp.sum(state * c_t[:, None, :], -1) + d * u_t

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    pad = -s % SCAN_BLOCK
    # (padded positions come last: what they do to the state is not read)
    xs = [jnp.moveaxis(jnp.pad(t, ((0, 0), (0, pad), (0, 0))), 1, 0)
          for t in (u, delta, b, c)]
    xs = [t.reshape((-1, SCAN_BLOCK) + t.shape[1:]) for t in xs]
    _, y = jax.lax.scan(
        block, jnp.zeros((batch, ch, a.shape[1]), jnp.float32), xs)
    return jnp.moveaxis(y.reshape((-1,) + y.shape[2:])[:s], 0, 1)


def mamba(p, x, cfg, round_to=_same):
    """(the mixer's output, the scan's output y before the gate)."""
    inner, n, rank = cfg["d_inner"], cfg["d_state"], cfg["dt_rank"]
    uz = round_to(x @ p["w_in"])
    u, z = uz[..., :inner], uz[..., inner:]
    u = round_to(silu(causal_conv(u, p["conv_w"]) + p["conv_b"]))
    dbc = round_to(u @ p["w_x"])
    delta = jax.nn.softplus(
        round_to(dbc[..., :rank] @ p["w_dt"]) + p["dt_bias"])
    y = round_to(selective_scan(
        u, delta, -jnp.exp(p["a_log"]), dbc[..., rank:rank + n],
        dbc[..., rank + n:], p["d"]))
    return round_to(round_to(y * silu(z)) @ p["w_out"]), y


def differential_attention(q, k, v, window):
    """(A_1, A_2) V with the S x S scores explicit, a block of
    QUERY_BLOCK query rows at a time (each rematerialised in the
    backward). q [B, S, G, 2, J, d] (c then j), k [B, S, G, 2, d],
    v [B, S, G, dv] -> [B, S, G, 2, J, dv]; `window` 0 is none."""
    s, d = q.shape[1], q.shape[-1]
    keys = jnp.arange(s)

    @jax.checkpoint
    def rows(q_blk, start):
        scores = jnp.einsum("bqgcjd,bkgcd->bgcjqk", q_blk, k) * d ** -0.5
        at = (start + jnp.arange(q_blk.shape[1]))[:, None]
        seen = at >= keys[None, :]
        if window:
            seen = seen & (keys[None, :] > at - window)
        p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return jnp.einsum("bgcjqk,bkgv->bqgcjv", p, v)

    return jnp.concatenate(
        [rows(q[:, i:i + QUERY_BLOCK], i) for i in range(0, s, QUERY_BLOCK)],
        axis=1)


def combine(p, o, lam_init, cfg, round_to=_same):
    """The two maps' difference, the sub-norm and the output
    projection; o [B, S, G, 2, J, dv]."""
    b, s = o.shape[:2]
    lam = jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"])) \
        - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + lam_init
    o = round_to(o[:, :, :, 0] - lam * o[:, :, :, 1])
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + cfg["eps"]) \
        * p["subln"] * (1.0 - lam_init)
    return round_to(round_to(o).reshape(b, s, -1) @ p["w_o"] + p["b_o"])


def _queries(q, cfg):
    b, s, _ = q.shape
    groups = cfg["kv_heads"] // 2
    return q.reshape(b, s, groups, 2, cfg["heads"] // cfg["kv_heads"],
                     cfg["head_dim"])


def self_attention(p, x, cfg, window, lam_init, round_to=_same):
    """(the mixer's output, k, v): k [B, S, G, 2, d], v [B, S, G, 2 d]."""
    b, s, _ = x.shape
    h, hkv, d = cfg["heads"], cfg["kv_heads"], cfg["head_dim"]
    qkv = round_to(x @ p["w_qkv"] + p["b_qkv"])
    k = qkv[..., h * d:(h + hkv) * d].reshape(b, s, hkv // 2, 2, d)
    v = qkv[..., (h + hkv) * d:].reshape(b, s, hkv // 2, 2 * d)
    o = round_to(differential_attention(
        _queries(qkv[..., :h * d], cfg), k, v, window))
    return combine(p, o, lam_init, cfg, round_to), k, v


def cross_attention(p, x, k, v, cfg, lam_init, round_to=_same):
    q = _queries(round_to(x @ p["w_q"] + p["b_q"]), cfg)
    o = round_to(differential_attention(q, k, v, 0))
    return combine(p, o, lam_init, cfg, round_to)


def gmu(p, x, m, round_to=_same):
    return round_to(round_to(silu(round_to(x @ p["w_in"])) * m) @ p["w_out"])


def mlp(p, x, round_to=_same):
    width = p["w_down"].shape[0]
    h = round_to(x @ p["w_gate_up"])
    return round_to(round_to(silu(h[..., :width]) * h[..., width:])
                    @ p["w_down"])


def layer_params(params, i):
    prefix = f"layers.{i}."
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def _sub(p, prefix):
    return {k[len(prefix):]: v for k, v in p.items() if k.startswith(prefix)}


def decoder_layer(p, x, shared, cfg, i, round_to=_same):
    """(x, shared): `shared` holds what later layers read, "m" from the
    memory layer and "k", "v" from the full-attention layer."""
    kind = cfg["layer_kinds"][i]
    lam_init = lambda_init(cfg["published_index"][i])
    h = round_to(layer_norm(x, p["ln1.w"], p["ln1.b"], cfg["eps"]))
    shared = dict(shared)
    if kind in ("mamba", "mamba_memory"):
        y, m = mamba(_sub(p, "mamba."), h, cfg, round_to)
        if kind == "mamba_memory":
            shared["m"] = m
    elif kind in ("sliding", "full"):
        y, k, v = self_attention(
            _sub(p, "attn."), h, cfg,
            cfg["window"] if kind == "sliding" else 0, lam_init, round_to)
        if kind == "full":
            shared["k"], shared["v"] = k, v
    elif kind == "gmu":
        y = gmu(_sub(p, "gmu."), h, shared["m"], round_to)
    else:
        y = cross_attention(_sub(p, "cross."), h, shared["k"], shared["v"],
                            cfg, lam_init, round_to)
    x = round_to(x + y)
    h = round_to(layer_norm(x, p["ln2.w"], p["ln2.b"], cfg["eps"]))
    return round_to(x + mlp(_sub(p, "mlp."), h, round_to)), shared


def loss(params, ids, labels, cfg, round_to=_same):
    """Next-token cross entropy averaged over the positions. Each layer
    is rematerialised in the backward, so the reference fits beside its
    weights at the cell's sizes."""
    with jax.default_matmul_precision("highest"):
        x = params["embed_tokens"][ids]
        shared = {}
        for i in range(len(cfg["layer_kinds"])):
            layer = jax.checkpoint(
                lambda p, x, shared, i=i:
                decoder_layer(p, x, shared, cfg, i, round_to))
            x, shared = layer(layer_params(params, i), x, shared)
        x = round_to(layer_norm(x, params["final_norm.w"],
                                params["final_norm.b"], cfg["eps"]))
        logits = x @ params["embed_tokens"].T
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))


def loss_and_grads(params, ids, labels, cfg, wrt=None, round_to=_same):
    """The loss and its gradients with respect to the parameters named
    in `wrt` (all of them by default)."""
    wrt = list(params) if wrt is None else list(wrt)
    return jax.value_and_grad(
        lambda diff: loss({**params, **diff}, ids, labels, cfg, round_to))(
        {n: params[n] for n in wrt})
