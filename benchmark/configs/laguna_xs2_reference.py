"""Laguna-XS.2, the plain reference: forward, loss and gradients in
`jax.numpy`, float32, `jax.default_matmul_precision("highest")`. No
kernel, no grouped product, no cache; it imports nothing from
`paddle_tpu`.

Layer equations, from the published `config.json` (`x` [B, S, hidden],
layer l of kind `layer_types[l]` with `H_l = heads_per_layer[l]` query
heads, 8 KV heads, D = 128; N = RMSNorm, eps 1e-6, weight from 1):

    x <- x + Attn_l(N(x));  x <- x + FFN_l(N(x));  logits = N(x) W_head

Attention: `q = h W_q`, `k = h W_k`, `v = h W_v`, `g = sigmoid(h W_g)`
([hidden, H_l]: one gate a token and head); no bias, no QK norm. Rotary,
rotate-half, positions 0..S-1. Window layer: all 128 dims, `inv_i =
10000^(-2i/128)`. Full layer: the first r = 64 dims (the other 64 pass
through), YaRN as `transformers` computes it:

    pos_i = 500000^(2i/r), i < r/2
    c(n) = r ln(4096 / (2 pi n)) / (2 ln 500000)
    low = max(floor(c(64)), 0) = 5;  high = min(ceil(c(1)), r - 1) = 16
    ramp_i = clip((i - low) / (high - low), 0, 1)
    inv_i = (1 - ramp_i) / pos_i + ramp_i / (64 pos_i)

and cos, sin multiplied by 1.4158883083359672 (= 0.1 ln 64 + 1).
`o = softmax(q k^T / sqrt(128) + mask) v` a head, query head j reading
KV head j // (H_l / 8); the mask is causal, and on a window layer key s
is visible to query t iff t - 512 < s <= t. `Attn = (g *_head o) W_o`.

FFN, layer 0 (dense): `E(h) = (SiLU(h W_gate) * h W_up) W_down`, 8192
wide. Layers >= 1 (sparse): `s = sigmoid(h W_r)` over ALL 256 experts;
T = the 8 largest s_e; `w_e = 2.5 s_e / sum_T s`;

    y = sum_{e in T, e held} w_e E_e(h) + E_shared(h)

each E 512 wide, the weight on the OUTPUT. Only the experts
`[expert_start, expert_start + held)` are here: what the absent ones
would add is left out. Loss: mean cross entropy over the positions.

What the source leaves open is in the configuration's JSON under
`assumed`: the gate's form (per head), sigmoid scoring with the chosen
eight renormalised, no selection bias, no expert groups, no auxiliary
loss, init normal(0, 0.02). `W_gate` and `W_up` are packed [gate | up]
in one parameter, `w_gate_up`.

`round_to` rounds every activation that crosses from one operation to
the next (identity in the reference proper): `chip_smoke.py` uses it to
read what bf16 activations would give, the nearest precision below the
one the configuration states, which the parity limits must refuse.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256    # query rows a block of the S x S attention


def _same(x):
    return x


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def silu(x):
    return x * jax.nn.sigmoid(x)


def yarn_range(r, theta, original, beta_fast, beta_slow):
    """(low, high), whole dims: see the equations above."""
    def c(n):
        return r * math.log(original / (2 * math.pi * n)) \
            / (2 * math.log(theta))
    return max(math.floor(c(beta_fast)), 0), min(math.ceil(c(beta_slow)),
                                                 r - 1)


def inv_freq(rope):
    """The rotary_dim / 2 inverse frequencies of a layer kind's rotary
    parameters, float32: plain, or YaRN's blend (made in float64 on the
    host and rounded once)."""
    r, theta = rope["rotary_dim"], rope["theta"]
    yarn = rope.get("yarn")
    if not yarn:
        return theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    i = np.arange(r // 2, dtype=np.float64)
    pos = theta ** (2 * i / r)
    low, high = yarn_range(r, theta, yarn["original_max_position"],
                           yarn["beta_fast"], yarn["beta_slow"])
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return jnp.asarray((1.0 - ramp) / pos + ramp / (yarn["factor"] * pos),
                       jnp.float32)


def rotary(x, rope):
    """Rotate-half over the first `rotary_dim` dims of each head; x
    [B, S, H, D], position = index in the sequence."""
    s, r = x.shape[1], rope["rotary_dim"]
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq(rope)[None]
    scale = rope.get("cos_sin_scale", 1.0)
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[None, :, None, :] * scale
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[None, :, None, :] * scale
    rot, rest = x[..., :r], x[..., r:]
    half = jnp.concatenate([-rot[..., r // 2:], rot[..., :r // 2]], -1)
    return jnp.concatenate([rot * cos + half * sin, rest], -1)


def masked_attention(q, k, v, window):
    """softmax(q k^T d^-1/2 + mask) v with the S x S scores explicit, a
    block of QUERY_BLOCK query rows at a time (each rematerialised in
    the backward; the rows padded to whole blocks, the padding dropped).
    q [B, S, H, D]; k, v [B, S, Hkv, D]; `window` 0 is none."""
    b, s, h, d = q.shape
    hkv = k.shape[2]
    block = min(QUERY_BLOCK, s)
    n = -(-s // block)
    q = jnp.pad(q, ((0, 0), (0, n * block - s), (0, 0), (0, 0)))
    # head j reads KV head j // group
    q = jnp.moveaxis(q.reshape(b, n, block, hkv, h // hkv, d), 1, 0)
    keys = jnp.arange(s)

    @jax.checkpoint
    def rows(args):
        q_blk, start = args
        scores = jnp.einsum("bqgjd,bkgd->bgjqk", q_blk, k) * d ** -0.5
        at = (start + jnp.arange(block))[:, None]
        seen = at >= keys[None, :]
        if window:
            seen = seen & (keys[None, :] > at - window)
        p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return jnp.einsum("bgjqk,bkgd->bqgjd", p, v)

    o = jax.lax.map(rows, (q, jnp.arange(n) * block))
    return jnp.moveaxis(o, 0, 1).reshape(b, n * block, h, d)[:, :s]


def gated_attention(p, x, cfg, heads, kind, round_to=_same):
    b, s, _ = x.shape
    hkv, d = cfg["kv_heads"], cfg["head_dim"]
    rope = cfg["rope"][kind]
    q = round_to(x @ p["w_q"]).reshape(b, s, heads, d)
    k = round_to(x @ p["w_k"]).reshape(b, s, hkv, d)
    v = round_to(x @ p["w_v"]).reshape(b, s, hkv, d)
    gate = jax.nn.sigmoid(round_to(x @ p["w_g"]))
    q, k = round_to(rotary(q, rope)), round_to(rotary(k, rope))
    o = round_to(masked_attention(
        q, k, v, cfg["window"] if kind == "sliding" else 0))
    o = round_to(o * gate[..., None])
    return round_to(o.reshape(b, s, heads * d) @ p["w_o"])


def gated_ffn(x, w_gate_up, w_down, round_to=_same):
    width = w_down.shape[0]
    h = round_to(x @ w_gate_up)
    return round_to(round_to(silu(h[..., :width]) * h[..., width:]) @ w_down)


def route(x, w_router, top_k, scale):
    """(expert ids [.., k], weights [.., k]): sigmoid scores over ALL
    experts in float32, the k largest, renormalised among themselves and
    scaled."""
    scores = jax.nn.sigmoid(x @ w_router)
    top_s, top_i = jax.lax.top_k(scores, top_k)
    return top_i, scale * top_s / jnp.sum(top_s, -1, keepdims=True)


def moe(p, x, cfg, round_to=_same):
    """Held experts are `expert_start + arange(held)`, `held` read off
    the weights; one after the other, each over EVERY token, weighted by
    what the router gave it (0 where not chosen): no sort, no gather, no
    grouped product. The experts are walked by a scan whose step is
    rematerialised in the backward, so that one expert's [tokens, 1024]
    intermediates live at a time."""
    top_i, top_w = route(x, p["w_router"], cfg["experts_per_tok"],
                         cfg["routed_scale"])

    @jax.checkpoint
    def one(y, expert):
        e, w_gate_up, w_down = expert
        weight = jnp.sum(
            jnp.where(top_i == cfg["expert_start"] + e, top_w, 0.0), -1)
        return y + weight[..., None] * gated_ffn(x, w_gate_up, w_down,
                                                 round_to), None

    held = p["w_gate_up"].shape[0]
    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (jnp.arange(held), p["w_gate_up"], p["w_down"]))
    shared = gated_ffn(x, p["shared_w_gate_up"], p["shared_w_down"], round_to)
    return round_to(y + shared)


def layer_params(params, i):
    prefix = f"layers.{i}."
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def _sub(p, prefix):
    return {k[len(prefix):]: v for k, v in p.items() if k.startswith(prefix)}


def decoder_layer(p, x, cfg, i, round_to=_same):
    h = round_to(rms_norm(x, p["input_norm"], cfg["eps"]))
    x = round_to(x + gated_attention(
        _sub(p, "attn."), h, cfg, cfg["heads_per_layer"][i],
        cfg["layer_types"][i], round_to))
    h = round_to(rms_norm(x, p["post_norm"], cfg["eps"]))
    if cfg["mlp_types"][i] == "dense":
        y = gated_ffn(h, p["mlp.w_gate_up"], p["mlp.w_down"], round_to)
    else:
        y = moe(_sub(p, "moe."), h, cfg, round_to)
    return round_to(x + y)


def loss(params, ids, labels, cfg, round_to=_same):
    """Next-token cross entropy averaged over the positions. Each layer
    is rematerialised in the backward, so the reference fits beside its
    weights at the cell's sizes."""
    with jax.default_matmul_precision("highest"):
        x = params["embed_tokens"][ids]
        for i in range(len(cfg["layer_types"])):
            layer = jax.checkpoint(
                lambda p, x, i=i: decoder_layer(p, x, cfg, i, round_to))
            x = layer(layer_params(params, i), x)
        x = round_to(rms_norm(x, params["final_norm"], cfg["eps"]))
        logits = x @ params["lm_head"]
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))


def loss_and_grads(params, ids, labels, cfg, wrt=None, round_to=_same):
    """The loss and its gradients with respect to the parameters named
    in `wrt` (all of them by default)."""
    wrt = list(params) if wrt is None else list(wrt)
    return jax.value_and_grad(
        lambda diff: loss({**params, **diff}, ids, labels, cfg, round_to))(
        {n: params[n] for n in wrt})
