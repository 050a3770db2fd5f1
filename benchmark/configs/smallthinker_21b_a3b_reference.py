"""SmallThinker-21BA3B, the plain reference: forward, loss and gradients
in `jax.numpy`, float32, `jax.default_matmul_precision("highest")`. No
kernel, no grouped product, no cache; it imports nothing from
`paddle_tpu`.

Layer equations, from the published `config.json` (`x` [B, S, 2560] the
residual stream; N = RMSNorm, eps 1e-6, weight from 1; 28 query heads
over 4 KV heads, D = 128; layer l rotates iff `rope_layout[l]` and is
windowed iff `sliding_window_layout[l]`: published layers 0, 4, 8, ..
are neither, the other three of every four are both):

    r      = x                      the ROUTER'S input: the layer's input,
                                    before the input norm, before attention
    h      = N(x; input_norm)
    q,k,v  = h W_q [2560, 3584], h W_k [2560, 512], h W_v [2560, 512]
    window layer: q, k = rotary(q, k), rotate-half over all 128 dims,
                  inv_i = 1500000^(-2i/128), positions 0..S-1;
                  query t sees keys t - 4096 < s <= t
    full layer:   NO rotary, no position of any kind; query t sees s <= t
    a      = softmax(q k^T / sqrt(128) under the mask) v
                                    query head j reads KV head j // 7
    x      = x + a W_o [3584, 2560]
    g      = N(x; post_norm)
    logits = r W_r [2560, 64]       float32
    T      = the 6 largest logits; w = softmax over the 64, renormalised
             among T (= softmax over the 6 chosen logits)
    x      = x + sum_{e in T, e held} w_e E_e(g)
    E_e(g) = (relu(g W_gate,e) * g W_up,e) W_down,e
    loss   = mean cross entropy of N(x; final_norm) W_head vs the next ids

Every expert is 768 wide and ReLU-gated ("sparse ReGLU"); there is no
shared expert and no dense layer; embedding and head are untied. Only
the experts `[expert_start, expert_start + held)` are here (`held` read
off the weights): what the absent ones would add is left out.

Departures and what the source leaves open are in the configuration's
JSON under `assumed`: the router input is taken BEFORE `input_layernorm`
(the release says "router placed before attention"; llama.cpp's graph
takes the logits from the layer input ahead of the attention norm); no
secondary experts, no auxiliary loss, no selection bias, no scaling
factor; no bias, no QK norm; init normal(0, 0.02), the embedding's normal(0,
1) (the routers read the stream un-normed). `W_gate` and `W_up`
are packed [gate | up] in one parameter, `w_gate_up` [held, 2560, 1536].

`round_to` rounds every activation that crosses from one operation to
the next (identity in the reference proper): `chip_smoke.py` uses it to
read what bf16 activations would give, the nearest precision below the
one the configuration states, which the parity limits must refuse.
"""
import jax
import jax.numpy as jnp

QUERY_BLOCK = 256    # query rows a block of the S x S attention


def _same(x):
    return x


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rotary(x, theta):
    """Rotate-half over all the dims of each head; x [B, S, H, D],
    position = index in the sequence."""
    s, d = x.shape[1], x.shape[3]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[None, :, None, :]
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + half * sin


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


def attention(p, x, cfg, rotate, windowed, round_to=_same):
    b, s, _ = x.shape
    heads, hkv, d = cfg["heads"], cfg["kv_heads"], cfg["head_dim"]
    q = round_to(x @ p["w_q"]).reshape(b, s, heads, d)
    k = round_to(x @ p["w_k"]).reshape(b, s, hkv, d)
    v = round_to(x @ p["w_v"]).reshape(b, s, hkv, d)
    if rotate:
        q = round_to(rotary(q, cfg["rope_theta"]))
        k = round_to(rotary(k, cfg["rope_theta"]))
    o = round_to(masked_attention(q, k, v,
                                  cfg["window"] if windowed else 0))
    return round_to(o.reshape(b, s, heads * d) @ p["w_o"])


def reglu_ffn(x, w_gate_up, w_down, round_to=_same):
    width = w_down.shape[0]
    h = round_to(x @ w_gate_up)
    return round_to(
        round_to(jax.nn.relu(h[..., :width]) * h[..., width:]) @ w_down)


def route(r, w_router, top_k):
    """(expert ids [.., k], weights [.., k]) from the router's input `r`:
    logits over ALL experts in float32, the k largest, softmax among
    themselves (= the softmax over all, renormalised among the k)."""
    top_l, top_i = jax.lax.top_k(r @ w_router, top_k)
    return top_i, jax.nn.softmax(top_l, -1)


def moe(p, r, g, cfg, round_to=_same):
    """The held experts' part for the experts' input `g` under the choice
    the router made from ITS input `r`. Held experts are `expert_start +
    arange(held)`; one after the other, each over EVERY token, weighted
    by what the router gave it (0 where not chosen): no sort, no gather,
    no grouped product. The experts are walked by a scan whose step is
    rematerialised in the backward, so that one expert's [tokens, 1536]
    intermediates live at a time."""
    top_i, top_w = route(r, p["w_router"], cfg["experts_per_tok"])

    @jax.checkpoint
    def one(y, expert):
        e, w_gate_up, w_down = expert
        weight = jnp.sum(
            jnp.where(top_i == cfg["expert_start"] + e, top_w, 0.0), -1)
        return y + weight[..., None] * reglu_ffn(g, w_gate_up, w_down,
                                                 round_to), None

    held = p["w_gate_up"].shape[0]
    y, _ = jax.lax.scan(one, jnp.zeros_like(g),
                        (jnp.arange(held), p["w_gate_up"], p["w_down"]))
    return round_to(y)


def layer_params(params, i):
    prefix = f"layers.{i}."
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def _sub(p, prefix):
    return {k[len(prefix):]: v for k, v in p.items() if k.startswith(prefix)}


def decoder_layer(p, x, cfg, i, round_to=_same):
    r = x
    h = round_to(rms_norm(x, p["input_norm"], cfg["eps"]))
    x = round_to(x + attention(_sub(p, "attn."), h, cfg,
                               cfg["rope_layout"][i],
                               cfg["window_layout"][i], round_to))
    g = round_to(rms_norm(x, p["post_norm"], cfg["eps"]))
    return round_to(x + moe(_sub(p, "moe."), r, g, cfg, round_to))


def loss(params, ids, labels, cfg, round_to=_same):
    """Next-token cross entropy averaged over the positions. Each layer
    is rematerialised in the backward, so the reference fits beside its
    weights at the cell's sizes."""
    with jax.default_matmul_precision("highest"):
        x = params["embed_tokens"][ids]
        for i in range(len(cfg["rope_layout"])):
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
