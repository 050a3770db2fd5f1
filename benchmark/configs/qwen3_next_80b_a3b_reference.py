"""Qwen3-Next-80B-A3B, the plain reference: forward, loss and gradients
in `jax.numpy`, float32, `jax.default_matmul_precision("highest")`. No
kernel, no chunk, no cache; it imports nothing from `paddle_tpu`.

Layer equations, from the published `config.json` and the released
modelling code of the family (`x` [B, S, hidden], one decoder layer):

    x <- x + Mixer(N(x));  x <- x + MoE(N(x))
    N(x) = x * rsqrt(mean(x^2) + eps) * (1 + w)          (zero-centred)

Mixer, layers 1-3 of each 4 (Gated DeltaNet): `[q, k, v, z] = x W_qkvz`,
`[b, a] = x W_ba`; `[q, k, v]` through a causal depthwise convolution
(kernel 4, no bias) and SiLU; the key heads repeated to the value heads;
`q`, `k` L2-normalised a head, `q` scaled by d_k^-1/2; `beta = sigmoid(b)`,
`alpha = exp(-exp(A_log) softplus(a + dt_bias))`; a head's state
S in R^{d_k x d_v}, S_0 = 0, TOKEN BY TOKEN:

    S~ = alpha_t S_{t-1};  u_t = beta_t (v_t - S~^T k_t)
    S_t = S~ + k_t u_t^T;  o_t = S_t^T q_t

out = (RMSNorm_head(o) * gamma * SiLU(z)) W_o.
Mixer, layer 4 of each 4 (gated attention): `[q, gate] = x W_q` (a head:
q then gate), `k = x W_k`, `v = x W_v`; zero-centred RMSNorm over each
head of q and k; rotary embedding (rotate-half) on the first
`partial_rotary_factor` of a head's dims; causal softmax attention,
scale d^-1/2, each K/V head serving heads/kv_heads query heads;
out = (attn * sigmoid(gate)) W_o.
MoE: `p = softmax(x W_r)` over ALL experts, top-k, weights renormalised
to sum 1; `E_e(x) = (SiLU(x W_g,e) * x W_u,e) W_d,e`;
`y = sum_{e in topk, e held} p^_e E_e(x) + sigmoid(x w_s) E_shared(x)`.
Only the experts `[expert_start, expert_start + held)` are here: what
the absent ones would add is left out. Auxiliary loss of a layer:
`experts * sum_e (assignments_e / tokens) * mean_t p_{t,e}`.

Departures from the release, each also in the configuration's JSON:
`W_qkvz` and `W_ba` are packed `[q | k | v | z]` and `[b | a]` (the
release interleaves them a key-head group; with random weights the two
are one distribution); the auxiliary loss is taken a layer and averaged
over the layers (the release concatenates the layers' routers first);
no multi-token-prediction layer.

`round_to` rounds every activation that crosses from one operation to
the next (identity in the reference proper): `chip_smoke.py` uses it to
read what bf16 activations would give, the nearest precision below the
one the configuration states, which the parity limits must refuse.
"""
import jax
import jax.numpy as jnp

SCAN_BLOCK = 64      # positions a rematerialised block of the recurrence
QUERY_BLOCK = 512    # query rows a block of the S x S attention


def _same(x):
    return x


def rms_norm(x, w, eps, zero_centered):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return y * (1.0 + w if zero_centered else w)


def silu(x):
    return x * jax.nn.sigmoid(x)


def causal_conv(x, w):
    """Depthwise: y[t, c] = sum_j w[c, j] x[t - (K-1) + j, c]; x [B, S, C],
    w [C, K]; positions before the sequence are zero."""
    k = w.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    s = x.shape[1]
    return sum(padded[:, j:j + s] * w[:, j] for j in range(k))


def delta_rule(q, k, v, alpha, beta):
    """The recurrence above, one position a step. q, k [B, S, H, dk],
    v [B, S, H, dv], alpha, beta [B, S, H] -> o [B, S, H, dv]. The scan
    is cut into blocks of SCAN_BLOCK positions, each rematerialised in
    the backward, so that the gradient keeps S / SCAN_BLOCK states and
    not S of them; the arithmetic is the token's, block or none."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]

    def token(state, xs):
        q_t, k_t, v_t, a_t, b_t = xs
        state = state * a_t[..., None, None]
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t))
        state = state + k_t[..., :, None] * u[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    pad = -s % SCAN_BLOCK
    xs = [jnp.moveaxis(jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2)),
                       1, 0)
          for t in (q, k, v, alpha, beta)]
    # (padded positions come last: what they do to the state is not read)
    xs = [t.reshape((-1, SCAN_BLOCK) + t.shape[1:]) for t in xs]
    _, o = jax.lax.scan(block, jnp.zeros((b, h, dk, dv), jnp.float32), xs)
    return jnp.moveaxis(o.reshape((-1,) + o.shape[2:])[:s], 0, 1)


def gated_delta_net(p, x, cfg, round_to=_same):
    b, s, _ = x.shape
    hk, hv = cfg["linear_key_heads"], cfg["linear_value_heads"]
    dk, dv = cfg["linear_key_dim"], cfg["linear_value_dim"]
    qkvz = round_to(x @ p["w_qkvz"])
    ba = round_to(x @ p["w_ba"])
    qkv, z = qkvz[..., :2 * hk * dk + hv * dv], qkvz[..., 2 * hk * dk + hv * dv:]
    qkv = round_to(silu(causal_conv(qkv, p["conv_w"])))
    q = qkv[..., :hk * dk].reshape(b, s, hk, dk)
    k = qkv[..., hk * dk:2 * hk * dk].reshape(b, s, hk, dk)
    v = qkv[..., 2 * hk * dk:].reshape(b, s, hv, dv)
    q, k = (jnp.repeat(t, hv // hk, axis=2) for t in (q, k))
    q, k = (t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)
            for t in (q, k))
    q = q * dk ** -0.5
    beta = jax.nn.sigmoid(ba[..., :hv])
    alpha = jnp.exp(-jnp.exp(p["a_log"])
                    * jax.nn.softplus(ba[..., hv:] + p["dt_bias"]))
    o = round_to(delta_rule(q, k, v, alpha, beta))
    o = rms_norm(o, p["norm"], cfg["eps"], False) \
        * silu(z.reshape(b, s, hv, dv))
    return round_to(round_to(o).reshape(b, s, hv * dv) @ p["w_o"])


def rotary(x, theta, rotary_dim):
    """Rotate-half over the first `rotary_dim` dims of each head; x
    [B, S, H, D], position = index in the sequence."""
    s = x.shape[1]
    inv = theta ** (-jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                    / rotary_dim)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[None, :, None, :]
    r, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    half = rotary_dim // 2
    rotated = jnp.concatenate([-r[..., half:], r[..., :half]], -1)
    return jnp.concatenate([r * cos + rotated * sin, rest], -1)


def causal_attention(q, k, v):
    """softmax(q k^T d^-1/2, causal) v with the S x S scores explicit, a
    block of QUERY_BLOCK query rows at a time (each rematerialised in
    the backward). q [B, S, H, D]; k, v [B, S, Hkv, D]."""
    b, s, h, d = q.shape
    k, v = (jnp.repeat(t, h // k.shape[2], axis=2) for t in (k, v))
    keys = jnp.arange(s)

    @jax.checkpoint
    def rows(q_blk, start):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k) * d ** -0.5
        seen = (start + jnp.arange(q_blk.shape[1]))[:, None] >= keys[None, :]
        p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    return jnp.concatenate(
        [rows(q[:, i:i + QUERY_BLOCK], i) for i in range(0, s, QUERY_BLOCK)],
        axis=1)


def gated_attention(p, x, cfg, round_to=_same):
    b, s, _ = x.shape
    h, hkv, d = cfg["heads"], cfg["kv_heads"], cfg["head_dim"]
    qg = round_to(x @ p["w_q"]).reshape(b, s, h, 2, d)
    q, gate = qg[..., 0, :], qg[..., 1, :]
    k = round_to(x @ p["w_k"]).reshape(b, s, hkv, d)
    v = round_to(x @ p["w_v"]).reshape(b, s, hkv, d)
    q = rms_norm(q, p["q_norm"], cfg["eps"], True)
    k = rms_norm(k, p["k_norm"], cfg["eps"], True)
    rotary_dim = int(d * cfg["partial_rotary_factor"])
    q = round_to(rotary(q, cfg["rope_theta"], rotary_dim))
    k = round_to(rotary(k, cfg["rope_theta"], rotary_dim))
    o = round_to(causal_attention(q, k, v)) * jax.nn.sigmoid(gate)
    return round_to(round_to(o).reshape(b, s, h * d) @ p["w_o"])


def route(x, w_router, top_k):
    """(expert ids [.., k], renormalised weights [.., k], the layer's
    auxiliary loss): softmax over ALL experts, in float32."""
    probs = jax.nn.softmax(x @ w_router, -1)
    top_p, top_i = jax.lax.top_k(probs, top_k)
    top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    experts = w_router.shape[1]
    flat_i = top_i.reshape(-1, top_k)
    share = jnp.zeros(experts).at[flat_i.reshape(-1)].add(1.0) \
        / flat_i.shape[0]
    aux = experts * jnp.sum(
        jax.lax.stop_gradient(share)
        * jnp.mean(probs.reshape(-1, experts), 0))
    return top_i, top_p, aux


def expert(x, w_gate_up, w_down, round_to=_same):
    width = w_down.shape[0]
    h = round_to(x @ w_gate_up)
    return round_to(round_to(silu(h[..., :width]) * h[..., width:]) @ w_down)


def moe(p, x, cfg, round_to=_same):
    """(y, aux). Held experts are `expert_start + arange(held)`, `held`
    read off the weights; a Python loop over them, each over every
    token, weighted by what the router gave it (0 where not chosen)."""
    top_i, top_p, aux = route(x, p["w_router"], cfg["experts_per_tok"])
    y = jnp.zeros_like(x)
    for e in range(p["w_gate_up"].shape[0]):
        weight = jnp.sum(
            jnp.where(top_i == cfg["expert_start"] + e, top_p, 0.0), -1)
        y = y + weight[..., None] * expert(x, p["w_gate_up"][e],
                                           p["w_down"][e], round_to)
    shared = expert(x, jnp.concatenate([p["shared_w_gate"],
                                        p["shared_w_up"]], 1),
                    p["shared_w_down"], round_to)
    return round_to(y + jax.nn.sigmoid(x @ p["shared_gate"]) * shared), aux


def layer_params(params, i):
    prefix = f"layers.{i}."
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def is_full_attention(cfg, i):
    return (i + 1) % cfg["full_attention_interval"] == 0


def decoder_layer(p, x, cfg, full_attention, round_to=_same):
    mixer = gated_attention if full_attention else gated_delta_net
    sub = {k.split(".", 1)[1]: v for k, v in p.items()
           if k.startswith("attn." if full_attention else "gdn.")}
    x = round_to(x + mixer(
        sub, round_to(rms_norm(x, p["input_norm"], cfg["eps"], True)), cfg,
        round_to))
    sub = {k.split(".", 1)[1]: v for k, v in p.items()
           if k.startswith("moe.")}
    y, aux = moe(sub, round_to(rms_norm(x, p["post_norm"], cfg["eps"], True)),
                 cfg, round_to)
    return round_to(x + y), aux


def losses(params, ids, labels, cfg, round_to=_same):
    """(cross entropy, auxiliary loss): next-token cross entropy averaged
    over the positions, and the layers' auxiliary losses averaged. Each
    decoder layer is rematerialised in the backward, so the reference
    fits beside its weights at the cell's sizes."""
    with jax.default_matmul_precision("highest"):
        x = params["embed_tokens"][ids]
        aux = 0.0
        for i in range(cfg["layers"]):
            layer = jax.checkpoint(
                lambda p, x, full=is_full_attention(cfg, i):
                decoder_layer(p, x, cfg, full, round_to))
            x, a = layer(layer_params(params, i), x)
            aux = aux + a / cfg["layers"]
        x = round_to(rms_norm(x, params["final_norm"], cfg["eps"], True))
        logits = x @ params["lm_head"]
        logp = jax.nn.log_softmax(logits, -1)
        ce = -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))
    return ce, aux


def loss_and_grads(params, ids, labels, cfg, wrt=None, round_to=_same):
    """The fetched loss (cross entropy alone) and the gradients of the
    trained loss, cross entropy + aux_coef x auxiliary, with respect to
    the parameters named in `wrt` (all of them by default)."""
    wrt = list(params) if wrt is None else list(wrt)

    def trained(diff):
        ce, aux = losses({**params, **diff}, ids, labels, cfg, round_to)
        return ce + cfg["aux_coef"] * aux, ce

    (_, ce), grads = jax.value_and_grad(trained, has_aux=True)(
        {n: params[n] for n in wrt})
    return ce, grads
