"""Ling-3.0-flash-VL's language model, the plain reference: forward, loss,
the gradients and the routers' bias update in `jax.numpy`, float32,
`jax.default_matmul_precision("highest")`. No kernel, no chunk, no
grouped product, no cache; it imports nothing from `paddle_tpu`.

Layer equations, from the published `config.json` (`x` [B, S, 2560], 32
heads, N = RMSNorm, eps 1e-6, weight from 1):

    x <- x + Mixer(N(x));  x <- x + FFN(N(x))

Mixer, layer i with (i + 1) % 6 != 0 (`layer_group_size` 6): Kimi delta
attention (arXiv:2510.26692), dk = dv = 128 (`num_kv_heads_for_linear_attn`
0: as many heads as attention's), no rotary:

    [q, k, v, a] = h W_qkva;  [q, k, v] = SiLU(causal_conv4([q, k, v]))
    q, k L2-normalised a head, q scaled by dk^-1/2
    beta = sigmoid(h W_b)                       one a head
    g = -5 sigmoid(exp(A_log) (a + dt_bias))    one a KEY CHANNEL, in (-5, 0)
    a head's state S in R^{dk x dv}, S_0 = 0, TOKEN BY TOKEN:
        S~ = Diag(exp(g_t)) S_{t-1};  u_t = beta_t (v_t - S~^T k_t)
        S_t = S~ + k_t u_t^T;  o_t = S_t^T q_t
    out = (N_4096(o) * sigmoid(h W_g)) W_o      the gate one a head

(`kda_lower_bound` -5 and `kda_safe_gate`: the bounded gate of the
flash-linear-attention library; `no_kda_lora`: W_a full rank [2560,
4096]; `group_norm_size` 1: one RMSNorm over all 4096 channels.)

Mixer, layer i with (i + 1) % 6 == 0: latent attention (DeepSeek-V2 §2.1)
with NO query latent (`q_lora_rank` null):

    q = h W_q                              a head [128 no-rotary | 64 rotary]
    [c_KV ; k_R] = h W_kv_a                [512 ; 64],  c_KV = N(c_KV)
    [k_n | v] = c_KV W_kv_b                a head 128 | 128
    Q_j = [q_n | rope(q_r)],  K_j = [k_n | rope(k_R)]  (the SAME k_R in
    every head);  o_j = softmax(Q_j K_j^T / sqrt(192) + causal) V_j
    out = ([o_1 .. o_32] * sigmoid(h W_g)) W_o

rope (rotate-half, `rotary_dim` 64, theta 6e6): the pair of dims (i, i +
32) of the 64 turns by position t x theta^(-2i/64), positions 0..S-1.

FFN, layers 0 and 1 (`first_k_dense_replace` 2): `E(h) = (SiLU(h W_gate)
* h W_up) W_down`, 6144 wide. Layers >= 2 (sparse): `s = sigmoid(h W_r)`
over ALL 512 experts; the experts are 8 groups of 64 in order
(`n_group`); a group scores the sum of its two largest s_e + b_e (b the
router's bias); the 4 best groups are kept (`topk_group`); T = the 8
experts of largest s_e + b_e inside them; `w_e = 2.5 s_e / sum_T s`
(`norm_topk_prob`, the UNBIASED s);

    y = sum_{e in T, e held} w_e E_e(h) + E_shared(h)

each E 768 wide. Only the experts `[expert_start, expert_start + held)`
are here: what the absent ones would add is left out. After the step,
b_e <- b_e + gamma sign(mean_e' load_e' - load_e), the loads counted over
all 512 experts from this step's T, outside the gradient.

What the source leaves open is in the configuration's JSON under
`assumed`. `W_gate` and `W_up` are packed [gate | up] in one parameter,
`w_gate_up`; KDA's four input projections are one, `w_qkva`.

Departures, for fitting: the recurrence in blocks of SCAN_BLOCK
positions, attention in blocks of QUERY_BLOCK query rows, experts
walked by a scan, each rematerialised in the backward, and each layer
too; none changes the equations.

`round_to` rounds every activation that crosses from one operation to
the next (identity in the reference proper): `chip_smoke.py` uses it to
read what bf16 activations would give, the nearest precision below the
one the configuration states, which the parity limits must refuse. Two
further controls the limits must refuse, by name in CONTROLS: the KDA
state kept in bf16 between tokens, and each head's per-channel decays
replaced by their mean (a decay a head, as Gated DeltaNet's).
"""
import jax
import jax.numpy as jnp

SCAN_BLOCK = 64      # positions a rematerialised block of the recurrence
QUERY_BLOCK = 256    # query rows a block of the S x S attention
LOGIT_ROWS = 256     # the first positions whose logits `readings` gives
CONTROLS = ("kda_state_bf16", "kda_gate_head_mean")


def _same(x):
    return x


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def silu(x):
    return x * jax.nn.sigmoid(x)


def causal_conv(x, w):
    """Depthwise: y[t, c] = sum_j w[c, j] x[t - (K-1) + j, c]; x [B, S, C],
    w [C, K]; positions before the sequence are zero."""
    k = w.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    s = x.shape[1]
    return sum(padded[:, j:j + s] * w[:, j] for j in range(k))


def kda_recurrence(q, k, v, g, beta, state_to=_same):
    """The recurrence above, one position a step. q, k [B, S, H, dk], v
    [B, S, H, dv], g [B, S, H, dk] the log decay, beta [B, S, H] -> o [B,
    S, H, dv]; ``state_to`` is applied to the state after each token.
    The scan is cut into blocks of SCAN_BLOCK positions, each
    rematerialised in the backward, so that the gradient keeps S /
    SCAN_BLOCK states and not S of them; the arithmetic is the token's,
    block or none."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]

    def token(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        state = state * jnp.exp(g_t)[..., :, None]
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t))
        state = state_to(state + k_t[..., :, None] * u[..., None, :])
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    pad = -s % SCAN_BLOCK
    xs = [jnp.moveaxis(jnp.pad(t, ((0, 0), (0, pad))
                               + ((0, 0),) * (t.ndim - 2)), 1, 0)
          for t in (q, k, v, g, beta)]
    # (padded positions come last: what they do to the state is not read)
    xs = [t.reshape((-1, SCAN_BLOCK) + t.shape[1:]) for t in xs]
    _, o = jax.lax.scan(block, jnp.zeros((b, h, dk, dv), jnp.float32), xs)
    return jnp.moveaxis(o.reshape((-1,) + o.shape[2:])[:s], 0, 1)


def _controls(cfg):
    """What ``cfg["controls"]`` (names of CONTROLS -> a flag, traced or
    not; absent = off) does to the KDA state and to its decays."""
    on = cfg.get("controls", {})
    state = on.get("kda_state_bf16", False)
    mean = on.get("kda_gate_head_mean", False)

    def state_to(x):
        return jnp.where(state, jax.lax.reduce_precision(x, 8, 7), x)

    def gate_to(g):
        return jnp.where(mean, jnp.broadcast_to(
            jnp.mean(g, -1, keepdims=True), g.shape), g)
    return state_to, gate_to


def kda(p, x, cfg, round_to=_same):
    b, s, _ = x.shape
    h, d = cfg["heads"], cfg["kda_head_dim"]
    state_to, gate_to = _controls(cfg)
    qkva = round_to(x @ p["w_qkva"])
    qkv = round_to(silu(causal_conv(qkva[..., :3 * h * d], p["conv_w"])))
    q, k, v = (qkv[..., i * h * d:(i + 1) * h * d].reshape(b, s, h, d)
               for i in range(3))
    q, k = (t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)
            for t in (q, k))
    q = q * d ** -0.5
    a = (qkva[..., 3 * h * d:] + p["dt_bias"]).reshape(b, s, h, d)
    g = gate_to(cfg["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(p["a_log"])[:, None] * a))
    beta = jax.nn.sigmoid(round_to(x @ p["w_b"]))
    o = round_to(kda_recurrence(q, k, v, g, beta, state_to))
    o = rms_norm(o.reshape(b, s, h * d), p["norm"], cfg["eps"])
    gate = jax.nn.sigmoid(round_to(x @ p["w_g"]))
    o = round_to(o.reshape(b, s, h, d) * gate[..., None])
    return round_to(o.reshape(b, s, h * d) @ p["w_o"])


def rope(x, theta):
    """Rotate-half: dims (i, i + r/2) of x [B, S, .., r] turned by
    position t x theta^(-2i/r)."""
    s, r = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angle = (jnp.arange(s, dtype=jnp.float32)[:, None] * inv).reshape(
        (1, s) + (1,) * (x.ndim - 3) + (r // 2,))
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)
    turned = jnp.concatenate([-x[..., r // 2:], x[..., :r // 2]], -1)
    return x * cos + turned * sin


def causal_attention(q, k, v):
    """softmax(q k^T d^-1/2 + causal) v with the S x S scores explicit, a
    block of QUERY_BLOCK query rows at a time (the rows padded to whole
    blocks, the padding dropped). q, k [B, S, H, D]; v [B, S, H, Dv]."""
    b, s, h, d = q.shape
    block = min(QUERY_BLOCK, s)
    n = -(-s // block)
    q = jnp.pad(q, ((0, 0), (0, n * block - s), (0, 0), (0, 0)))
    q = jnp.moveaxis(q.reshape(b, n, block, h, d), 1, 0)
    keys = jnp.arange(s)

    @jax.checkpoint
    def rows(args):
        q_blk, start = args
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k) * d ** -0.5
        seen = (start + jnp.arange(block))[:, None] >= keys[None, :]
        p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    o = jax.lax.map(rows, (q, jnp.arange(n) * block))
    return jnp.moveaxis(o, 0, 1).reshape(b, n * block, h, -1)[:, :s]


def mla(p, x, cfg, round_to=_same):
    b, s, _ = x.shape
    h, n, r = cfg["heads"], cfg["nope_dim"], cfg["rope_dim"]
    q = round_to(x @ p["w_q"]).reshape(b, s, h, n + r)
    down = round_to(x @ p["w_kv_a"])
    c_kv = round_to(rms_norm(down[..., :cfg["kv_latent"]], p["kv_a_norm"],
                             cfg["eps"]))
    k_r = down[..., cfg["kv_latent"]:]
    kv = round_to(c_kv @ p["w_kv_b"]).reshape(b, s, h, -1)
    q = round_to(jnp.concatenate(
        [q[..., :n], rope(q[..., n:], cfg["rope_theta"])], -1))
    k_r = round_to(jnp.broadcast_to(rope(k_r, cfg["rope_theta"])[:, :, None],
                                    (b, s, h, r)))
    k = jnp.concatenate([kv[..., :n], k_r], -1)
    o = round_to(causal_attention(q, k, kv[..., n:]))
    gate = jax.nn.sigmoid(round_to(x @ p["w_g"]))
    o = round_to(o * gate[..., None])
    return round_to(o.reshape(b, s, -1) @ p["w_o"])


def gated_ffn(x, w_gate_up, w_down, round_to=_same):
    width = w_down.shape[0]
    h = round_to(x @ w_gate_up)
    return round_to(round_to(silu(h[..., :width]) * h[..., width:]) @ w_down)


def route(x, w_router, bias, cfg):
    """(expert ids [.., k], weights [.., k]): sigmoid scores over ALL
    experts in float32; the groups of E / n_group experts in order, each
    scored by the sum of its two largest scores + bias; the experts of
    the topk_group best groups alone compete: the k largest scores + bias
    among them; the chosen UNBIASED scores renormalised among themselves
    and scaled."""
    scores = jax.nn.sigmoid(x @ w_router)
    choice = scores + bias
    groups = cfg["n_group"]
    by_group = choice.reshape(choice.shape[:-1] + (groups, -1))
    group_score = jnp.sum(jnp.sort(by_group, -1)[..., -2:], -1)
    rank = jnp.argsort(jnp.argsort(-group_score, -1), -1)
    kept = (rank < cfg["topk_group"])[..., None]
    choice = jnp.where(kept, by_group, -jnp.inf).reshape(choice.shape)
    _, top_i = jax.lax.top_k(choice, cfg["experts_per_tok"])
    top_s = jnp.take_along_axis(scores, top_i, -1)
    return top_i, cfg["routed_scale"] * top_s / jnp.sum(top_s, -1,
                                                        keepdims=True)


def bias_after(bias, top_i, cfg):
    """b + gamma sign(mean load - load_e), the loads of all experts."""
    e = bias.shape[0]
    load = jnp.zeros((e,), jnp.float32).at[top_i.reshape(-1)].add(1.0)
    return bias + cfg["bias_update_rate"] * jnp.sign(top_i.size / e - load)


def moe(p, x, cfg, bias, round_to=_same):
    """(y, the chosen ids). Held experts are `expert_start +
    arange(held)`, `held` read off the weights; one after the other,
    each over EVERY token, weighted by what the router gave it (0 where
    not chosen): no sort, no gather, no grouped product. The scan's step
    is rematerialised in the backward."""
    top_i, top_w = route(x, p["w_router"], bias, cfg)

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
    return round_to(y + shared), top_i


def _sub(p, prefix):
    return {k[len(prefix):]: v for k, v in p.items() if k.startswith(prefix)}


def decoder_layer(p, x, cfg, kind, bias, round_to=_same):
    """(x after the layer, the router's ids or None for a dense layer);
    ``kind`` "kda" or "mla"; ``bias`` None is a dense layer."""
    mixer = mla if kind == "mla" else kda
    h = round_to(rms_norm(x, p["input_norm"], cfg["eps"]))
    x = round_to(x + mixer(_sub(p, "attn."), h, cfg, round_to))
    h = round_to(rms_norm(x, p["post_norm"], cfg["eps"]))
    if bias is None:
        return round_to(x + gated_ffn(h, p["mlp.w_gate_up"], p["mlp.w_down"],
                                      round_to)), None
    y, top_i = moe(_sub(p, "moe."), h, cfg, bias, round_to)
    return round_to(x + y), top_i


def routers(cfg):
    """The prefixes of the layers that route, in order."""
    return [f"layers.{i}." for i, kind in enumerate(cfg["mlp_types"])
            if kind == "sparse"]


def loss(params, biases, ids, labels, cfg, round_to=_same):
    """(the cross entropy averaged over the positions, (the logits of the
    first LOGIT_ROWS positions, {bias name: bias after the step}));
    ``biases`` {`<prefix>moe.router_bias`: [E]}. Each layer is
    rematerialised in the backward, so the reference fits beside its
    weights at the cell's sizes."""
    after = {}
    with jax.default_matmul_precision("highest"):
        x = params["embed_tokens"][ids]
        for i in range(len(cfg["mlp_types"])):
            prefix = f"layers.{i}."
            name = prefix + "moe.router_bias"
            fn = jax.checkpoint(
                lambda p, x, b, kind=cfg["layer_types"][i]:
                decoder_layer(p, x, cfg, kind, b, round_to))
            x, top_i = fn(_sub(params, prefix), x, biases.get(name))
            if top_i is not None:
                after[name] = bias_after(biases[name], top_i, cfg)
        logits = round_to(rms_norm(x, params["final_norm"], cfg["eps"])) \
            @ params["lm_head"]
        logp = jax.nn.log_softmax(logits, -1)
        ce = -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))
    return ce, (logits[:, :LOGIT_ROWS], after)


def zero_biases(cfg):
    return {prefix + "moe.router_bias": jnp.zeros((cfg["num_experts"],),
                                                  jnp.float32)
            for prefix in routers(cfg)}


def readings(params, feed, cfg, names, round_to=_same, controls=None,
             biases=None):
    """What `chip_smoke.py` compares, by name: "loss", "logits" (the
    first LOGIT_ROWS positions'), `<parameter>@GRAD`, and each router's
    bias after the step (its persistable's name), from ``biases`` (zero,
    a fresh start-up's, by default); ``controls`` {a name of CONTROLS:
    flag} turns the controls on."""
    cfg = dict(cfg, controls=controls or {})
    biases = zero_biases(cfg) if biases is None else biases
    wrt = [n[:-len("@GRAD")] for n in names if n.endswith("@GRAD")]
    ids, labels = (jnp.asarray(feed[k]).reshape(feed["ids"].shape)
                   for k in ("ids", "labels"))
    (ce, (logits, after)), grads = jax.value_and_grad(
        lambda diff: loss({**params, **diff}, biases, ids, labels, cfg,
                          round_to), has_aux=True)(
        {n: params[n] for n in wrt})
    got = {"loss": ce, "logits": logits, **after,
           **{n + "@GRAD": g for n, g in grads.items()}}
    return {n: got[n] for n in names}
