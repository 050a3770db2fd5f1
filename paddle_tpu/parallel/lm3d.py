"""Composed 3D-parallel GPT-style LM lane — dp × pp × sp (+ MoE expert
parallel) on ONE mesh (ROADMAP item 4).

Every parallelism axis this package ships is composed into a single
compiled train step on the `mesh3d` ("dp", "pp", "sp") mesh:

  * pipeline — the decoder stack is split into ``pp`` stages scheduled
    by `parallel.pipeline.gpipe` (compiled GPipe tick loop, ppermute
    stage handoff, reverse pipeline via the vjp transpose);
  * sequence — attention inside every stage is
    `ring_attention_local` over the "sp" axis (K/V blocks rotate over
    the manual-axis ppermute — the same body the standalone
    `ring_attention` shard_maps, here NESTED inside the gpipe stage);
  * data — the microbatch's batch dim shards over "dp"; gradient
    all-reduces fall out of the shard_map transpose;
  * experts — the MoE variant dispatches tokens over "dp" reused as the
    expert-parallel axis (`moe_ffn_local` all_to_all, per-expert
    capacity with COUNTED token drops surfaced through gpipe's
    ``with_aux`` schedule-total).

The numeric-fault plane composes across all axes at once: ONE fused
health scalar (`fluid.ir.fused_health` over every grad leaf + the
per-microbatch losses) guards the WHOLE microbatch schedule per step —
not per stage — with the PR 5 skip-mode discard (``where(health, new,
old)`` over every param) and, under ``amp=True``, the dynamic
loss-scaling transition (`fluid.executor._amp_scale_update`) consuming
the same scalar. The rng-fold contract holds across axes too: step keys
fold by GLOBAL step index, and every dropout site folds by (stage,
layer, microbatch) — `gpipe(pass_micro=True)` hands the stage body the
microbatch index its tick computes — so the single-device oracle
(`make_oracle_step`: same params, same folds, python loop over stages
and microbatches, degenerate n=1 collectives) draws identical masks.

Parity contract (tests/test_parallel3d.py): per-step losses of the
composed lane match the oracle within documented fp32 tolerance (the
dp/sp partial-sum orders differ from the oracle's single-device
reductions by last-ulp rounding; a pp-only composition is observed
bit-identical).
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from .mesh import mesh3d
from .moe import expert_capacity, moe_ffn_local
from .pipeline import gpipe
from .ring_attention import ring_attention_local

__all__ = ["LMConfig", "mesh3d", "init_params",
           "place_params", "place_window", "init_amp_state",
           "sample_window", "make_train_step", "make_window_step",
           "make_oracle_step", "make_oracle_window"]

# dynamic loss-scaling hyperparams (PR 5 defaults, reference
# update_loss_scaling contract — fluid/executor._amp_scale_update)
AMP_CFG = {"incr_every_n_steps": 8, "decr_every_n_nan_or_inf": 1,
           "incr_ratio": 2.0, "decr_ratio": 0.5}
INIT_LOSS_SCALE = 2.0 ** 10


class LMConfig:
    """Shapes + parallel degrees of the lane. ``n_experts == 0`` is the
    dense-FFN variant; ``n_experts > 0`` shards experts over "dp"."""

    def __init__(self, vocab=64, d_model=32, n_heads=4, d_ff=None,
                 seq_len=32, layers_per_stage=1, dp=1, pp=1, sp=1,
                 n_experts=0, capacity_factor=4.0, dropout=0.0,
                 lr=0.1, n_micro=2, batch=4, amp=False, seed=0):
        self.vocab, self.d_model, self.n_heads = vocab, d_model, n_heads
        self.d_ff = d_ff if d_ff is not None else 4 * d_model
        self.seq_len, self.layers_per_stage = seq_len, layers_per_stage
        self.dp, self.pp, self.sp = dp, pp, sp
        self.n_experts = n_experts
        self.capacity_factor = capacity_factor
        self.dropout, self.lr = dropout, lr
        self.n_micro, self.batch = n_micro, batch
        self.amp, self.seed = amp, seed
        if d_model % n_heads:
            raise ValueError(f"d_model {d_model} % n_heads {n_heads}")
        if seq_len % sp:
            raise ValueError(f"seq_len {seq_len} not divisible by "
                             f"sp={sp}")
        if batch % n_micro:
            raise ValueError(f"batch {batch} % n_micro {n_micro}")
        if (batch // n_micro) % dp:
            raise ValueError(f"microbatch {batch // n_micro} not "
                             f"divisible by dp={dp}")
        if n_experts and n_experts % dp:
            raise ValueError(f"experts {n_experts} not divisible by "
                             f"the expert-parallel axis dp={dp}")

    @property
    def n_layers(self):
        return self.pp * self.layers_per_stage

    @property
    def n_devices(self):
        return self.dp * self.pp * self.sp

    def mesh(self, devices=None):
        return mesh3d(self.dp, self.pp, self.sp, devices=devices)

    @property
    def compute_dtype(self):
        return jnp.bfloat16 if self.amp else jnp.float32


# ----------------------------------------------------------------- params
def init_params(cfg: LMConfig) -> Dict[str, Any]:
    """Deterministic fp32 params. Stage leaves stack [pp, Lps, ...]."""
    r = np.random.RandomState(cfg.seed)
    D, F, V, E = cfg.d_model, cfg.d_ff, cfg.vocab, cfg.n_experts
    pp, L = cfg.pp, cfg.layers_per_stage

    def n(*shape, scale=0.02):
        return jnp.asarray(r.normal(size=shape) * scale, jnp.float32)

    st = {
        "ln1_g": jnp.ones((pp, L, D), jnp.float32),
        "ln1_b": jnp.zeros((pp, L, D), jnp.float32),
        "wq": n(pp, L, D, D), "wk": n(pp, L, D, D),
        "wv": n(pp, L, D, D),
        "wo": n(pp, L, D, D, scale=0.02 / np.sqrt(2 * cfg.n_layers)),
        "ln2_g": jnp.ones((pp, L, D), jnp.float32),
        "ln2_b": jnp.zeros((pp, L, D), jnp.float32),
    }
    if E:
        st.update({
            "gate": n(pp, L, D, E),
            "w1": n(pp, L, E, D, F), "b1": jnp.zeros((pp, L, E, F),
                                                     jnp.float32),
            "w2": n(pp, L, E, F, D,
                    scale=0.02 / np.sqrt(2 * cfg.n_layers)),
            "b2": jnp.zeros((pp, L, E, D), jnp.float32),
        })
    else:
        st.update({
            "w1": n(pp, L, D, F), "b1": jnp.zeros((pp, L, F),
                                                  jnp.float32),
            "w2": n(pp, L, F, D,
                    scale=0.02 / np.sqrt(2 * cfg.n_layers)),
            "b2": jnp.zeros((pp, L, D), jnp.float32),
        })
    return {
        "emb": n(V, D), "pos": n(cfg.seq_len, D),
        "ln_f_g": jnp.ones((D,), jnp.float32),
        "ln_f_b": jnp.zeros((D,), jnp.float32),
        "head": n(D, V),
        "stages": st,
    }


def _stage_specs(cfg: LMConfig, stages: Dict[str, Any]):
    """PartitionSpecs of the stacked stage params on the 3D mesh: every
    leaf leads with "pp"; MoE expert-count dims additionally shard over
    "dp" (the expert-parallel axis)."""
    expert_leaves = {"w1", "b1", "w2", "b2"} if cfg.n_experts else set()

    def spec(name, x):
        if name in expert_leaves:
            # [pp, Lps, E, ...]: E over the expert axis. Specs stay in
            # their SHORT form (no trailing Nones): XLA normalizes
            # output shardings that way, and NamedSharding __eq__ —
            # which the jit cache keys on — treats P("pp") and
            # P("pp", None, None) as DIFFERENT, so a long-form
            # pre-placement would retrace on the second dispatch.
            return P("pp", None, "dp")
        return P("pp")
    return {k: spec(k, v) for k, v in stages.items()}


# ------------------------------------------------------------------ model
def _ln(x, g, b, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * g + b).astype(x.dtype)


def _dropout(cfg: LMConfig, a, key, sidx, lidx, micro, site):
    """Inverted dropout whose mask folds by (stage, layer, site) and
    microbatch — the rng-fold contract that lets the oracle (python
    stage/micro indices) mirror the pipelined lane (traced indices)
    mask-for-mask. No-op at rate 0."""
    if cfg.dropout <= 0.0 or key is None:
        return a
    k = jax.random.fold_in(
        key, (sidx * cfg.layers_per_stage + lidx) * 2 + site)
    k = jax.random.fold_in(k, micro)
    keep = jax.random.bernoulli(k, 1.0 - cfg.dropout, a.shape)
    return jnp.where(keep, a / (1.0 - cfg.dropout),
                     jnp.zeros((), a.dtype)).astype(a.dtype)


def _layer(cfg: LMConfig, p, x, micro, key, sidx, lidx, sp_n):
    """One pre-LN decoder block on one device's activation shard
    x [mb_loc, S_loc, D]. ``sp_n`` is the sequence-axis degree (1 ⇒
    the degenerate no-collective oracle path; the expert-parallel
    degree is inferred from the local expert slice width).
    Returns (x, dropped) — dropped = this shard's MoE capacity
    overflow count (0 for the dense FFN)."""
    dt = x.dtype
    mb, S_l, D = x.shape
    H = cfg.n_heads
    Dh = D // H

    h = _ln(x, p["ln1_g"], p["ln1_b"])

    def heads(w):
        y = h @ w.astype(dt)
        return y.reshape(mb, S_l, H, Dh).transpose(0, 2, 1, 3)

    a = ring_attention_local(heads(p["wq"]), heads(p["wk"]),
                             heads(p["wv"]), causal=True, axis="sp",
                             n=sp_n)
    a = a.transpose(0, 2, 1, 3).reshape(mb, S_l, D) @ p["wo"].astype(dt)
    x = x + _dropout(cfg, a, key, sidx, lidx, micro, site=0)

    h = _ln(x, p["ln2_g"], p["ln2_b"])
    if cfg.n_experts:
        capacity = expert_capacity(mb * S_l, cfg.n_experts,
                                   cfg.capacity_factor)
        y, dropped = moe_ffn_local(
            h.reshape(-1, D), p["gate"], p["w1"], p["b1"], p["w2"],
            p["b2"], axis="dp", capacity=capacity)
        y = y.reshape(mb, S_l, D)
    else:
        y = jax.nn.gelu(h @ p["w1"].astype(dt)
                        + p["b1"].astype(dt)) @ p["w2"].astype(dt) \
            + p["b2"].astype(dt)
        dropped = jnp.zeros((), jnp.int32)
    x = x + _dropout(cfg, y, key, sidx, lidx, micro, site=1)
    return x, dropped


def _stage_body(cfg: LMConfig, p_stage, x, micro, key, sidx, sp_n):
    """All of one pipeline stage's layers. p_stage leaves [Lps, ...]."""
    dropped = jnp.zeros((), jnp.int32)
    for l in range(cfg.layers_per_stage):
        pl = {k: v[l] for k, v in p_stage.items()}
        x, d = _layer(cfg, pl, x, micro, key, sidx, l, sp_n)
        dropped = dropped + d
    return x, dropped


def _embed(cfg: LMConfig, params, xb):
    x = params["emb"][xb] + params["pos"][None, None]
    return x.astype(cfg.compute_dtype)


def _head_loss(cfg: LMConfig, params, ys, yb):
    """Final LN + LM head + per-microbatch mean xent. ys
    [n_micro, mb, S, D]; yb int targets [n_micro, mb, S]. Returns
    (mean loss, per-microbatch losses [n_micro]) in fp32."""
    h = _ln(ys.astype(jnp.float32), params["ln_f_g"], params["ln_f_b"])
    logits = h @ params["head"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, yb[..., None], axis=-1)[..., 0]
    losses = jnp.mean(nll, axis=(1, 2))
    return jnp.mean(losses), losses


def _forward_composed(cfg: LMConfig, params, xb, yb, key, mesh):
    x = _embed(cfg, params, xb)

    def stage_fn(p, xx, micro):
        sidx = lax.axis_index("pp")
        return _stage_body(cfg, p, xx, micro, key, sidx, cfg.sp)

    ys, dropped = gpipe(
        stage_fn, params["stages"], x, mesh=mesh,
        param_specs=_stage_specs(cfg, params["stages"]),
        xs_spec=P(None, "dp", "sp", None), with_aux=True,
        pass_micro=True)
    loss, losses = _head_loss(cfg, params, ys, yb)
    return loss, losses, dropped


def _forward_oracle(cfg: LMConfig, params, xb, yb, key):
    """Single-device reference: same params/folds, python loops over
    stages and microbatches, degenerate (n=1) collectives."""
    x = _embed(cfg, params, xb)
    outs, dropped = [], jnp.zeros((), jnp.int32)
    for m in range(cfg.n_micro):
        xi = x[m]
        for s in range(cfg.pp):
            p_s = {k: v[s] for k, v in params["stages"].items()}
            xi, d = _stage_body(cfg, p_s, xi, m, key, s, 1)
            dropped = dropped + d
        outs.append(xi)
    ys = jnp.stack(outs)
    loss, losses = _head_loss(cfg, params, ys, yb)
    return loss, losses, dropped


# ------------------------------------------------------------- train step
def init_amp_state(cfg: LMConfig, mesh=None):
    """Fresh dynamic loss-scaling state; pass ``mesh`` to pre-place it
    replicated (the steady-state sharding — same retrace rationale as
    `place_params`)."""
    if not cfg.amp:
        return {}
    st = {"scale": jnp.full((1,), INIT_LOSS_SCALE, jnp.float32),
          "good": jnp.zeros((1,), jnp.int32),
          "bad": jnp.zeros((1,), jnp.int32)}
    if mesh is not None:
        st = {k: jax.device_put(v, NamedSharding(mesh, P()))
              for k, v in st.items()}
    return st


def _make_step(cfg: LMConfig, forward, guard: bool = True):
    """The shared train-step epilogue around either forward — ONE
    implementation of the PR 5 composition for the lane and its oracle
    (so the parity the tests pin cannot drift): scaled loss → grads →
    unscale → ONE fused health scalar over every grad leaf + the
    per-microbatch losses → SGD update → skip-mode discard → AMP scale
    transition."""
    from ..fluid.ir import fused_health

    def loss_fn(params, xb, yb, key, scale):
        loss, losses, dropped = forward(params, xb, yb, key)
        return loss * scale.astype(loss.dtype), (losses, dropped)

    def step(params, amp_state, xb, yb, key):
        scale = (amp_state["scale"][0] if cfg.amp
                 else jnp.float32(1.0))
        grads, (losses, dropped) = jax.grad(
            loss_fn, has_aux=True)(params, xb, yb, key, scale)
        if cfg.amp:
            inv = (1.0 / scale).astype(jnp.float32)
            grads = jax.tree_util.tree_map(
                lambda g: g.astype(jnp.float32) * inv, grads)
        health = fused_health(
            jax.tree_util.tree_leaves(grads) + [losses])
        new_params = jax.tree_util.tree_map(
            lambda pv, g: pv - cfg.lr * g.astype(pv.dtype), params,
            grads)
        if guard:
            new_params = jax.tree_util.tree_map(
                lambda nv, ov: jnp.where(health, nv, ov), new_params,
                params)
        if cfg.amp:
            from ..fluid.executor import _amp_scale_update
            s, g, b = _amp_scale_update(
                health, amp_state["scale"], amp_state["good"],
                amp_state["bad"], AMP_CFG)
            amp_state = {"scale": s, "good": g, "bad": b}
        loss = jnp.mean(losses)
        return new_params, amp_state, (loss, losses, health, dropped)
    return step


def _param_shardings(cfg: LMConfig, mesh, params):
    specs = _stage_specs(cfg, params["stages"])
    return {k: (NamedSharding(mesh, P()) if k != "stages" else
                {k2: NamedSharding(mesh, specs[k2])
                 for k2 in params["stages"]})
            for k in params}


def make_train_step(cfg: LMConfig, mesh, guard: bool = True):
    """One composed 3D-parallel optimizer step:
    step(params, amp_state, xb, yb, key) →
    (params', amp_state', (loss, losses[n_micro], health, dropped)).
    Updated params are sharding-constrained back to their input layout
    (stage stacks over "pp"/"dp", the rest replicated) — without the
    pin, GSPMD re-shards e.g. the position table over "sp" on output
    and the NEXT dispatch retraces against the changed input sharding
    (the executor_retraces_total ≠ 0 failure mode)."""
    inner = _make_step(
        cfg, lambda params, xb, yb, key: _forward_composed(
            cfg, params, xb, yb, key, mesh), guard=guard)
    shardings = None

    def step(params, amp_state, xb, yb, key):
        nonlocal shardings
        if shardings is None:
            shardings = _param_shardings(cfg, mesh, params)
        new_params, amp_state, out = inner(params, amp_state, xb, yb,
                                           key)
        new_params = jax.lax.with_sharding_constraint(new_params,
                                                      shardings)
        return new_params, amp_state, out
    return step


def make_oracle_step(cfg: LMConfig, guard: bool = True):
    def forward(params, xb, yb, key):
        return _forward_oracle(cfg, params, xb, yb, key)
    return _make_step(cfg, forward, guard=guard)


def _window(cfg: LMConfig, step, constrain=None):
    def window(params, amp_state, windows, key_base, idx0):
        """K steps as ONE lax.scan — ``windows`` [K, n_micro, mb, S+1]
        int32 token stacks (one device_put per window; microbatch
        slices and the input/target shift are carved ON-DEVICE). The
        per-step key folds by GLOBAL step index idx0+i — the PR 2
        window rng contract, so a K-window run is bit-identical to K
        sequential step() calls."""
        k = windows.shape[0]

        def body(carry, x):
            params, amp_state = carry
            i, w = x
            key = jax.random.fold_in(key_base, i)
            xb, yb = w[..., :-1], w[..., 1:]
            params, amp_state, out = step(params, amp_state, xb, yb,
                                          key)
            return (params, amp_state), out
        (params, amp_state), outs = lax.scan(
            body, (params, amp_state),
            (idx0 + jnp.arange(k), windows))
        if constrain is not None:
            # the per-step constraint does not survive the scan-carry →
            # jit-output chain (XLA re-shards the final carry); re-pin
            # the window's param/amp outputs so window i+1 never
            # retraces
            params, amp_state = constrain(params, amp_state)
        return params, amp_state, outs
    return window


def make_window_step(cfg: LMConfig, mesh, guard: bool = True):
    shardings = None

    def constrain(params, amp_state):
        nonlocal shardings
        if shardings is None:
            shardings = _param_shardings(cfg, mesh, params)
        params = jax.lax.with_sharding_constraint(params, shardings)
        if amp_state:
            amp_state = jax.lax.with_sharding_constraint(
                amp_state, {k: NamedSharding(mesh, P())
                            for k in amp_state})
        return params, amp_state
    return _window(cfg, make_train_step(cfg, mesh, guard=guard),
                   constrain=constrain)


def make_oracle_window(cfg: LMConfig, guard: bool = True):
    return _window(cfg, make_oracle_step(cfg, guard=guard))


# ------------------------------------------------------------------- data
def sample_window(cfg: LMConfig, idx0: int, k: int = 1) -> np.ndarray:
    """K distinct step batches of structured synthetic sequences
    (per-row arithmetic progressions mod vocab — the delta is inferable
    from any adjacent pair, so a 1-layer causal transformer learns it)
    → [k, n_micro, mb, S+1] int32, deterministic in (seed, step)."""
    out = []
    for step in range(idx0, idx0 + k):
        r = np.random.RandomState(
            (cfg.seed * 1_000_003 + step * 7919) % (2 ** 31 - 1))
        start = r.randint(0, cfg.vocab, size=(cfg.batch, 1))
        delta = r.choice([1, 2, 3, 5], size=(cfg.batch, 1))
        toks = (start + delta * np.arange(cfg.seq_len + 1)[None]) \
            % cfg.vocab
        out.append(toks.reshape(cfg.n_micro, cfg.batch // cfg.n_micro,
                                cfg.seq_len + 1))
    return np.asarray(out, np.int32)


def place_params(cfg: LMConfig, mesh, params):
    """Pre-place params with their steady-state shardings (stage leaves
    per `_stage_specs`, everything else replicated) so the FIRST window
    dispatch already sees the same input shardings the step's outputs
    carry — without this the second call retraces against the
    now-sharded params (the PR 2 warm-twice note, solved at the source
    here)."""
    specs = _stage_specs(cfg, params["stages"])

    def put(x, spec):
        return jax.device_put(x, NamedSharding(mesh, spec))
    out = {k: (jax.device_put(v, NamedSharding(mesh, P()))
               if k != "stages" else None)
           for k, v in params.items()}
    out["stages"] = {k: put(v, specs[k])
                     for k, v in params["stages"].items()}
    return out


def place_window(cfg: LMConfig, mesh, windows: np.ndarray):
    """ONE device_put of a [K, n_micro, mb, S+1] window stack: batch
    dim over "dp", everything else replicated (the sequence dim carries
    S+1 tokens — the shift to S-token inputs/targets happens on-device,
    after which gpipe reshards S over "sp")."""
    return jax.device_put(
        windows, NamedSharding(mesh, P(None, None, "dp", None)))
