"""Expert parallelism: Mixture-of-Experts FFN with token-choice top-1
routing and all-to-all dispatch over an "ep" mesh axis.

Beyond-reference capability (the reference has no MoE; its closest
analogue is the sparse PS plane) designed TPU-first: experts are sharded
over the mesh's "ep" axis, tokens are dispatched into static-shape
per-expert capacity buffers (no dynamic shapes under jit), and the
exchange is ONE jax.lax.all_to_all each way inside shard_map — the
canonical MoE dispatch that rides ICI (GShard/Switch recipe as described
in the public scaling-book material).

Capacity semantics: each expert accepts at most ``capacity`` tokens per
shard; overflow tokens are dropped (their combine weight is zero), the
standard Switch-style trade that keeps shapes static for XLA. Drops are
COUNTED: the local dispatch returns the number of locally-routed tokens
that overflowed, so a training lane can watch expert balance instead of
silently losing tokens.

Two layers (mirrors parallel/pipeline.py):
  * ``moe_ffn_local`` — the per-device body, written against a NAMED
    mesh axis with raw ``lax.all_to_all`` collectives so it composes
    inside an ALREADY-OPEN ``shard_map`` region — e.g. nested in a
    GPipe stage over a dp×pp×sp mesh, where the expert axis is one of
    the other mesh axes (parallel/lm3d.py uses axis="dp").
  * ``moe_ffn`` — the standalone wrapper: one shard_map over the "ep"
    axis around ``moe_ffn_local``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

EP_AXIS = "ep"

__all__ = ["EP_AXIS", "expert_mesh", "expert_capacity", "moe_ffn_local",
           "moe_ffn", "moe_ffn_reference"]


def expert_mesh(n_devices: Optional[int] = None, devices=None) -> Mesh:
    devs = list(devices if devices is not None else jax.devices())
    if n_devices is not None:
        if len(devs) < n_devices:
            raise ValueError(
                f"expert_mesh needs {n_devices} devices, have {len(devs)}")
        devs = devs[:n_devices]
    return Mesh(np.asarray(devs), (EP_AXIS,))


def expert_capacity(tokens_per_shard: int, n_experts: int,
                    capacity_factor: float) -> int:
    """Per-expert per-shard capacity buffer length (static shape)."""
    return max(1, int(np.ceil(
        tokens_per_shard * capacity_factor / n_experts)))


def _dispatch_local(x, gate_logits, n_experts, capacity):
    """Token→expert dispatch within one shard. Returns (buffers [E, C, D],
    combine info, dropped count) with static shapes."""
    n_tok, d = x.shape
    top1 = jnp.argmax(gate_logits, axis=-1)               # [T]
    gate = jax.nn.softmax(gate_logits, axis=-1)
    top1_gate = jnp.take_along_axis(gate, top1[:, None], axis=1)[:, 0]
    onehot = jax.nn.one_hot(top1, n_experts, dtype=jnp.int32)   # [T, E]
    # position of each token inside its expert's capacity buffer
    pos_in_expert = (jnp.cumsum(onehot, axis=0) - 1) * onehot   # [T, E]
    pos = jnp.sum(pos_in_expert, axis=-1)                       # [T]
    keep = pos < capacity
    weight = jnp.where(keep, top1_gate, 0.0)
    dropped = jnp.sum(jnp.logical_not(keep).astype(jnp.int32))
    buf = jnp.zeros((n_experts, capacity, d), x.dtype)
    buf = buf.at[top1, jnp.minimum(pos, capacity - 1)].add(
        x * keep[:, None].astype(x.dtype))
    return buf, (top1, jnp.minimum(pos, capacity - 1), weight), dropped


def _combine_local(expert_out, info):
    top1, pos, weight = info
    gathered = expert_out[top1, pos]                      # [T, D]
    return gathered * weight[:, None].astype(expert_out.dtype)


def moe_ffn_local(xt, gate_w, w1, b1, w2, b2, *, axis, capacity,
                  activation=jax.nn.gelu):
    """One device's MoE FFN inside an open shard_map region.

    xt   [T, D]           this shard's tokens
    gate_w [D, E]         replicated router (E = GLOBAL expert count)
    w1 [E/n, D, F], b1 [E/n, F], w2 [E/n, F, D], b2 [E/n, D]
                          THIS device's expert slice along ``axis``
    axis                  mesh axis name the experts shard over (must be
                          a manual axis of the enclosing shard_map)
    capacity              per-expert per-shard buffer length
                          (see ``expert_capacity``)

    Returns ``(out [T, D], dropped)`` — ``dropped`` is the int32 count
    of THIS shard's tokens that overflowed their expert's capacity (sum
    ``lax.psum(dropped, axis)`` for the global count). Route its local
    tokens, all_to_all the capacity buffers so every device holds ITS
    experts' tokens from all shards, run the local experts' FFN,
    all_to_all back, combine.
    """
    E = gate_w.shape[-1]
    e_local = w1.shape[0]
    if E % e_local:
        raise ValueError(f"global experts {E} not divisible into local "
                         f"slices of {e_local}")
    n_dev = E // e_local
    T, D = xt.shape
    logits = (xt @ gate_w.astype(xt.dtype)).astype(jnp.float32)  # [T, E]
    buf, info, dropped = _dispatch_local(xt, logits, E, capacity)
    if n_dev == 1:
        # every expert is local (the single-device oracle composition,
        # or ep degree 1 on a degenerate mesh) — no exchange to ride
        mine = buf.reshape(e_local, capacity, D)
    else:
        # [E, C, D] → exchange: split E across devices, concat the shard
        # dim → [E/n, n·C, D] (this device's experts, tokens of every
        # shard)
        mine = lax.all_to_all(buf.reshape(n_dev, e_local, capacity, D),
                              axis, 0, 0, tiled=False)
        mine = jnp.moveaxis(mine, 0, 1).reshape(e_local,
                                                n_dev * capacity, D)
    h = activation(jnp.einsum("ecd,edf->ecf", mine, w1.astype(xt.dtype))
                   + b1.astype(xt.dtype)[:, None, :])
    out = jnp.einsum("ecf,efd->ecd", h, w2.astype(xt.dtype)) \
        + b2.astype(xt.dtype)[:, None, :]
    if n_dev == 1:
        back = out.reshape(E, capacity, D)
    else:
        # inverse exchange: back to [E, C, D] on the token's home shard
        out = jnp.moveaxis(out.reshape(e_local, n_dev, capacity, D),
                           1, 0)
        back = lax.all_to_all(out, axis, 0, 0, tiled=False)
        back = back.reshape(E, capacity, D)
    return _combine_local(back, info), dropped


def moe_ffn(x, gate_w, w1, b1, w2, b2, mesh: Mesh,
            capacity_factor: float = 2.0, activation=jax.nn.gelu,
            return_dropped: bool = False):
    """MoE FFN layer: x [B, S, D] (tokens sharded over "ep" on B),
    gate_w [D, E]; w1 [E, D, F], b1 [E, F], w2 [E, F, D], b2 [E, D] with
    experts sharded over "ep" on E. Output [B, S, D], token-sharded;
    with ``return_dropped`` also the GLOBAL int32 count of tokens
    dropped by the per-expert capacity bound (replicated scalar)."""
    n_dev = mesh.shape[EP_AXIS]
    E = gate_w.shape[-1]
    assert E % n_dev == 0, (E, n_dev)

    B, S, D = x.shape
    capacity = expert_capacity((B // n_dev) * S, E, capacity_factor)

    def shard_fn(xs, gw, w1s, b1s, w2s, b2s):
        # xs: [B/n, S, D] local tokens; w1s: [E/n, D, F] local experts
        y, dropped = moe_ffn_local(xs.reshape(-1, D), gw, w1s, b1s, w2s,
                                   b2s, axis=EP_AXIS, capacity=capacity,
                                   activation=activation)
        return y.reshape(xs.shape), lax.psum(dropped, EP_AXIS)

    from .mesh import shard_map
    fn = shard_map(shard_fn, mesh=mesh,
                   in_specs=(P(EP_AXIS, None, None), P(None, None),
                             P(EP_AXIS, None, None), P(EP_AXIS, None),
                             P(EP_AXIS, None, None), P(EP_AXIS, None)),
                   out_specs=(P(EP_AXIS, None, None), P()))
    y, dropped = fn(x, gate_w, w1, b1, w2, b2)
    return (y, dropped) if return_dropped else y


def moe_ffn_reference(x, gate_w, w1, b1, w2, b2,
                      activation=jax.nn.gelu):
    """Dense single-device oracle: every token through its top-1 expert
    (ample capacity ⇒ moe_ffn must match this exactly)."""
    B, S, D = x.shape
    xt = x.reshape(-1, D)
    logits = xt @ gate_w
    top1 = jnp.argmax(logits, axis=-1)
    gate = jax.nn.softmax(logits, axis=-1)
    w = jnp.take_along_axis(gate, top1[:, None], axis=1)[:, 0]
    h = activation(jnp.einsum("td,edf->tef", xt, w1) + b1[None])
    outs = jnp.einsum("tef,efd->ted", h, w2) + b2[None]
    sel = jnp.take_along_axis(
        outs, top1[:, None, None].repeat(D, -1), axis=1)[:, 0]
    return (sel * w[:, None]).reshape(x.shape)
