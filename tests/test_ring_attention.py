"""Sequence/context parallelism tests: ring + Ulysses attention vs the
dense single-device oracle, forward and backward, on the 8-CPU mesh."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.attention_ops import _dense_attention as _ref_attention
from paddle_tpu.parallel.ring_attention import (
    ring_attention, sequence_mesh, ulysses_attention)

B, H, S, D = 2, 4, 32, 8
SP = 4


def _qkv(seed):
    r = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(r.normal(size=(B, H, S, D)), jnp.float32)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
# r19 fleet-PR buyback (~10s both params): lm3d pp-only parity
# trains through ring_attention_local against its oracle per-commit
# (PR 14 demoted the grad-parity sibling with the same twin).
@pytest.mark.slow
def test_ring_matches_dense(causal):
    q, k, v = _qkv(0)
    mesh = sequence_mesh(SP)
    scale = 1.0 / np.sqrt(D)
    out = ring_attention(q, k, v, scale, causal, mesh=mesh)
    ref = _ref_attention(q, k, v, scale, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.slow
@pytest.mark.parametrize("causal", [False, True])
# causal=False demoted r13, causal=True r19 (suite-time buyback, 17s):
# forward ring-vs-dense parity for BOTH causal modes stays tier-1
# above, and the composed lm3d lane trains THROUGH ring_attention_local
# with grads bit-identical to its oracle every commit
# (test_parallel3d.py) — the direct dense-grad parity pair is the
# round-end full tier's job
def test_ring_grads_match_dense(causal):
    q, k, v = _qkv(1)
    mesh = sequence_mesh(SP)
    scale = 1.0 / np.sqrt(D)

    def ring_loss(q, k, v):
        return jnp.sum(ring_attention(q, k, v, scale, causal,
                                      mesh=mesh) ** 2)

    def dense_loss(q, k, v):
        return jnp.sum(_ref_attention(q, k, v, scale, causal) ** 2)

    gr = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_dense(causal):
    q, k, v = _qkv(2)
    mesh = sequence_mesh(SP)
    scale = 1.0 / np.sqrt(D)
    out = ulysses_attention(q, k, v, scale, causal, mesh=mesh)
    ref = _ref_attention(q, k, v, scale, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


# r19 fleet-PR buyback (~6s); same rationale as above — the lm3d
# lane exercises the sp axis per-commit.
@pytest.mark.slow
def test_ulysses_grads_match_dense():
    q, k, v = _qkv(3)
    mesh = sequence_mesh(SP)
    scale = 1.0 / np.sqrt(D)
    gr = jax.grad(lambda q, k, v: jnp.sum(
        ulysses_attention(q, k, v, scale, True, mesh=mesh) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(lambda q, k, v: jnp.sum(
        _ref_attention(q, k, v, scale, True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_ring_sharded_inputs_stay_sharded():
    """With pre-sharded device arrays, the output keeps the sequence
    sharding (no gather to host-resident full array)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    q, k, v = _qkv(4)
    mesh = sequence_mesh(SP)
    sh = NamedSharding(mesh, P(None, None, "sp", None))
    q, k, v = (jax.device_put(x, sh) for x in (q, k, v))
    out = jax.jit(lambda q, k, v: ring_attention(
        q, k, v, 1.0 / np.sqrt(D), False, mesh=mesh))(q, k, v)
    assert out.sharding.spec == P(None, None, "sp", None)


def test_ulysses_head_divisibility_error():
    q, k, v = _qkv(5)
    mesh = sequence_mesh(3)
    with pytest.raises(ValueError, match="divisible"):
        ulysses_attention(q, k, v, mesh=mesh)
