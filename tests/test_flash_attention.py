"""The Pallas flash-attention kernel itself, run through the Pallas
interpreter on CPU — so the suite exercises the REAL kernel (forward,
lse, and both backward kernels), not the `_dense_attention` fallback
(reference behavior contract: operators/fused/multihead_matmul_op.cu).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.attention_ops import _dense_attention
from paddle_tpu.ops.pallas import flash_attention as fa


@pytest.fixture(autouse=True)
def _interpret():
    with fa.interpret_guard():
        yield


def _rand_qkv(B, H, S, D, seed=0, dtype=np.float32):
    r = np.random.RandomState(seed)
    return tuple(jnp.asarray(r.normal(size=(B, H, S, D)).astype(dtype))
                 for _ in range(3))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("S", [128, 256])
def test_forward_matches_reference(S, causal):
    q, k, v = _rand_qkv(1, 2, S, 64)
    sm = 1.0 / 8.0
    assert fa._use_kernels(), "kernel path must be taken under interpret"
    out = fa.flash_attention(q, k, v, sm, causal)
    ref = _dense_attention(q, k, v, sm, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal,D", [(False, 32), (True, 32), (True, 256)],
                         ids=["full", "causal", "causal_d256"])
def test_grads_match_reference(causal, D):
    """D = 256 causal is Qwen3-Next's gated attention head."""
    q, k, v = _rand_qkv(1, 1, 256, D, seed=1)
    sm = 1.0 / np.sqrt(D)
    w = jnp.asarray(np.random.RandomState(2).normal(
        size=q.shape).astype(np.float32))

    def loss_flash(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, sm, causal) * w)

    def loss_ref(q, k, v):
        return jnp.sum(_dense_attention(q, k, v, sm, causal) * w)

    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_rf = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_fl, g_rf, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4, err_msg=f"d{name}")


def test_multi_kblock_online_softmax():
    """S=256 with blk=128 forces ≥2 K blocks per Q block, exercising the
    running-max rescale (the part the round-1 kernel didn't have)."""
    q, k, v = _rand_qkv(2, 2, 256, 64, seed=3)
    # spike late keys so the running max actually changes between blocks
    k = k.at[:, :, 200:].mul(5.0)
    out = fa.flash_attention(q, k, v, 0.125, False)
    ref = _dense_attention(q, k, v, 0.125, False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_lse_residual():
    q, k, v = _rand_qkv(1, 1, 128, 32, seed=4)
    seed = jnp.zeros((1,), jnp.int32)
    o, lse = fa._pallas_fwd(q, k, v, seed, 0.2, False, 128, 128)
    # wire form: (B·H, S, LANES) with the row stat broadcast across lanes
    assert lse.shape == (1, 128, fa.LANES)
    lse_np = np.asarray(lse)
    assert (lse_np == lse_np[:, :, :1]).all()
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * 0.2
    ref_lse = jax.scipy.special.logsumexp(s, axis=-1)
    np.testing.assert_allclose(lse_np[:, :, 0].reshape(1, 1, 128),
                               np.asarray(ref_lse), rtol=1e-5, atol=1e-5)


def test_bf16_inputs():
    q, k, v = _rand_qkv(1, 2, 128, 64, seed=5, dtype=np.float32)
    q, k, v = (t.astype(jnp.bfloat16) for t in (q, k, v))
    out = fa.flash_attention(q, k, v, 0.125, True)
    ref = _dense_attention(q, k, v, 0.125, True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("S,Sk", [(192, 192), (100, 100), (130, 75),
                                  (100, 256)])
def test_ragged_shapes_stay_on_kernel(S, Sk):
    """Non-block-divisible lengths run the Pallas kernels via in-kernel
    bounds masking (padded rows/cols contribute nothing) — no einsum
    fallback, forward AND grads."""
    r = np.random.RandomState(6)
    q = jnp.asarray(r.normal(size=(1, 2, S, 16)).astype(np.float32))
    k = jnp.asarray(r.normal(size=(1, 2, Sk, 16)).astype(np.float32))
    v = jnp.asarray(r.normal(size=(1, 2, Sk, 16)).astype(np.float32))
    assert fa._use_kernels()
    out = fa.flash_attention(q, k, v, 0.25, False)
    ref = _dense_attention(q, k, v, 0.25, False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, 0.25, False) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_dense_attention(q, k, v, 0.25, False) ** 2)

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_ragged_causal_matches_reference():
    q, k, v = _rand_qkv(1, 2, 100, 16, seed=8)
    out = fa.flash_attention(q, k, v, 0.25, True)
    ref = _dense_attention(q, k, v, 0.25, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


# ---------------------------------------------------------------- dropout
def _dropout_reference(q, k, v, sm_scale, causal, rate, seed):
    """jnp twin of the in-kernel dropout: softmax first, then the SAME
    counter-based keep mask (keep_mask_reference), scaled by 1/(1-rate)."""
    B, H, S, D = q.shape
    Sk = k.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * sm_scale
    if causal:
        m = jnp.arange(S)[:, None] >= jnp.arange(Sk)[None, :]
        s = jnp.where(m, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    masks = np.stack([
        fa.keep_mask_reference(seed, bh, np.arange(S), np.arange(Sk), rate)
        for bh in range(B * H)]).reshape(B, H, S, Sk)
    p = p * jnp.asarray(masks, jnp.float32) / (1.0 - rate)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)
                      ).astype(q.dtype)


def test_dropout_matches_mask_reference():
    q, k, v = _rand_qkv(1, 2, 256, 32, seed=8)
    seed = jnp.asarray([1234], jnp.int32)
    out = fa.flash_attention(q, k, v, 0.125, False, dropout_rate=0.1,
                             dropout_seed=seed)
    ref = _dropout_reference(q, k, v, 0.125, False, 0.1, 1234)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_dropout_determinism_and_rate():
    q, k, v = _rand_qkv(1, 1, 128, 32, seed=9)
    s1 = jnp.asarray([7], jnp.int32)
    s2 = jnp.asarray([8], jnp.int32)
    o1 = fa.flash_attention(q, k, v, 0.2, False, dropout_rate=0.3,
                            dropout_seed=s1)
    o1b = fa.flash_attention(q, k, v, 0.2, False, dropout_rate=0.3,
                             dropout_seed=s1)
    o2 = fa.flash_attention(q, k, v, 0.2, False, dropout_rate=0.3,
                            dropout_seed=s2)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o1b))
    assert not np.allclose(np.asarray(o1), np.asarray(o2))
    # empirical keep fraction of the mask generator ≈ 1 - rate
    m = fa.keep_mask_reference(7, 0, np.arange(512), np.arange(512), 0.3)
    assert abs(m.mean() - 0.7) < 0.01


def test_dropout_grads_match_mask_reference():
    q, k, v = _rand_qkv(1, 1, 128, 16, seed=10)
    seed = jnp.asarray([55], jnp.int32)
    w = jnp.asarray(np.random.RandomState(11).normal(
        size=q.shape).astype(np.float32))

    def loss_flash(q, k, v):
        return jnp.sum(fa.flash_attention(
            q, k, v, 0.25, True, dropout_rate=0.2, dropout_seed=seed) * w)

    def loss_ref(q, k, v):
        return jnp.sum(_dropout_reference(q, k, v, 0.25, True, 0.2, 55)
                       * w)

    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_rf = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_fl, g_rf, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4,
                                   err_msg=f"d{name}")


# -------------------------------------------------------- key-padding bias
def test_bias_matches_reference():
    q, k, v = _rand_qkv(2, 2, 128, 32, seed=12)
    # mask out a key suffix per batch row (padding form)
    bias = np.zeros((2, 128), np.float32)
    bias[0, 100:] = -1e9
    bias[1, 64:] = -1e9
    bias = jnp.asarray(bias)
    out = fa.flash_attention(q, k, v, 0.125, fa.Mask(bias=bias))
    ref = _dense_attention(q, k, v, 0.125, fa.Mask(bias=bias))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_bias_grads_and_causal_dropout_combo():
    q, k, v = _rand_qkv(1, 2, 128, 16, seed=14)
    bias = np.zeros((1, 128), np.float32)
    bias[0, 96:] = -1e9
    bias = jnp.asarray(bias)
    seed = jnp.asarray([99], jnp.int32)
    w = jnp.asarray(np.random.RandomState(15).normal(
        size=q.shape).astype(np.float32))

    def masked_ref(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * 0.25
        s = s + jnp.maximum(bias, fa.NEG_INF)[:, None, None, :]
        S = q.shape[2]
        cm = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(cm, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        masks = np.stack([
            fa.keep_mask_reference(99, bh, np.arange(S), np.arange(S), 0.1)
            for bh in range(2)]).reshape(1, 2, S, S)
        p = p * jnp.asarray(masks, jnp.float32) / 0.9
        return jnp.einsum("bhqk,bhkd->bhqd", p, v)

    def loss_flash(q, k, v):
        return jnp.sum(fa.flash_attention(
            q, k, v, 0.25, fa.Mask(True, bias=bias), dropout_rate=0.1,
            dropout_seed=seed) * w)

    def loss_ref(q, k, v):
        return jnp.sum(masked_ref(q, k, v) * w)

    np.testing.assert_allclose(
        np.asarray(loss_flash(q, k, v)), np.asarray(loss_ref(q, k, v)),
        rtol=1e-3)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_rf = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_fl, g_rf, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4,
                                   err_msg=f"d{name}")


def test_fully_masked_rows_emit_zeros_on_both_paths():
    """A key-padding bias masking ALL keys of a batch row used to yield
    finite garbage (~mean of V) on the Pallas path and NaN-adjacent
    output on the reference path; the defined semantics are now zeros
    and zero grads on both (ADVICE r2)."""
    B, H, S, D = 2, 2, 128, 64
    q, k, v = _rand_qkv(B, H, S, D, seed=7)
    bias = np.zeros((B, S), np.float32)
    bias[0, :] = -1e30  # batch row 0: every key masked
    bias = jnp.asarray(bias)

    o_pallas = fa.flash_attention(q, k, v, 0.125, fa.Mask(bias=bias))
    o_ref = _dense_attention(q, k, v, 0.125, fa.Mask(bias=bias))
    np.testing.assert_array_equal(np.asarray(o_pallas[0]), 0.0)
    np.testing.assert_array_equal(np.asarray(o_ref[0]), 0.0)
    # unmasked batch row is untouched and the two paths agree
    np.testing.assert_allclose(np.asarray(o_pallas[1], np.float32),
                               np.asarray(o_ref[1], np.float32),
                               rtol=2e-4, atol=2e-5)

    def loss_pallas(q, k, v):
        return jnp.sum(fa.flash_attention(
            q, k, v, 0.125, fa.Mask(bias=bias)).astype(jnp.float32) ** 2)

    dq, dk, dv = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    for g in (dq, dk, dv):
        assert np.isfinite(np.asarray(g, np.float32)).all()
    np.testing.assert_array_equal(np.asarray(dq[0], np.float32), 0.0)


# ------------------------------------------------- any block pair (PR 33)
def _reference(q, k, v, sm_scale, mask, rate=0.0, seed=0):
    """Dense attention under ``mask``; with dropout, under the kernels'
    own keep mask (a hash of absolute row and column, whatever the
    tiling)."""
    if not rate:
        return _dense_attention(q, k, v, sm_scale, mask)
    B, H, S, _ = q.shape
    Sk = k.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * sm_scale
    if mask.bias is not None:
        s = s + jnp.maximum(mask.bias, fa.NEG_INF)[:, None, None, :]
    if mask.causal or mask.window:
        rows, cols = jnp.arange(S)[:, None], jnp.arange(Sk)[None, :]
        seen = rows >= cols
        if mask.window:
            seen = seen & (cols > rows - mask.window)
        s = jnp.where(seen, s, -jnp.inf)
    keep = np.stack([
        fa.keep_mask_reference(seed, bh, np.arange(S), np.arange(Sk), rate)
        for bh in range(B * H)]).reshape(B, H, S, Sk)
    p = jax.nn.softmax(s, axis=-1) * jnp.asarray(keep, jnp.float32)
    return jnp.einsum("bhqk,bhkd->bhqd", p / (1.0 - rate), v)


def _keypad(B, Sk, kept):
    bias = np.zeros((B, Sk), np.float32)
    for b, n in enumerate(kept):
        bias[b, n:] = -1e9
    return jnp.asarray(bias)


BLOCK_CASES = {
    # name: (B, S, Sk, D, Dv, mask, dropout)
    "causal": (1, 256, 256, 16, 16, fa.Mask(True), 0.0),
    "window": (1, 256, 256, 16, 16, fa.Mask(True, 40), 0.0),
    "window_wide_v": (1, 256, 256, 16, 32, fa.Mask(True, 100), 0.0),
    "keypad": (2, 256, 256, 16, 16,
               fa.Mask(bias=_keypad(2, 256, (200, 131))), 0.0),
    "keypad_dropout": (2, 256, 256, 16, 16,
                       fa.Mask(bias=_keypad(2, 256, (256, 77))), 0.2),
    "causal_dropout": (1, 256, 256, 16, 16, fa.Mask(True), 0.1),
    "ragged": (1, 200, 300, 16, 16, fa.Mask(), 0.0),
    "ragged_causal_wide_v": (1, 200, 200, 16, 24, fa.Mask(True), 0.0),
}


@pytest.mark.parametrize("blocks", [(128, 256), (256, 128), (64, 128),
                                    (256, 256)],
                         ids=lambda b: "%dx%d" % b)
@pytest.mark.parametrize("case", sorted(BLOCK_CASES))
def test_any_block_pair_gives_dense_attention_and_its_gradients(case,
                                                                blocks):
    """Non-square blocks, blocks longer than the window and blocks as
    long as the sequence, every mask form: the value and the three
    gradients are dense attention's (`_block_sizes` may choose any
    pair)."""
    B, S, Sk, D, Dv, mask, rate = BLOCK_CASES[case]
    r = np.random.RandomState(21)
    q = jnp.asarray(r.normal(size=(B, 2, S, D)).astype(np.float32))
    k = jnp.asarray(r.normal(size=(B, 2, Sk, D)).astype(np.float32))
    v = jnp.asarray(r.normal(size=(B, 2, Sk, Dv)).astype(np.float32))
    w = jnp.asarray(r.normal(size=(B, 2, S, Dv)).astype(np.float32))
    seed = jnp.asarray([31], jnp.int32)

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, 0.25, mask, dropout_rate=rate,
                                  dropout_seed=seed if rate else None)

    def want(q, k, v):
        return _reference(q, k, v, 0.25, mask, rate, 31)

    with fa.block_override(*blocks):
        got = flash(q, k, v)
        grads = jax.grad(lambda *a: jnp.sum(flash(*a) * w),
                         argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want(q, k, v)),
                               rtol=2e-4, atol=2e-5)
    refs = jax.grad(lambda *a: jnp.sum(want(*a) * w),
                    argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(grads, refs, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                   atol=2e-4, err_msg=f"d{name}")


def test_dropout_is_the_same_mask_at_any_tiling():
    """The keep mask hashes ABSOLUTE (row, column): two tilings of one
    call drop the same entries, so their outputs differ by the order of
    summation alone (and both differ from the call without dropout)."""
    q, k, v = _rand_qkv(1, 2, 512, 32, seed=16)
    seed = jnp.asarray([4242], jnp.int32)

    def run(blocks, rate=0.1):
        with fa.block_override(*blocks):
            return np.asarray(fa.flash_attention(
                q, k, v, 0.2, fa.Mask(True), dropout_rate=rate,
                dropout_seed=seed))
    small, large = run((128, 128)), run((256, 512))
    np.testing.assert_allclose(small, large, rtol=1e-5, atol=1e-6)
    assert np.abs(small - run((128, 128), 0.0)).max() > 1e-2
