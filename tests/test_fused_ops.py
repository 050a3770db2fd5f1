"""Fused-op family tests (reference: tests/unittests/test_fc_op.py,
test_fused_elemwise_activation_op.py, test_fused_emb_seq_pool_op.py,
test_fusion_gru_op.py, test_fusion_lstm_op.py,
test_fusion_seqpool_concat_op.py, test_fusion_squared_mat_sub_op.py,
test_fusion_transpose_flatten_concat_op.py, test_fusion_repeated_fc_relu_op.py).
Each fused op must equal its unfused composition."""
import numpy as np
import pytest
import torch
import torch.nn.functional as F

from tests.test_sequence_ops import run_seq_op


def test_fc_op():
    rng = np.random.RandomState(0)
    x = rng.rand(4, 3, 5).astype(np.float32)
    w = rng.rand(15, 7).astype(np.float32)
    b = rng.rand(7).astype(np.float32)
    (o,), _ = run_seq_op("fc", x, None, x_slot="Input",
                         extra_inputs=[("W", w, None), ("Bias", b, None)],
                         attrs={"in_num_col_dims": 1,
                                "activation_type": "relu"})
    ref = np.maximum(x.reshape(4, 15) @ w + b, 0).reshape(4, 7)
    np.testing.assert_allclose(o, ref, rtol=1e-5)


def test_fused_elemwise_activation():
    rng = np.random.RandomState(1)
    x = rng.randn(3, 4).astype(np.float32)
    y = rng.randn(3, 4).astype(np.float32)
    (o,), _ = run_seq_op("fused_elemwise_activation", x, None,
                         extra_inputs=[("Y", y, None)],
                         attrs={"functor_list": ["relu", "elementwise_add"]},
                         outputs=("Out",))
    np.testing.assert_allclose(o, np.maximum(x + y, 0), rtol=1e-6)
    (o2,), _ = run_seq_op("fused_elemwise_activation", x, None,
                          extra_inputs=[("Y", y, None)],
                          attrs={"functor_list": ["elementwise_add", "scale"],
                                 "scale": 2.0},
                          outputs=("Out",))
    np.testing.assert_allclose(o2, x + 2.0 * y, rtol=1e-6)


def test_fused_batch_norm_act():
    rng = np.random.RandomState(2)
    x = rng.rand(4, 3, 5, 5).astype(np.float32)
    ones, zeros = np.ones(3, np.float32), np.zeros(3, np.float32)
    (y,), _ = run_seq_op(
        "fused_batch_norm_act", x, None,
        extra_inputs=[("Scale", ones, None), ("Bias", zeros, None),
                      ("Mean", zeros, None), ("Variance", ones, None)],
        attrs={"is_test": True, "use_global_stats": True,
               "epsilon": 1e-5, "act_type": "relu"},
        outputs=("Y",))
    ref = F.relu(F.batch_norm(torch.from_numpy(x), torch.zeros(3),
                              torch.ones(3), torch.ones(3), torch.zeros(3),
                              training=False, eps=1e-5)).numpy()
    np.testing.assert_allclose(y, ref, rtol=1e-4, atol=1e-5)


def test_fused_embedding_eltwise_layernorm():
    rng = np.random.RandomState(3)
    emb1 = rng.rand(10, 8).astype(np.float32)
    emb2 = rng.rand(4, 8).astype(np.float32)
    ids1 = rng.randint(0, 10, (2, 5, 1)).astype(np.int64)
    ids2 = rng.randint(0, 4, (2, 5, 1)).astype(np.int64)
    scale = rng.rand(8).astype(np.float32)
    bias = rng.rand(8).astype(np.float32)
    (o,), _ = run_seq_op(
        "fused_embedding_eltwise_layernorm", ids1, None, x_slot="Ids",
        extra_inputs=[("Ids", ids2, None), ("Embs", emb1, None),
                      ("Embs", emb2, None), ("Scale", scale, None),
                      ("Bias", bias, None)],
        attrs={"epsilon": 1e-5})
    acc = emb1[ids1[..., 0]] + emb2[ids2[..., 0]]
    mu = acc.mean(-1, keepdims=True)
    var = acc.var(-1, keepdims=True)
    ref = (acc - mu) / np.sqrt(var + 1e-5) * scale + bias
    np.testing.assert_allclose(o, ref, rtol=1e-4, atol=1e-5)


def test_fused_embedding_seq_pool():
    rng = np.random.RandomState(4)
    w = rng.rand(12, 6).astype(np.float32)
    ids = rng.randint(0, 12, (7, 1)).astype(np.int64)
    lod = [[3, 4]]
    (o,), _ = run_seq_op("fused_embedding_seq_pool", ids, lod, x_slot="Ids",
                         extra_inputs=[("W", w, None)],
                         attrs={"combiner": "sum"})
    ref = np.stack([w[ids[:3, 0]].sum(0), w[ids[3:, 0]].sum(0)])
    np.testing.assert_allclose(o, ref, rtol=1e-5)


def test_fused_fc_elementwise_layernorm():
    rng = np.random.RandomState(5)
    x = rng.rand(4, 6).astype(np.float32)
    w = rng.rand(6, 8).astype(np.float32)
    b0 = rng.rand(8).astype(np.float32)
    y = rng.rand(4, 8).astype(np.float32)
    scale = rng.rand(8).astype(np.float32)
    b1 = rng.rand(8).astype(np.float32)
    (o,), _ = run_seq_op(
        "fused_fc_elementwise_layernorm", x, None,
        extra_inputs=[("W", w, None), ("Bias0", b0, None), ("Y", y, None),
                      ("Scale", scale, None), ("Bias1", b1, None)],
        attrs={"epsilon": 1e-5})
    t = x @ w + b0 + y
    mu, var = t.mean(-1, keepdims=True), t.var(-1, keepdims=True)
    ref = (t - mu) / np.sqrt(var + 1e-5) * scale + b1
    np.testing.assert_allclose(o, ref, rtol=1e-4, atol=1e-5)


def test_fusion_gru_equals_projected_dynamic_gru():
    rng = np.random.RandomState(6)
    T, M, H = 6, 4, 5
    x = rng.rand(T, M).astype(np.float32)
    wx = rng.rand(M, 3 * H).astype(np.float32)
    wh = rng.rand(H, 3 * H).astype(np.float32)
    b = rng.rand(1, 3 * H).astype(np.float32)
    lod = [[2, 4]]
    (h_fused,), _ = run_seq_op(
        "fusion_gru", x, lod,
        extra_inputs=[("WeightX", wx, None), ("WeightH", wh, None),
                      ("Bias", b, None)],
        outputs=("Hidden",))
    (h_ref,), _ = run_seq_op(
        "dynamic_gru", x @ wx, lod, x_slot="Input",
        extra_inputs=[("Weight", wh, None), ("Bias", b, None)],
        outputs=("Hidden",))
    np.testing.assert_allclose(h_fused, h_ref, rtol=1e-5, atol=1e-6)


def test_fusion_lstm_equals_projected_dynamic_lstm():
    rng = np.random.RandomState(7)
    T, M, H = 5, 3, 4
    x = rng.rand(T, M).astype(np.float32)
    wx = rng.rand(M, 4 * H).astype(np.float32)
    wh = rng.rand(H, 4 * H).astype(np.float32)
    b = rng.rand(1, 4 * H).astype(np.float32)
    lod = [[2, 3]]
    (h_fused, c_fused), _ = run_seq_op(
        "fusion_lstm", x, lod,
        extra_inputs=[("WeightX", wx, None), ("WeightH", wh, None),
                      ("Bias", b, None)],
        attrs={"use_peepholes": False},
        outputs=("Hidden", "Cell"))
    (h_ref, c_ref), _ = run_seq_op(
        "dynamic_lstm", x @ wx, lod, x_slot="Input",
        extra_inputs=[("Weight", wh, None), ("Bias", b, None)],
        attrs={"use_peepholes": False},
        outputs=("Hidden", "Cell"))
    np.testing.assert_allclose(h_fused, h_ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(c_fused, c_ref, rtol=1e-5, atol=1e-6)


def test_fusion_repeated_fc_relu():
    rng = np.random.RandomState(8)
    x = rng.rand(3, 4).astype(np.float32)
    w1 = rng.rand(4, 5).astype(np.float32)
    b1 = rng.rand(5).astype(np.float32)
    w2 = rng.rand(5, 2).astype(np.float32)
    b2 = rng.rand(2).astype(np.float32)
    (o,), _ = run_seq_op(
        "fusion_repeated_fc_relu", x, None,
        extra_inputs=[("W", w1, None), ("W", w2, None),
                      ("Bias", b1, None), ("Bias", b2, None)])
    ref = np.maximum(np.maximum(x @ w1 + b1, 0) @ w2 + b2, 0)
    np.testing.assert_allclose(o, ref, rtol=1e-5)


def test_fusion_seqpool_concat():
    rng = np.random.RandomState(9)
    x1 = rng.rand(5, 3).astype(np.float32)
    x2 = rng.rand(5, 2).astype(np.float32)
    lod = [[2, 3]]
    (o,), _ = run_seq_op("fusion_seqpool_concat", x1, lod,
                         extra_inputs=[("X", x2, lod)],
                         attrs={"pooltype": "SUM"})
    ref = np.concatenate([
        np.stack([x1[:2].sum(0), x1[2:].sum(0)]),
        np.stack([x2[:2].sum(0), x2[2:].sum(0)])], axis=1)
    np.testing.assert_allclose(o, ref, rtol=1e-5)


def test_fusion_squared_mat_sub():
    rng = np.random.RandomState(10)
    x = rng.rand(3, 4).astype(np.float32)
    y = rng.rand(4, 5).astype(np.float32)
    (o,), _ = run_seq_op("fusion_squared_mat_sub", x, None,
                         extra_inputs=[("Y", y, None)],
                         attrs={"scalar": 0.5})
    ref = 0.5 * ((x @ y) ** 2 - (x * x) @ (y * y))
    np.testing.assert_allclose(o, ref, rtol=1e-4, atol=1e-5)


def test_fusion_transpose_flatten_concat():
    rng = np.random.RandomState(11)
    x1 = rng.rand(2, 3, 4, 5).astype(np.float32)
    x2 = rng.rand(2, 3, 4, 5).astype(np.float32)
    (o,), _ = run_seq_op("fusion_transpose_flatten_concat", x1, None,
                         extra_inputs=[("X", x2, None)],
                         attrs={"trans_axis": [0, 2, 3, 1],
                                "flatten_axis": 1, "concat_axis": 1})
    f1 = x1.transpose(0, 2, 3, 1).reshape(2, -1)
    f2 = x2.transpose(0, 2, 3, 1).reshape(2, -1)
    np.testing.assert_allclose(o, np.concatenate([f1, f2], 1), rtol=1e-6)


def test_fusion_seqexpand_concat_fc():
    rng = np.random.RandomState(12)
    x = rng.rand(5, 3).astype(np.float32)       # LoD [[2,3]]
    z = rng.rand(2, 4).astype(np.float32)       # per-sequence row
    w = rng.rand(7, 6).astype(np.float32)
    b = rng.rand(6).astype(np.float32)
    (o,), _ = run_seq_op(
        "fusion_seqexpand_concat_fc", x, [[2, 3]],
        extra_inputs=[("X", z, None), ("FCWeight", w, None),
                      ("FCBias", b, None)],
        attrs={"fc_activation": "relu"})
    zexp = np.repeat(z, [2, 3], axis=0)
    ref = np.maximum(np.concatenate([x, zexp], 1) @ w + b, 0)
    np.testing.assert_allclose(o, ref, rtol=1e-5)


def test_conv2d_fusion():
    rng = np.random.RandomState(13)
    x = rng.rand(1, 3, 6, 6).astype(np.float32)
    w = rng.rand(4, 3, 3, 3).astype(np.float32)
    res = rng.rand(1, 4, 6, 6).astype(np.float32)
    (o,), _ = run_seq_op("conv2d_fusion", x, None, x_slot="Input",
                         extra_inputs=[("Filter", w, None),
                                       ("ResidualData", res, None)],
                         attrs={"strides": [1, 1], "paddings": [1, 1],
                                "dilations": [1, 1], "activation": "relu"},
                         outputs=("Output",))
    ref = F.relu(F.conv2d(torch.from_numpy(x), torch.from_numpy(w),
                          padding=1) + torch.from_numpy(res)).numpy()
    np.testing.assert_allclose(o, ref, rtol=1e-4, atol=1e-4)


def test_fusion_group_raises():
    x = np.zeros((2, 2), np.float32)
    with pytest.raises(NotImplementedError):
        run_seq_op("fusion_group", x, None)


def test_fused_attention_broadcastable_bias_routes_to_einsum():
    """A merely BROADCASTABLE bias ([B,1,1,1] scalar-per-batch) must NOT
    take the flash kernel (its (1, blk_k) bias block indexes real B/Sk
    extents); the einsum path broadcasts it correctly. Regression: this
    produced NaN when routed to the kernel."""
    import numpy as np
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.ops.registry import OPS
    r = np.random.RandomState(0)
    B, S, H, D = 2, 256, 2, 32  # above DENSE_MAX_SEQ: no-bias = kernels
    q = jnp.asarray(r.normal(size=(B, S, H * D)), jnp.float32)
    k = jnp.asarray(r.normal(size=(B, S, H * D)), jnp.float32)
    v = jnp.asarray(r.normal(size=(B, S, H * D)), jnp.float32)
    bias = jnp.asarray(r.normal(size=(B, 1, 1, 1)), jnp.float32)
    with fa.interpret_guard():  # make the flash path eligible on CPU
        outs = OPS.get("fused_attention_qkv").kernel(
            {"Q": [q], "K": [k], "V": [v], "Bias": [bias]},
            {"num_heads": H, "dropout_rate": 0.0, "causal": False})
    o = np.asarray(outs["Out"][0])
    assert np.isfinite(o).all()
    # scalar-per-batch bias shifts all scores equally → same as no bias
    with fa.interpret_guard():
        outs2 = OPS.get("fused_attention_qkv").kernel(
            {"Q": [q], "K": [k], "V": [v], "Bias": [None]},
            {"num_heads": H, "dropout_rate": 0.0, "causal": False})
    np.testing.assert_allclose(o, np.asarray(outs2["Out"][0]),
                               rtol=2e-4, atol=2e-5)


def _mhm_qkv_packed(B, S, H, D, seed=0):
    r = np.random.RandomState(seed)
    import jax.numpy as jnp
    return jnp.asarray(r.normal(size=(B, S, 3, H, D)) * 0.3, jnp.float32)


def test_multihead_matmul_keypad_bias_takes_flash_path(monkeypatch):
    """The fused inference op must ride the Pallas flash kernel for the
    key-padding BiasQK form [B,1,1,Sk] — the common BERT inference mask
    (reference: multihead_matmul_op.cu IS the fast path) — and its
    numerics must match the einsum path it replaces."""
    import jax.numpy as jnp
    from paddle_tpu.ops import attention_ops
    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.ops.registry import OPS
    B, S, H, D = 2, 256, 2, 32  # above DENSE_MAX_SEQ
    x = _mhm_qkv_packed(B, S, H, D)
    pad = np.zeros((B, 1, 1, S), np.float32)
    pad[:, :, :, S // 2:] = -1e9  # mask the right half of the keys
    bias_qk = jnp.asarray(pad)
    attrs = {"head_number": H, "alpha": 1.0 / np.sqrt(D)}

    calls = []
    real = fa.flash_attention

    def counting(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(attention_ops, "flash_attention", counting)
    with fa.interpret_guard():
        o_flash = np.asarray(OPS.get("multihead_matmul").kernel(
            {"Input": [x], "W": [None], "Bias": [None],
             "BiasQK": [bias_qk]}, dict(attrs))["Out"][0])
    assert calls, "key-padding BiasQK did not dispatch to the flash kernel"

    # einsum oracle: same op with the kernel ineligible (no interpret)
    o_einsum = np.asarray(OPS.get("multihead_matmul").kernel(
        {"Input": [x], "W": [None], "Bias": [None],
         "BiasQK": [bias_qk]}, dict(attrs))["Out"][0])
    np.testing.assert_allclose(o_flash, o_einsum, rtol=2e-4, atol=2e-5)


def test_multihead_matmul_generic_bias_keeps_einsum(monkeypatch):
    """A generic [B,H,Sq,Sk] BiasQK has no in-kernel form — it must stay
    on the einsum path even when the kernel is eligible."""
    import jax.numpy as jnp
    from paddle_tpu.ops import attention_ops
    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.ops.registry import OPS
    B, S, H, D = 2, 256, 2, 32  # above DENSE_MAX_SEQ: the bias decides
    x = _mhm_qkv_packed(B, S, H, D)
    bias_qk = jnp.asarray(
        np.random.RandomState(1).uniform(-1, 0, (B, H, S, S)), jnp.float32)

    def boom(*a, **kw):
        raise AssertionError("generic bias must not reach the flash kernel")

    monkeypatch.setattr(attention_ops, "flash_attention", boom)
    with fa.interpret_guard():
        o = np.asarray(OPS.get("multihead_matmul").kernel(
            {"Input": [x], "W": [None], "Bias": [None],
             "BiasQK": [bias_qk]},
            {"head_number": H, "alpha": 1.0 / np.sqrt(D)})["Out"][0])
    assert np.isfinite(o).all()


@pytest.mark.parametrize("path", ["dense", "kernels"])
def test_fused_attention_bf16_matmul_flag(monkeypatch, path):
    """FLAGS_use_bf16_matmul casts the attention matmuls to bf16 (MXU
    native rate — same contract as math_ops._mm) while keeping the f32
    output dtype; result stays inside bf16 tolerance of the f32 path,
    and gradients still flow, on the dense path this short sequence
    takes and on the kernels (the bound patched down). The cast is
    gated to non-CPU backends (emulated bf16 is a pessimization without
    an MXU), so the test spoofs a TPU backend to exercise it."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.fluid import core
    from paddle_tpu.ops.registry import OPS

    r = np.random.RandomState(3)
    B, S, H, D = 2, 16, 2, 8
    q, k, v = (jnp.asarray(r.normal(size=(B, S, H * D)) * 0.5, jnp.float32)
               for _ in range(3))
    kern = OPS.get("fused_attention_qkv").kernel
    attrs = {"num_heads": H, "dropout_rate": 0.0, "causal": False}
    ref = np.asarray(kern({"Q": [q], "K": [k], "V": [v], "Bias": [None]},
                          dict(attrs))["Out"][0])
    from paddle_tpu.ops.pallas import flash_attention as fa
    prev = core.globals_["FLAGS_use_bf16_matmul"]
    core.set_flag("FLAGS_use_bf16_matmul", True)
    from paddle_tpu.ops import attention_ops as ao
    monkeypatch.setattr(ao, "_mxu_backend", lambda: True)
    if path == "kernels":
        monkeypatch.setattr(ao, "DENSE_MAX_SEQ", 0)
    try:
        with fa.interpret_guard():  # spoofed TPU backend, CPU execution
            got = kern({"Q": [q], "K": [k], "V": [v], "Bias": [None]},
                       dict(attrs))["Out"][0]
            assert got.dtype == jnp.float32  # output dtype contract kept
            np.testing.assert_allclose(np.asarray(got), ref, rtol=2e-2,
                                       atol=2e-2)

            def loss(q_):
                return jnp.sum(kern(
                    {"Q": [q_], "K": [k], "V": [v], "Bias": [None]},
                    dict(attrs))["Out"][0] ** 2)
            g = jax.grad(loss)(q)
            assert np.isfinite(np.asarray(g)).all() and np.abs(g).max() > 0
    finally:
        core.set_flag("FLAGS_use_bf16_matmul", prev)


def test_bf16_dispatch_paths_share_f32_accumulation(monkeypatch):
    """Under FLAGS_use_bf16_matmul the einsum path must follow the flash
    kernel's f32-accumulation contract (preferred_element_type=f32 on
    QK^T and PV): softmax statistics see f32 scores on BOTH dispatch
    paths, so the same program gets the same numerics whichever way the
    bias shape routes it (r5 advisor finding: the einsum path used to
    round scores to bf16 before softmax)."""
    import jax.numpy as jnp
    from paddle_tpu.fluid import core
    from paddle_tpu.ops import attention_ops as ao
    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.ops.registry import OPS

    r = np.random.RandomState(5)
    # scale 2.0 makes |scores| ~ O(10): bf16 has ~3 significant digits,
    # so bf16-ROUNDED scores (the old einsum path) are off by ~0.06
    # absolute — softmax is sensitive to ABSOLUTE score error, so the
    # old path lands ~0.08 from the flash path, 5x the bf16 output-
    # rounding floor (~0.016) the fixed path sits on
    B, S, H, D = 2, 64, 2, 32
    q, k, v = (jnp.asarray(r.normal(size=(B, S, H * D)) * 2.0, jnp.float32)
               for _ in range(3))
    kern = OPS.get("fused_attention_qkv").kernel
    attrs = {"num_heads": H, "dropout_rate": 0.0, "causal": False}
    prev = core.globals_["FLAGS_use_bf16_matmul"]
    core.set_flag("FLAGS_use_bf16_matmul", True)
    monkeypatch.setattr(ao, "_mxu_backend", lambda: True)
    calls = []
    real = ao.flash_attention

    def counting(*a, **kw):
        calls.append(1)
        return real(*a, **kw)
    monkeypatch.setattr(ao, "flash_attention", counting)
    # S = 64 is under the bound: patched down, so that the no-bias call
    # is the kernels' (through the interpreter)
    monkeypatch.setattr(ao, "DENSE_MAX_SEQ", 0)
    try:
        with fa.interpret_guard():
            o_flash = np.asarray(kern(
                {"Q": [q], "K": [k], "V": [v], "Bias": [None]},
                dict(attrs))["Out"][0])
            assert calls, "no-bias call must take the flash path"
            del calls[:]
            # an all-zero GENERIC bias shape forces the einsum path
            # while leaving the math identical to no-bias
            zero_bias = jnp.zeros((B, H, S, S), jnp.float32)
            o_einsum = np.asarray(kern(
                {"Q": [q], "K": [k], "V": [v], "Bias": [zero_bias]},
                dict(attrs))["Out"][0])
            assert not calls, "generic bias must route to the einsum path"
    finally:
        core.set_flag("FLAGS_use_bf16_matmul", prev)
    # 2 bf16 ulps at this output scale; the bf16-rounded-scores bug sat
    # at ~0.08 here
    assert np.max(np.abs(o_flash - o_einsum)) < 0.03


# --------------------------------------------------------------------------
# the rule: which calls are the flash kernels' (attention_ops._use_flash)
# --------------------------------------------------------------------------
def _count_flash_calls(monkeypatch):
    """[] that grows by one with every call the ops make to the kernels'
    entry point (which still runs)."""
    from paddle_tpu.ops import attention_ops
    calls = []
    real = attention_ops.flash_attention
    monkeypatch.setattr(attention_ops, "flash_attention",
                        lambda *a, **kw: calls.append(1) or real(*a, **kw))
    return calls


def _attention_bias(form, B, H, Sq, Sk):
    import jax.numpy as jnp
    if form == "keypad":          # the exact in-kernel form
        pad = np.zeros((B, 1, 1, Sk), np.float32)
        pad[:, :, :, Sk // 2:] = -1e9
        return jnp.asarray(pad)
    if form == "generic":         # no in-kernel form
        return jnp.asarray(np.random.RandomState(1).uniform(
            -1, 0, (B, H, Sq, Sk)), jnp.float32)
    return None


_RULE_CASES = [  # Sq, Sk, bias form, calls to the kernels
    (16, 16, None, 0),
    (128, 128, None, 0),          # AT the bound: one kernel block, dense
    (128, 128, "keypad", 0),
    (129, 129, None, 1),          # one past it
    (256, 256, "keypad", 1),
    (256, 256, "generic", 0),     # above, but no kernel form for the bias
    (64, 256, None, 1),           # cross attention: either length decides
    (256, 64, "keypad", 1),
]


@pytest.mark.parametrize("op,Sq,Sk,bias_form,kernel_calls", [
    (op,) + case for case in _RULE_CASES
    for op in ("fused_attention_qkv", "multihead_matmul")
    # multihead_matmul's packed QKV has one length
    if op == "fused_attention_qkv" or case[0] == case[1]])
def test_attention_path_is_chosen_by_sequence_length(monkeypatch, op, Sq, Sk,
                                                     bias_form, kernel_calls):
    """Where a head's whole score tile fits one kernel block (both
    lengths <= DENSE_MAX_SEQ) the kernels stream nothing and both ops
    compute dense attention; one past it, the calls the kernels have a
    form for are theirs. The same rule for both ops, read from the
    call's shapes and bias form alone."""
    import jax.numpy as jnp
    from paddle_tpu.ops import attention_ops
    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.ops.registry import OPS
    assert attention_ops.DENSE_MAX_SEQ == 128
    B, H, D = 1, 2, 8
    r = np.random.RandomState(0)
    bias = _attention_bias(bias_form, B, H, Sq, Sk)
    calls = _count_flash_calls(monkeypatch)
    with fa.interpret_guard():
        if op == "fused_attention_qkv":
            q = jnp.asarray(r.normal(size=(B, Sq, H * D)), jnp.float32)
            k, v = (jnp.asarray(r.normal(size=(B, Sk, H * D)), jnp.float32)
                    for _ in range(2))
            o = OPS.get(op).kernel(
                {"Q": [q], "K": [k], "V": [v], "Bias": [bias]},
                {"num_heads": H, "dropout_rate": 0.0,
                 "causal": False})["Out"][0]
        else:
            o = OPS.get(op).kernel(
                {"Input": [_mhm_qkv_packed(B, Sq, H, D)], "W": [None],
                 "Bias": [None], "BiasQK": [bias]},
                {"head_number": H, "alpha": 1.0 / np.sqrt(D)})["Out"][0]
    assert len(calls) == kernel_calls
    assert o.shape == (B, Sq, H * D) and np.isfinite(np.asarray(o)).all()


@pytest.mark.parametrize("S", [64, 256], ids=["below_bound", "above_bound"])
@pytest.mark.parametrize("causal", [False, True])
def test_attention_paths_agree_on_each_side_of_the_bound(monkeypatch, S,
                                                         causal):
    """The rule chooses an implementation, not a result: with bf16
    operands (f32 scores and softmax on both paths) the op's output and
    its three gradients are the same, within bf16's rounding of the
    output, whether the bound sends the call to the kernels or to dense
    attention — at a shape that is dense's by default and at one that
    is the kernels'."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.fluid import core
    from paddle_tpu.ops import attention_ops as ao
    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.ops.registry import OPS
    B, H, D = 2, 2, 32
    r = np.random.RandomState(7)
    q, k, v = (jnp.asarray(r.normal(size=(B, S, H * D)) * 2.0, jnp.float32)
               for _ in range(3))
    w = jnp.asarray(r.normal(size=(B, S, H * D)), jnp.float32)
    kern = OPS.get("fused_attention_qkv").kernel
    attrs = {"num_heads": H, "dropout_rate": 0.0, "causal": causal}

    calls = _count_flash_calls(monkeypatch)

    def value_and_grads(bound):
        del calls[:]
        monkeypatch.setattr(ao, "DENSE_MAX_SEQ", bound)

        def loss(q, k, v):
            o = kern({"Q": [q], "K": [k], "V": [v], "Bias": [None]},
                     dict(attrs))["Out"][0]
            return jnp.sum(o * w), o
        with fa.interpret_guard():
            (_, o), grads = jax.value_and_grad(
                loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return bool(calls), [np.asarray(t) for t in (o,) + grads]

    prev = core.globals_["FLAGS_use_bf16_matmul"]
    core.set_flag("FLAGS_use_bf16_matmul", True)
    monkeypatch.setattr(ao, "_mxu_backend", lambda: True)
    try:
        kernels = value_and_grads(0)
        dense = value_and_grads(S)
    finally:
        core.set_flag("FLAGS_use_bf16_matmul", prev)
    assert kernels[0] and not dense[0]
    for a, b in zip(kernels[1], dense[1]):
        # the kernels round their output and gradients to bf16, dense
        # keeps f32: 4 bf16 ulps of the tensor's largest element (read:
        # at most 2; test_bf16_dispatch_paths_share_f32_accumulation's
        # 0.03 at an output of order 1 is the same room)
        assert np.max(np.abs(a - b)) < 2 ** -6 * max(1.0, np.abs(b).max())

