"""Model-family smoke tests on tiny shapes (reference tier-3 strategy:
tests/book/ + test_imperative_resnet/transformer — build, train a few
steps, assert loss decreases / stays finite)."""
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import core
from paddle_tpu.models import resnet, bert


# Root-caused r20 (was the STANDING KNOWN-FAIL since PR 15): at
# lr=0.05 / momentum=0.9 on one repeated 4-sample batch the first
# ~6 steps are a ringing transient (loss overshoots to 11.9-15.6 at
# step 3) that exponentially amplifies ULP-level reduction-order
# differences — under the suite's --xla_force_host_platform_device_count=8
# the step-5 loss lands at 3.55 (> initial 2.66) where the 1-device
# run lands at 1.97 (<). Both converge to ~0 by step 7. The old
# 5-step losses[-1] < losses[0] assertion sat inside the transient;
# assert past it instead (PR 13 Adagrad-ringing precedent). Stays
# `slow` as a ~20s heavyweight per the docs/ci.md convention.
@pytest.mark.slow
def test_resnet18_tiny_trains():
    np.random.seed(0)
    main, startup, feeds, fetches = resnet.build_resnet_train_program(
        depth=18, class_dim=4, image_size=16, lr=0.05)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = core.Scope()
    rng = np.random.RandomState(0)
    feed = {"image": rng.rand(4, 3, 16, 16).astype("float32"),
            "label": rng.randint(0, 4, (4, 1)).astype("int64")}
    with fluid.scope_guard(scope):
        exe.run(startup)
        losses = []
        for _ in range(12):
            lv, _ = exe.run(main, feed=feed, fetch_list=fetches)
            losses.append(float(lv[0]))
    assert np.isfinite(losses).all()
    assert min(losses[6:]) < 0.5 * losses[0]


def test_resnet50_builds():
    main, startup, feeds, fetches = resnet.build_resnet_train_program(
        depth=50, class_dim=10, image_size=32)
    types = {op.type for op in main.global_block().ops}
    assert "conv2d" in types and "batch_norm" in types
    # 53 convs in resnet50 (49 + shortcuts... just sanity-count)
    n_conv = sum(1 for op in main.global_block().ops if op.type == "conv2d")
    assert n_conv == 53


# r19 fleet-PR buyback (~15s compile-dominated convergence smoke):
# bert coverage stays per-commit via test_book_models bert feed +
# the recompute path in test_backward_executor (PR 13 precedent:
# vgg/transformer convergence twins live in the full tier).
@pytest.mark.slow
def test_bert_tiny_trains():
    cfg = dict(bert.bert_base_config())
    cfg.update(vocab_size=100, hidden=32, layers=2, heads=2, ffn=64,
               max_len=16)
    main, startup, feeds, fetches = bert.build_bert_pretrain_program(
        cfg, seq_len=16, lr=1e-3)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = core.Scope()
    rng = np.random.RandomState(0)
    B, S, M = 2, 16, 4
    feed = {
        "src_ids": rng.randint(0, 100, (B, S)).astype("int64"),
        "pos_ids": np.tile(np.arange(S), (B, 1)).astype("int64"),
        "sent_ids": np.zeros((B, S), "int64"),
        "mask_pos": rng.randint(0, B * S, (M, 1)).astype("int64"),
        "mask_label": rng.randint(0, 100, (M, 1)).astype("int64"),
    }
    with fluid.scope_guard(scope):
        exe.run(startup)
        losses = []
        for _ in range(8):
            lv, = exe.run(main, feed=feed, fetch_list=fetches)
            losses.append(float(lv[0]))
    assert np.isfinite(losses[-1])
    assert losses[-1] < losses[0]


def test_flash_attention_matches_reference():
    """Pallas/jax flash_attention vs naive softmax attention."""
    import jax.numpy as jnp
    from paddle_tpu.ops.attention_ops import \
        _dense_attention as _ref_attention
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.rand(2, 3, 16, 8).astype("float32"))
    k = jnp.asarray(rng.rand(2, 3, 16, 8).astype("float32"))
    v = jnp.asarray(rng.rand(2, 3, 16, 8).astype("float32"))
    o1 = flash_attention(q, k, v, 0.35)
    o2 = _ref_attention(q, k, v, 0.35)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)
    # causal
    o3 = flash_attention(q, k, v, 0.35, True)
    o4 = _ref_attention(q, k, v, 0.35, True)
    np.testing.assert_allclose(np.asarray(o3), np.asarray(o4), atol=1e-5)


def test_fused_attention_op_grad():
    """fused_attention_qkv backward via custom vjp is finite & correct
    direction (analytic vs numeric on a tiny case)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.registry import OPS
    info = OPS.get("fused_attention_qkv")
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.rand(1, 4, 8).astype("float32"))
    k = jnp.asarray(rng.rand(1, 4, 8).astype("float32"))
    v = jnp.asarray(rng.rand(1, 4, 8).astype("float32"))

    def f(q):
        o = info.kernel({"Q": [q], "K": [k], "V": [v]},
                        {"num_heads": 2})["Out"][0]
        return jnp.sum(o)

    g = jax.grad(f)(q)
    eps = 1e-3
    q2 = q.at[0, 1, 2].add(eps)
    num = (f(q2) - f(q)) / eps
    assert abs(float(g[0, 1, 2]) - float(num)) < 1e-2


@pytest.mark.slow  # 28s: BERT-scale remat parity is full-tier; the
# per-commit remat coverage is test_backward_executor's recompute test
# (PR 13 suite-time buyback, PR 8 precedent)
def test_bert_recompute_checkpoints_engage_and_match():
    """build_bert_pretrain_program(recompute=True): per-layer remat
    engages (no fallback warning, plan present) and per-step losses
    match the plain build exactly."""
    import warnings as _w
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import core
    from paddle_tpu.models import bert

    cfg = bert.bert_base_config()
    cfg.update(layers=3, hidden=64, heads=4, ffn=128)
    rng = np.random.RandomState(0)
    feed = {
        "src_ids": rng.randint(0, cfg["vocab_size"],
                               (2, 16)).astype("int64"),
        "pos_ids": np.tile(np.arange(16), (2, 1)).astype("int64"),
        "sent_ids": np.zeros((2, 16), "int64"),
        "mask_pos": rng.randint(0, 32, (4, 1)).astype("int64"),
        "mask_label": rng.randint(0, cfg["vocab_size"],
                                  (4, 1)).astype("int64"),
    }
    out = {}
    for recompute in (False, True):
        main, startup, feeds, fetches = bert.build_bert_pretrain_program(
            cfg, seq_len=16, dropout=0.0, lr=1e-3, recompute=recompute)
        main.random_seed = startup.random_seed = 3
        exe = fluid.Executor()
        scope = core.Scope()
        ctx = _w.catch_warnings()
        with ctx:
            if recompute:
                _w.simplefilter("error")  # fallback warning = failure
            with fluid.scope_guard(scope):
                exe.run(startup)
                ls = []
                for _ in range(3):
                    (l,) = exe.run(main, feed=feed, fetch_list=fetches)
                    ls.append(float(np.asarray(l).ravel()[0]))
        if recompute:
            cb = list(exe._compiled_cache.values())[-1]
            assert cb._remat_plan is not None
        out[recompute] = ls
    np.testing.assert_allclose(out[True], out[False], rtol=2e-5)
