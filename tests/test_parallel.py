"""Data-parallel tests on the virtual 8-device CPU mesh — the reference's
"compare N-rank against 1-rank losses" oracle (reference:
test_dist_base.py:933 check_with_place) without real chips."""
import numpy as np
import pytest

import jax

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import core
from paddle_tpu.fluid.framework import Program, program_guard
from paddle_tpu.parallel.mesh import build_mesh
from paddle_tpu.parallel.moe import expert_mesh


def _build(seed=11):
    main, startup = Program(), Program()
    main.random_seed = seed
    startup.random_seed = seed
    with program_guard(main, startup):
        x = fluid.data("x", shape=[16], dtype="float32")
        label = fluid.data("y", shape=[1], dtype="int64")
        h = fluid.layers.fc(x, 32, act="relu")
        pred = fluid.layers.fc(h, 4, act="softmax")
        loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, label))
        fluid.optimizer.SGD(0.1).minimize(loss)
    return main, startup, loss


def _train(mesh, steps=5):
    main, startup, loss = _build()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = core.Scope()
    rng = np.random.RandomState(0)
    X = rng.rand(64, 16).astype("float32")
    Y = rng.randint(0, 4, (64, 1)).astype("int64")
    losses = []
    with fluid.scope_guard(scope):
        exe.run(startup)
        for _ in range(steps):
            lv, = exe.run(main, feed={"x": X, "y": Y}, fetch_list=[loss],
                          mesh=mesh)
            losses.append(float(lv[0]))
    return losses


def test_mesh_dp_matches_single_device():
    """8-way data parallel must produce the same per-step losses as the
    single-device run on the same global batch."""
    single = _train(mesh=None)
    mesh = build_mesh(num_devices=8)
    dp = _train(mesh=mesh)
    np.testing.assert_allclose(single, dp, rtol=2e-4)
    assert dp[-1] < dp[0]


def test_compiled_program_with_data_parallel():
    main, startup, loss = _build()
    cp = fluid.CompiledProgram(main).with_data_parallel(loss_name=loss.name)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = core.Scope()
    rng = np.random.RandomState(0)
    X = rng.rand(64, 16).astype("float32")
    Y = rng.randint(0, 4, (64, 1)).astype("int64")
    with fluid.scope_guard(scope):
        exe.run(startup)
        l0 = None
        for _ in range(5):
            lv, = exe.run(cp, feed={"x": X, "y": Y}, fetch_list=[loss])
            if l0 is None:
                l0 = float(lv[0])
    assert float(lv[0]) < l0


def test_feed_not_divisible_raises():
    main, startup, loss = _build()
    mesh = build_mesh(num_devices=8)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = core.Scope()
    rng = np.random.RandomState(0)
    with fluid.scope_guard(scope):
        exe.run(startup)
        with pytest.raises(ValueError, match="not divisible"):
            exe.run(main, feed={"x": rng.rand(6, 16).astype("float32"),
                                "y": rng.randint(0, 4, (6, 1)).astype("int64")},
                    fetch_list=[loss], mesh=mesh)


def test_fleet_collective_single_process():
    """fleet.distributed_optimizer path end-to-end (1 process, 8 devices)."""
    from paddle_tpu.fluid.incubate.fleet.collective import (
        fleet, DistributedStrategy)
    from paddle_tpu.fluid.incubate.fleet.base.role_maker import (
        UserDefinedCollectiveRoleMaker)
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.data("x", shape=[16], dtype="float32")
        label = fluid.data("y", shape=[1], dtype="int64")
        h = fluid.layers.fc(x, 32, act="relu")
        pred = fluid.layers.fc(h, 4, act="softmax")
        loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, label))
        fleet.init(UserDefinedCollectiveRoleMaker(0, ["127.0.0.1:1"]))
        opt = fleet.distributed_optimizer(fluid.optimizer.SGD(0.1),
                                          DistributedStrategy())
        opt.minimize(loss, startup_program=startup)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = core.Scope()
    rng = np.random.RandomState(0)
    X = rng.rand(64, 16).astype("float32")
    Y = rng.randint(0, 4, (64, 1)).astype("int64")
    with fluid.scope_guard(scope):
        exe.run(fleet.startup_program)
        l0 = None
        for _ in range(5):
            lv, = exe.run(fleet.main_program, feed={"x": X, "y": Y},
                          fetch_list=[loss])
            if l0 is None:
                l0 = float(lv[0])
    assert float(lv[0]) < l0


def test_collective_c_ops_identity_outside_mesh():
    """c_allreduce_* are identity with world size 1 (NCCL single-rank
    semantics) — transpiled reference programs stay correct."""
    from paddle_tpu.ops.registry import OPS
    import jax.numpy as jnp
    x = jnp.asarray(np.random.rand(4).astype("float32"))
    for op in ("c_allreduce_sum", "c_allreduce_max", "c_broadcast",
               "c_allgather", "c_reducescatter", "c_sync_calc_stream"):
        o = OPS.get(op).kernel({"X": [x]}, {"ring_id": 0})["Out"][0]
        np.testing.assert_allclose(np.asarray(o), np.asarray(x))


def test_collective_ops_inside_shard_map():
    """ring_id → mesh axis: inside shard_map the c_ops lower to ICI
    collectives."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from paddle_tpu.ops import collective_ops
    from paddle_tpu.ops.registry import OPS
    import jax.numpy as jnp

    mesh = build_mesh(num_devices=8)
    collective_ops.set_ring_axis(0, "dp")
    try:
        def f(x):
            return OPS.get("c_allreduce_sum").kernel(
                {"X": [x]}, {"ring_id": 0})["Out"][0]

        x = jnp.arange(8.0).reshape(8, 1)
        y = shard_map(f, mesh=mesh, in_specs=P("dp", None),
                      out_specs=P("dp", None))(x)
        np.testing.assert_allclose(np.asarray(y),
                                   np.full((8, 1), 28.0))
    finally:
        collective_ops.set_ring_axis(0, None)


def test_init_distributed_wiring(monkeypatch):
    """parallel.env.init_distributed maps the PADDLE_* env contract onto
    jax.distributed.initialize (reference: gen_nccl_id bootstrap)."""
    import jax
    from paddle_tpu.parallel import env as penv

    calls = []
    monkeypatch.setattr(jax.distributed, "initialize",
                        lambda **kw: calls.append(kw))
    monkeypatch.setattr(jax.distributed, "is_initialized",
                        lambda: False, raising=False)
    monkeypatch.setenv("PADDLE_TRAINERS_NUM", "4")
    monkeypatch.setenv("PADDLE_TRAINER_ID", "2")
    monkeypatch.setenv("PADDLE_TRAINER_ENDPOINTS",
                       "10.0.0.1:6170,10.0.0.2:6170")
    assert penv.init_distributed() is True
    assert calls == [{"coordinator_address": "10.0.0.1:6170",
                      "num_processes": 4, "process_id": 2}]
    # single-process: no-op
    monkeypatch.setenv("PADDLE_TRAINERS_NUM", "1")
    assert penv.init_distributed() is False


@pytest.mark.parametrize("model_parallel", [1, 2])
@pytest.mark.parametrize("path", ["kernels", "dense"])
def test_attention_under_a_mesh_matches_one_device(model_parallel, path):
    """XLA cannot partition a Mosaic kernel, so under a ("dp", "mp") mesh
    `flash_attention` wraps itself in a shard_map (batch over dp, heads
    over mp). With the kernels on the path (interpreter here, Mosaic in
    tests/test_chip_compile.py; the attention ops' bound patched down,
    this tiny BERT's s16 being dense's otherwise) a tiny BERT's losses
    on four devices match one device, and the step still holds its
    all-reduce. On the dense side of the rule the same parity holds and
    no shard_map is entered: XLA partitions its own ops."""
    import __graft_entry__ as legs
    from paddle_tpu.models import bert
    from paddle_tpu.ops import attention_ops
    from paddle_tpu.ops.pallas import flash_attention as fa

    cfg = legs._tiny_cfg()
    main, startup, _, fetches = bert.build_bert_pretrain_program(
        cfg, seq_len=16, lr=1e-3)
    program = (main, startup, fetches)
    feed = bert.synthetic_pretrain_batch(cfg, 8, 16)
    calls = []
    on_mesh = fa._flash_on_mesh
    with fa.interpret_guard(), pytest.MonkeyPatch.context() as mp:
        mp.setattr(fa, "_flash_on_mesh",
                   lambda *a: calls.append(a[0]) or on_mesh(*a))
        if path == "kernels":
            mp.setattr(attention_ops, "DENSE_MAX_SEQ", 0)
        solo = legs.bert_losses(program, feed, steps=3)
        assert not calls
        row = legs.bert_n_vs_1(jax.devices()[:4], program, cfg, feed,
                               model_parallel, solo)
    if path == "kernels":
        assert calls and all(m.devices.size == 4 for m in calls)
    else:
        assert not calls
    assert row["devices"] == 4


def test_flash_dropout_under_a_mesh_differs_per_shard():
    """Each shard mixes its mesh position into the dropout seed: the
    kernel's mask hashes LOCAL row indices, and with equal seeds every
    data shard would drop the same entries."""
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import flash_attention as fa

    mesh = build_mesh(devices=jax.devices()[:2])
    r = np.random.RandomState(0)
    half = r.normal(size=(1, 2, 16, 8)).astype("float32")
    q = jnp.asarray(np.concatenate([half, half]))  # two identical rows
    seed = jnp.asarray([7], jnp.int32)
    with fa.interpret_guard(), fa.mesh_guard(mesh):
        o = np.asarray(jax.jit(lambda q: fa.flash_attention(
            q, q, q, 0.3, dropout_rate=0.5, dropout_seed=seed))(q))
    assert np.isfinite(o).all()
    assert not np.array_equal(o[0], o[1])


@pytest.mark.parametrize("heads", [4, 3])  # 3: replicated over "mp"
def test_flash_on_mesh_value_and_grads_match_unpartitioned(heads):
    """The shard_map runs unchecked (check_vma=False); what that leaves
    open is the transpose over an axis the operands are replicated on
    (heads that do not divide "mp"). Value and all three grads must
    equal the unpartitioned kernel's."""
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import flash_attention as fa

    mesh = build_mesh(model_parallel=2, devices=jax.devices()[:4])
    r = np.random.RandomState(3)
    q, k, v = (jnp.asarray(r.normal(size=(2, heads, 16, 8)), jnp.float32)
               for _ in range(3))

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, 0.35, True) ** 2)

    grad = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    with fa.interpret_guard():
        want = grad(q, k, v)
        with fa.mesh_guard(mesh):
            got = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(
                q, k, v)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("make", [
    build_mesh, lambda n: build_mesh(n, model_parallel=2), expert_mesh,
], ids=["build_mesh", "build_mesh_mp", "expert_mesh"])
def test_mesh_over_more_devices_than_there_are_raises(make):
    """A mesh asked for n devices is a mesh of n devices or an error —
    never the first few that happen to exist (the suite has 8)."""
    assert make(8).devices.size == 8
    with pytest.raises(ValueError, match="needs 16 devices, have 8"):
        make(16)


@pytest.mark.parametrize("beyond", [8, 11])
def test_tpu_place_beyond_the_local_devices_raises(beyond):
    """TPUPlace(i) is local device i: an id at or past the device count
    is an error, not device i modulo the count."""
    assert core.TPUPlace(7).jax_device() == jax.local_devices()[7]
    with pytest.raises(ValueError, match="8 local cpu device"):
        core.TPUPlace(beyond).jax_device()
    main, startup, loss = _build()
    with fluid.scope_guard(core.Scope()):
        fluid.Executor().run(startup)
        with pytest.raises(ValueError, match="8 local cpu device"):
            fluid.Executor(core.TPUPlace(beyond)).run(
                main, feed={"x": np.zeros((8, 16), "float32"),
                            "y": np.zeros((8, 1), "int64")},
                fetch_list=[loss])
