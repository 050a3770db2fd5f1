"""`_CompiledBlock._place_inputs` on the virtual 8-device CPU mesh: a
placement plan a block, and state that already lies as the plan says
passed through as the object the scope holds. Counts and parity only;
what the no-op `device_put` calls cost is a chip's to say (PERF.md §6,
PR 29)."""
import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec as P

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import core, profiler, telemetry
from paddle_tpu.fluid.framework import Program, program_guard
from paddle_tpu.parallel.mesh import build_mesh

TP = {"w1": P(None, "mp"), "w2": P("mp", None)}
# what the parent commit (b09600b) gives for _program() on MESHES["dp2xmp2"]
# with TP, three steps (the CPU backend's numbers, not a chip's)
PARENT_DP2XMP2_LOSSES = [1.4843604564666748, 1.3647282123565674,
                         1.3039379119873047]
MESHES = {"dp8": dict(num_devices=8), "dp4": dict(num_devices=4),
          "dp2xmp2": dict(num_devices=4, model_parallel=2)}


def _program(train=True):
    main, startup = Program(), Program()
    main.random_seed = startup.random_seed = 11
    with program_guard(main, startup):
        x = fluid.data("x", shape=[16], dtype="float32")
        label = fluid.data("y", shape=[1], dtype="int64")
        h = fluid.layers.fc(x, 32, act="relu",
                            param_attr=fluid.ParamAttr(name="w1"),
                            bias_attr=fluid.ParamAttr(name="b1"))
        pred = fluid.layers.fc(h, 4, act="softmax",
                               param_attr=fluid.ParamAttr(name="w2"),
                               bias_attr=fluid.ParamAttr(name="b2"))
        loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, label))
        if train:
            fluid.optimizer.Adam(0.01).minimize(loss)
    return main, startup, loss


def _feed():
    rng = np.random.RandomState(0)
    return {"x": rng.rand(64, 16).astype("float32"),
            "y": rng.randint(0, 4, (64, 1)).astype("int64")}


class _Run:
    """One program on one mesh from a fresh scope, a step a call."""

    def __init__(self, mesh, shardings=None, train=True):
        self.mesh, self.shardings = mesh, shardings
        self.main, startup, self.loss = _program(train)
        self.exe, self.scope = fluid.Executor(fluid.CPUPlace()), core.Scope()
        self.exe.run(startup, scope=self.scope)
        self.feed = _feed()

    def step(self, **kw):
        lv, = self.exe.run(self.main, feed=self.feed, fetch_list=[self.loss],
                           scope=self.scope, mesh=self.mesh,
                           param_shardings=self.shardings, **kw)
        return np.asarray(lv).ravel()

    @property
    def block(self):
        return [v for v in self.exe._compiled_cache.values()
                if not isinstance(v, tuple) and v.mesh is self.mesh][-1]

    @property
    def state(self):
        cb = self.block
        return cb.mut_state + cb.ro_state

    def array(self, name):
        return self.scope.find_var(name).get_tensor().array

    def off_plan(self):
        """State names whose array in the scope does not lie as the
        block's plan says."""
        cb = self.block

        def on_plan(n, a):
            return isinstance(a, jax.Array) and a.sharding.is_equivalent_to(
                cb._planned_sharding(n, a), a.ndim)
        return [n for n in self.state if not on_plan(n, self.array(n))]


def _placed_total():
    fam = telemetry.REGISTRY.get("executor_state_arrays_placed_total")
    return 0 if fam is None else fam.value()


@pytest.fixture
def device_puts(monkeypatch):
    """What `jax.device_put` is handed while the fixture is live."""
    seen, real = [], jax.device_put

    def counting(x, *args, **kwargs):
        seen.append(x)
        return real(x, *args, **kwargs)
    monkeypatch.setattr(jax, "device_put", counting)
    return seen


@pytest.mark.parametrize("mesh_name", sorted(MESHES))
def test_steady_state_passes_the_scopes_own_arrays_through(mesh_name,
                                                           device_puts):
    """After two steps every state array the step takes IS the object
    the scope holds, and `device_put` sees the feeds and the key only."""
    run = _Run(build_mesh(**MESHES[mesh_name]))
    before = _placed_total()
    run.step()
    cb = run.block
    assert cb._placed == len(run.state) == _placed_total() - before
    run.step()
    assert cb._placed == 0 and run.off_plan() == []
    held = {n: run.array(n) for n in run.state}
    del device_puts[:]
    key = jax.random.key(0)
    mut, ro, feeds, _ = cb._place_inputs(
        run.scope, {n: run.array(n) for n in cb.feed_names}, key)
    assert cb._placed == 0 and _placed_total() - before == len(run.state)
    assert set(mut) | set(ro) == set(held)
    for n, a in {**mut, **ro}.items():
        assert a is held[n], n
    assert len(device_puts) == len(feeds) + 1 and device_puts[-1] is key
    assert all(f.sharding.spec[0] == "dp" for f in feeds.values())


def test_the_plan_is_made_once_a_block():
    """One `NamedSharding` a state name, decided on the first placement
    (the accumulator rule reads the array's rank) and kept."""
    run = _Run(build_mesh(**MESHES["dp2xmp2"]), TP)
    run.step()
    cb = run.block
    plan = dict(cb._placement_plan)
    assert set(plan) == set(run.state)

    def spec(prefix):  # accumulators are numbered by the process
        name, = [n for n in plan if n == prefix or (
            n.startswith(prefix + "_") and n[len(prefix) + 1:].isdigit())]
        return plan[name].spec
    assert spec("w1") == spec("w1_moment1") == TP["w1"]
    assert spec("w2") == spec("w2_moment2") == TP["w2"]
    # '<param>_<acc>' of another rank, and what no spec names: replicated
    assert spec("w1_beta1_pow_acc") == spec("b1") == P()
    run.step()
    assert all(cb._placement_plan[n] is sh for n, sh in plan.items())


@pytest.mark.parametrize("how", ["numpy", "one_device"])
def test_a_value_put_in_the_scope_is_placed_again(how):
    """A state var overwritten between steps (`set_value`: a checkpoint
    load, a user's assignment) goes through `device_put` to the plan's
    sharding, alone, and the losses are an untouched run's."""
    mesh = build_mesh(**MESHES["dp4"])
    untouched = _Run(mesh)
    want = [untouched.step() for _ in range(4)]
    run = _Run(mesh)
    got = [run.step(), run.step()]
    value = np.asarray(run.array("w1"))
    run.scope.find_var("w1").set_value(core.LoDTensor(
        value if how == "numpy" else jax.device_put(value,
                                                    jax.devices()[0])))
    assert run.off_plan() == ["w1"]
    before = _placed_total()
    got.append(run.step())
    assert run.block._placed == 1 and _placed_total() - before == 1
    assert run.off_plan() == []
    got.append(run.step())
    assert run.block._placed == 0 and _placed_total() - before == 1
    np.testing.assert_array_equal(got, want)


def test_dp_x_mp_losses_are_the_parents_and_every_array_is_accounted_for():
    """With `param_shardings` on dp2 x mp2 the step may hand a replicated
    bias back split over "mp" (no `out_shardings` pins it): such an array
    is placed again, as the parent placed every array, and counted. The
    losses are bit-equal to a run that places every array from the host
    every step, and are the parent commit's."""
    mesh = build_mesh(**MESHES["dp2xmp2"])
    run, every_step = _Run(mesh, TP), _Run(mesh, TP)
    losses, reference = [run.step()], [every_step.step()]
    for _ in range(2):
        off_plan = run.off_plan()
        assert set(off_plan) <= set(run.block.mut_state)
        losses.append(run.step())
        assert run.block._placed == len(off_plan)
        for n in every_step.state:
            every_step.scope.find_var(n).set_value(
                core.LoDTensor(np.asarray(every_step.array(n))))
        reference.append(every_step.step())
        assert every_step.block._placed == len(every_step.state)
    np.testing.assert_array_equal(losses, reference)
    np.testing.assert_allclose(np.ravel(losses), PARENT_DP2XMP2_LOSSES,
                               rtol=1e-6)
    # what the sharded weights' own accumulators come back as is the plan
    assert not [n for n in off_plan if n.startswith(("w1", "w2"))]


@pytest.mark.parametrize("mesh_name", ["dp4", "dp2xmp2"])
def test_no_use_after_donation(mesh_name):
    """Step k's overwritten inputs are donated (the scope's own buffers
    now, with no `device_put` in between): dead after the step, and the
    scope holds live arrays."""
    run = _Run(build_mesh(**MESHES[mesh_name]),
               TP if mesh_name == "dp2xmp2" else None)
    run.step()
    run.step()
    for _ in range(2):
        cb = run.block
        # what lies off the plan (dp x mp: a bias XLA handed back split)
        # is placed again: the copy is donated, not the scope's array
        replaced = run.off_plan()
        held = {n: run.array(n) for n in cb.mut_state if n not in replaced}
        read_only = {n: run.array(n) for n in cb.ro_state}
        run.step()
        assert len(held) >= 17 and all(a.is_deleted() for a in held.values())
        assert all(run.array(n) is not a for n, a in held.items())
        assert all(run.array(n) is a and not a.is_deleted()
                   for n, a in read_only.items())
        for n in run.state:
            assert not run.array(n).is_deleted(), n
            assert np.isfinite(np.asarray(run.array(n))).all(), n


def test_read_only_state_is_placed_once_and_left_in_the_scope():
    """No step writes read-only state back (a forward program's every
    parameter): the placed array takes its place in the scope, LoD
    kept, so the second run finds it there."""
    run = _Run(build_mesh(**MESHES["dp4"]), train=False)
    first = run.step()
    cb = run.block
    assert cb.mut_state == () and cb._placed == len(cb.ro_state) == 4
    held = {n: run.array(n) for n in cb.ro_state}
    assert run.off_plan() == []
    np.testing.assert_array_equal(run.step(), first)
    assert cb._placed == 0
    assert all(run.array(n) is a for n, a in held.items())


@pytest.mark.parametrize("mesh_name", [None, "dp4"])
def test_place_span_args_and_the_registry_counter_add_up(mesh_name):
    """`exe:place` carries `arrays` and `placed`; the registry's counter
    is the sum of `placed` over the steps. Off a mesh nothing is placed
    and the counter does not move."""
    run = _Run(mesh_name and build_mesh(**MESHES[mesh_name]))
    before = _placed_total()
    with profiler.profiler(state="CPU", profile_path=""):
        for _ in range(3):
            run.step()
        spans = [e["args"] for e in profiler.snapshot_events()
                 if e["name"] == "exe:place"]
    n = len(run.state)
    first = n if mesh_name else 0
    assert spans == [{"arrays": n, "placed": first},
                     {"arrays": n, "placed": 0}, {"arrays": n, "placed": 0}]
    assert _placed_total() - before == first


def test_a_window_places_once_and_leaves_the_state_placed():
    """`run_window` (the `n_steps` scan): one placement a window; the
    window after it passes everything through."""
    mesh = build_mesh(**MESHES["dp4"])
    run = _Run(mesh)
    run.feed = {n: np.stack([a, a]) for n, a in run.feed.items()}
    with profiler.profiler(state="CPU", profile_path=""):
        losses = [run.step(n_steps=2), run.step(n_steps=2)]
        spans = [e["args"] for e in profiler.snapshot_events()
                 if e["name"] == "exe:place"]
    n = len(run.state)
    assert spans == [{"arrays": n, "placed": n}, {"arrays": n, "placed": 0}]
    assert run.off_plan() == []
    per_step = _Run(mesh)
    want = [per_step.step() for _ in range(4)]
    np.testing.assert_allclose(np.ravel(losses), np.ravel(want), rtol=1e-6)
