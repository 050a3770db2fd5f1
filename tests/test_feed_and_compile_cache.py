"""The Executor's feed stage (`exe:feed` is `_as_lodtensor` alone: a host
array is uploaded every run, a `jax.Array` is wrapped where it lies) and
the FLAGS_compilation_cache_dir persistent-executable smoke test."""
import contextlib
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import core
from paddle_tpu.parallel.mesh import build_mesh


@contextlib.contextmanager
def _flags(**flags):
    prev = {k: core.globals_[k] for k in flags}
    fluid.set_flags(flags)
    try:
        yield
    finally:
        fluid.set_flags(prev)


def _build_scale(island=False):
    """out = 2 * x; with ``island`` a host op (py_func) sits between two
    compiled ops, so the block cannot compile whole."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", shape=[4], dtype="float32")
        if island:
            a = fluid.layers.scale(x, scale=4.0)
            b = main.global_block().create_var(name="feed_pyf_out",
                                               dtype="float32")
            fluid.layers.py_func(lambda t: t, a, b)
            out = fluid.layers.scale(b, scale=0.5)
        else:
            out = fluid.layers.scale(x, scale=2.0)
    return main, out


RUN_MODES = {
    "compiled": dict(FLAGS_executor_mode="compiled"),
    "segmented": dict(FLAGS_executor_mode="compiled",
                      FLAGS_executor_segmentation=True,
                      FLAGS_executor_seg_min_ops=1),
    "interpreted": dict(FLAGS_executor_mode="interpreted"),
}


@pytest.mark.parametrize("mode", sorted(RUN_MODES))
def test_same_ndarray_changed_in_place_is_uploaded_again(mode):
    """Nothing between the caller's array and the device remembers an
    earlier upload: the SAME ndarray object fed again after an in-place
    change (an element, then a row swap that keeps every sum) gives the
    new content, whichever way the block runs."""
    main, out = _build_scale(island=(mode == "segmented"))
    exe, scope = fluid.Executor(), core.Scope()
    x = np.asarray([[1., 2., 3., 4.], [5., 6., 7., 8.]], np.float32)
    with _flags(**RUN_MODES[mode]), fluid.scope_guard(scope):
        (r1,) = exe.run(main, feed={"x": x}, fetch_list=[out])
        assert exe._last_run_mode == mode
        x[0, 0] = 100.0
        (r2,) = exe.run(main, feed={"x": x}, fetch_list=[out])
        x[[0, 1]] = x[[1, 0]]
        (r3,) = exe.run(main, feed={"x": x}, fetch_list=[out])
    np.testing.assert_allclose(r1, [[2, 4, 6, 8], [10, 12, 14, 16]])
    np.testing.assert_allclose(r2, [[200, 4, 6, 8], [10, 12, 14, 16]])
    np.testing.assert_allclose(r3, [[10, 12, 14, 16], [200, 4, 6, 8]])


def _train_program():
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup):
        x = fluid.data("x", shape=[16], dtype="float32")
        y = fluid.data("y", shape=[1], dtype="int64")
        pred = fluid.layers.fc(x, 4, act="softmax")
        loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, y))
        fluid.optimizer.SGD(0.1).minimize(loss)
    return main, startup, loss


@pytest.mark.parametrize("mesh_name", ["no_mesh", "dp4"])
def test_a_jax_array_feed_is_the_device_resident_feed(mesh_name):
    """The way to feed without an upload is to feed a `jax.Array`: off a
    mesh the scope holds the very object that was fed (no host copy, no
    second device array), and on a mesh or off it the losses are the
    numpy feed's."""
    mesh = None if mesh_name == "no_mesh" else build_mesh(num_devices=4)
    rng = np.random.RandomState(0)
    host = {"x": rng.rand(64, 16).astype("float32"),
            "y": rng.randint(0, 4, (64, 1)).astype("int64")}
    resident = {k: jnp.asarray(v) for k, v in host.items()}

    def losses(feed):
        main, startup, loss = _train_program()
        exe, scope = fluid.Executor(fluid.CPUPlace()), core.Scope()
        exe.run(startup, scope=scope)
        got = [np.asarray(exe.run(main, feed=feed, fetch_list=[loss],
                                  scope=scope, mesh=mesh)[0]).ravel()[0]
               for _ in range(3)]
        return got, scope

    from_host, _ = losses(host)
    from_device, scope = losses(resident)
    assert from_device == from_host and from_host[2] < from_host[0]
    if mesh is None:
        for name, arr in resident.items():
            assert scope.find_var(name).get_tensor().array is arr


# ------------------------------------------ persistent compile cache
_CACHE_SCRIPT = r"""
import os, sys, json
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np
import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import core

main, startup = fluid.Program(), fluid.Program()
with fluid.program_guard(main, startup):
    x = fluid.data("x", shape=[8], dtype="float32")
    h = fluid.layers.fc(x, 8, act="relu")
    out = fluid.layers.reduce_sum(h)
exe = fluid.Executor()  # reads FLAGS_compilation_cache_dir from env
scope = core.Scope()
with fluid.scope_guard(scope):
    exe.run(startup)
    exe.run(main, feed={"x": np.ones((2, 8), np.float32)},
            fetch_list=[out])
cd = os.environ["FLAGS_compilation_cache_dir"]
entries = [f for f in os.listdir(cd) if not f.startswith(".")]
print(json.dumps({"entries": len(entries)}))
"""


def test_compilation_cache_dir_flag_cross_process(tmp_path):
    """FLAGS_compilation_cache_dir: the first Executor process populates
    the on-disk executable cache; a second fresh process runs the same
    program against it WITHOUT adding entries — every compile was served
    from disk (the cache is keyed by HLO hash, so a miss would write)."""
    cache_dir = str(tmp_path / "xla_cache")
    env = dict(os.environ, FLAGS_compilation_cache_dir=cache_dir,
               JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)  # the flag is the API then

    def run_once():
        out = subprocess.run([sys.executable, "-c", _CACHE_SCRIPT],
                             capture_output=True, text=True, env=env,
                             timeout=240,
                             cwd=os.path.dirname(os.path.dirname(
                                 os.path.abspath(__file__))))
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    first = run_once()
    if first["entries"] == 0:
        pytest.skip("backend does not persist executables on this box")
    second = run_once()
    assert second["entries"] == first["entries"], \
        "second process recompiled (cache entries grew) instead of " \
        "loading executables from the persistent cache"


_LATE_FLAG_SCRIPT = r"""
import os, sys, json
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np
import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import core

exe = fluid.Executor()  # constructed BEFORE the flag is set
core.set_flag("FLAGS_compilation_cache_dir", sys.argv[1])
main, startup = fluid.Program(), fluid.Program()
with fluid.program_guard(main, startup):
    x = fluid.data("x", shape=[8], dtype="float32")
    out = fluid.layers.reduce_sum(fluid.layers.fc(x, 8))
scope = core.Scope()
with fluid.scope_guard(scope):
    exe.run(startup)
    exe.run(main, feed={"x": np.ones((2, 8), np.float32)},
            fetch_list=[out])
entries = [f for f in os.listdir(sys.argv[1]) if not f.startswith(".")]
print(json.dumps({"entries": len(entries)}))
"""


def test_compilation_cache_flag_set_after_executor_ctor(tmp_path):
    """The flag is re-checked per run, not just at construction —
    setting it after `Executor()` exists must still enable the cache."""
    cache_dir = str(tmp_path / "late_cache")
    os.makedirs(cache_dir)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("FLAGS_compilation_cache_dir", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run([sys.executable, "-c", _LATE_FLAG_SCRIPT,
                          cache_dir],
                         capture_output=True, text=True, env=env,
                         timeout=240,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    if res["entries"] == 0:
        pytest.skip("backend does not persist executables on this box")


_PLACED_CACHE_SCRIPT = r"""
import json, os, sys
import numpy as np
import paddle_tpu.fluid as fluid
from paddle_tpu import inference
from paddle_tpu.fluid import core

used = inference.enable_compile_cache(sys.argv[1])
main, startup = fluid.Program(), fluid.Program()
with fluid.program_guard(main, startup):
    x = fluid.data("x", shape=[8], dtype="float32")
    out = fluid.layers.reduce_sum(fluid.layers.fc(x, 8))
scope = core.Scope()
with fluid.scope_guard(scope):
    exe = fluid.Executor()
    exe.run(startup)
    exe.run(main, feed={"x": np.ones((2, 8), np.float32)},
            fetch_list=[out])
print(json.dumps({"used": used, "env": os.environ.get(
    "JAX_COMPILATION_CACHE_DIR")}))
"""


def _entries(path):
    return [f for f in os.listdir(path) if not f.startswith(".")] \
        if os.path.isdir(path) else []


def _run_placed(argv_dir, env):
    out = subprocess.run([sys.executable, "-c", _PLACED_CACHE_SCRIPT,
                          argv_dir], capture_output=True, text=True,
                         env=env, timeout=240,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_cache_placed_from_outside_is_not_moved(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, whoever runs the process has
    placed the cache: the entries land there, and
    enable_compile_cache("elsewhere") moves neither them nor the
    variable."""
    placed, elsewhere = str(tmp_path / "placed"), str(tmp_path / "elsewhere")
    res = _run_placed(elsewhere, dict(os.environ, JAX_PLATFORMS="cpu",
                                      JAX_COMPILATION_CACHE_DIR=placed))
    assert res == {"used": placed, "env": placed}
    assert _entries(placed) and not _entries(elsewhere)


def test_cache_default_is_the_fixed_in_checkout_dir_when_unset(tmp_path):
    """Unset, the argument is the cache (FLAGS_compilation_cache_dir /
    set_optim_cache_dir stay the user's API) and the variable stays
    unset; what chip_smoke.py passes is the fixed <checkout>/.xla_cache:
    no temporary name, pid or time in it."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    mine = str(tmp_path / "mine")
    assert _run_placed(mine, env) == {"used": mine, "env": None}
    assert _entries(mine)

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-c",
         "import chip_smoke; print(chip_smoke.CACHE_DIR)"],
        capture_output=True, text=True, env=env, timeout=120, cwd=root)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == [os.path.join(root, ".xla_cache")]
