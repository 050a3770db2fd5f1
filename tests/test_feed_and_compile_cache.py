"""FLAGS_feed_device_cache coverage (ISSUE 2 satellite: hit skips
re-upload, stale in-place mutations are detected, off-path unchanged)
and the FLAGS_compilation_cache_dir persistent-executable smoke test."""
import contextlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import core, executor as executor_mod


@contextlib.contextmanager
def _feed_cache(enabled):
    prev = core.globals_["FLAGS_feed_device_cache"]
    core.set_flag("FLAGS_feed_device_cache", enabled)
    try:
        yield
    finally:
        core.set_flag("FLAGS_feed_device_cache", prev)


@contextlib.contextmanager
def _count_uploads():
    """Count _as_lodtensor calls from Executor.run's feed path — a feed
    cache HIT returns the pinned device tensor without calling it."""
    calls = []
    orig = executor_mod._as_lodtensor

    def counting(data, place):
        calls.append(1)
        return orig(data, place)
    executor_mod._as_lodtensor = counting
    try:
        yield calls
    finally:
        executor_mod._as_lodtensor = orig


def _build_scale():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", shape=[4], dtype="float32")
        out = fluid.layers.scale(x, scale=2.0)
    return main, startup, out


def test_feed_cache_hit_skips_reupload():
    main, startup, out = _build_scale()
    exe = fluid.Executor()
    scope = core.Scope()
    x = np.ones((2, 4), np.float32)
    with _feed_cache(True), fluid.scope_guard(scope):
        with _count_uploads() as calls:
            exe.run(main, feed={"x": x}, fetch_list=[out])
            first = len(calls)
            assert first >= 1
            exe.run(main, feed={"x": x}, fetch_list=[out])
            assert len(calls) == first  # same array, same content: HIT
        # the cache pinned the device tensor for this name
        assert exe._feed_cache["x"][2] is x


def test_feed_cache_detects_inplace_mutation():
    """The CRC fingerprint catches a stale entry: mutating the SAME
    ndarray in place must re-upload and compute on the new contents."""
    main, startup, out = _build_scale()
    exe = fluid.Executor()
    scope = core.Scope()
    x = np.ones((2, 4), np.float32)
    with _feed_cache(True), fluid.scope_guard(scope):
        (r1,) = exe.run(main, feed={"x": x}, fetch_list=[out])
        np.testing.assert_allclose(r1, 2.0)
        x[:] = 3.0  # in-place: same id, same buffer address
        (r2,) = exe.run(main, feed={"x": x}, fetch_list=[out])
        np.testing.assert_allclose(r2, 6.0)  # stale device copy NOT used


def test_feed_cache_off_path_uploads_every_run():
    main, startup, out = _build_scale()
    exe = fluid.Executor()
    scope = core.Scope()
    x = np.ones((2, 4), np.float32)
    with _feed_cache(False), fluid.scope_guard(scope):
        with _count_uploads() as calls:
            exe.run(main, feed={"x": x}, fetch_list=[out])
            exe.run(main, feed={"x": x}, fetch_list=[out])
            assert len(calls) == 2  # no cache: one upload per run
        assert not hasattr(exe, "_feed_cache") or \
            "x" not in getattr(exe, "_feed_cache", {})


def test_feed_cache_fresh_arrays_stop_fingerprinting():
    """Names fed a fresh ndarray every step (the dataloader shape) go
    'uncacheable' after a short miss streak instead of CRC-scanning
    forever."""
    main, startup, out = _build_scale()
    exe = fluid.Executor()
    scope = core.Scope()
    with _feed_cache(True), fluid.scope_guard(scope):
        for i in range(executor_mod.Executor._FEED_CACHE_MISS_LIMIT + 2):
            exe.run(main, feed={"x": np.full((2, 4), float(i),
                                             np.float32)},
                    fetch_list=[out])
        assert exe._feed_cache["x"] == "uncacheable"


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
    unset; what bench.py and chip_smoke.py pass is the fixed
    <checkout>/.xla_cache — no temporary name, pid or time in it."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    mine = str(tmp_path / "mine")
    assert _run_placed(mine, env) == {"used": mine, "env": None}
    assert _entries(mine)

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-c", "import bench, chip_smoke; "
         "print(bench.CACHE_DIR); print(chip_smoke.CACHE_DIR)"],
        capture_output=True, text=True, env=env, timeout=120, cwd=root)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == [os.path.join(root, ".xla_cache")] * 2
