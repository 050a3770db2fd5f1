"""Inference engine tests: save_inference_model → AnalysisPredictor round
trip (reference: inference/tests/api + tests/unittests/
test_inference_model_io.py)."""
import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu import inference
from paddle_tpu.fluid import core


def train_and_save(dirname):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", shape=[4], dtype="float32")
        y = fluid.data("y", shape=[1], dtype="float32")
        pred = fluid.layers.fc(x, 1, param_attr=fluid.ParamAttr(name="w"))
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor()
    scope = core.Scope()
    rng = np.random.RandomState(0)
    X = rng.rand(16, 4).astype("float32")
    W = np.array([[1.0], [2.0], [-1.0], [0.5]], np.float32)
    Y = X @ W
    with fluid.scope_guard(scope):
        exe.run(startup)
        for _ in range(60):
            exe.run(main, feed={"x": X, "y": Y}, fetch_list=[loss])
        fluid.io.save_inference_model(dirname, ["x"], [pred], exe, main)
        (out,) = exe.run(main, feed={"x": X, "y": Y}, fetch_list=[pred])
    return X, out


def test_predictor_matches_training_forward(tmp_path):
    d = str(tmp_path / "model")
    X, want = train_and_save(d)
    config = inference.Config(d)
    predictor = inference.create_predictor(config)
    assert predictor.get_input_names() == ["x"]
    inp = predictor.get_input_handle("x")
    inp.copy_from_cpu(X)
    predictor.run()
    out = predictor.get_output_handle(predictor.get_output_names()[0])
    got = out.copy_to_cpu()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_predictor_run_list_api_and_clone(tmp_path):
    d = str(tmp_path / "model")
    X, want = train_and_save(d)
    predictor = inference.create_predictor(inference.Config(d))
    (got,) = predictor.run([X])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    clone = predictor.clone()
    (got2,) = clone.run([X[:3]])
    np.testing.assert_allclose(got2, want[:3], rtol=1e-5, atol=1e-6)


def test_load_inference_model_executor_path(tmp_path):
    """The classic fluid path: load_inference_model + exe.run (reference
    io.py usage), including pruning of train-only vars."""
    d = str(tmp_path / "model")
    X, want = train_and_save(d)
    exe = fluid.Executor()
    scope = core.Scope()
    with fluid.scope_guard(scope):
        prog, feeds, fetches = fluid.io.load_inference_model(d, exe)
        assert feeds == ["x"]
        (got,) = exe.run(prog, feed={"x": X}, fetch_list=fetches)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_predictor_from_memory_buffers_golden_format():
    """SetModelBuffer path: serve a model whose ProgramDesc + params are
    reference-format byte buffers (the golden fixtures were produced
    independently via protoc over the reference framework.proto)."""
    import os
    from paddle_tpu import inference
    fix = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures")
    prog_bytes = open(os.path.join(fix, "golden_fc.program.pb"),
                      "rb").read()
    params = (open(os.path.join(fix, "golden_fc_b.tensor"), "rb").read()
              + open(os.path.join(fix, "golden_fc_w.tensor"), "rb").read())
    # params stream order = sorted persistable names: fc_b then fc_w
    cfg = inference.Config()
    cfg.set_model_buffer(prog_bytes, params)
    assert cfg.model_from_memory()
    pred = inference.create_predictor(cfg)
    exp = np.load(os.path.join(fix, "golden_expected.npz"))
    x = np.random.RandomState(3).rand(5, 4).astype("float32")
    (out,) = pred.run([x])
    np.testing.assert_allclose(out, x @ exp["w"] + exp["b"],
                               rtol=1e-5, atol=1e-6)


def test_predictor_clone_shares_weights(tmp_path):
    from paddle_tpu import inference
    d = str(tmp_path / "m1")
    train_and_save(d)
    cfg = inference.Config(d)
    p1 = inference.create_predictor(cfg)
    p2 = p1.clone()
    assert p2._scope is p1._scope  # zero weight duplication
    x = np.random.rand(2, 4).astype("float32")
    np.testing.assert_allclose(p1.run([x])[0], p2.run([x])[0], rtol=1e-6)
    pool = inference.PredictorPool(cfg, size=3)
    assert pool.size() == 3
    np.testing.assert_allclose(pool.retrieve(2).run([x])[0],
                               p1.run([x])[0], rtol=1e-6)


def test_pass_builder_customization(tmp_path):
    from paddle_tpu import inference
    d = str(tmp_path / "m2")
    train_and_save(d)
    cfg = inference.Config(d)
    pb = cfg.pass_builder()
    n0 = len(pb.all_passes())
    pb.delete_pass("fc_fuse_pass")
    assert len(pb.all_passes()) == n0 - 1
    pred = inference.create_predictor(cfg)
    # without fc_fuse_pass the mul+elementwise_add stay decomposed
    types = [op.type for op in pred._program.global_block().ops]
    assert "fc" not in types and "mul" in types
    x = np.random.rand(2, 4).astype("float32")
    assert pred.run([x])[0].shape == (2, 1)
    import pytest
    with pytest.raises(ValueError):
        pb.append_pass("not_a_real_pass")


def test_predictor_misc_api(tmp_path):
    from paddle_tpu import inference
    d = str(tmp_path / "m3")
    train_and_save(d)
    cfg = inference.Config(d)
    cfg.enable_bf16()
    assert cfg.bf16_enabled()
    pred = inference.create_predictor(cfg)
    shapes = pred.get_input_tensor_shape()
    assert list(shapes) == pred.get_input_names()
    x = np.random.rand(2, 4).astype("float32")
    y1 = pred.run([x])[0]
    pred.try_shrink_memory()
    y2 = pred.run([x])[0]
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32), rtol=1e-2)
    from paddle_tpu.fluid import core
    core.set_flag("FLAGS_use_bf16_matmul", False)  # reset global


def test_predictor_aot_compile_cache_cross_process(tmp_path):
    """set_optim_cache_dir (reference analysis_config.cc SetOptimCacheDir
    / TensorRT engine-cache role): a SECOND process loading the same
    model must hit the persistent XLA executable cache instead of
    recompiling. The child reports jax's own 'compilation cache hit'
    log plus its outputs; outputs must also match across processes."""
    import json
    import subprocess
    import sys

    model_dir = str(tmp_path / "model")
    cache_dir = str(tmp_path / "xla_cache")
    build = """
import json, logging, sys
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import core
import paddle_tpu.inference as infer

model_dir, cache_dir, make = MODEL_DIR, CACHE_DIR, MAKE
if make:
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", shape=[64], dtype="float32")
        h = x
        for i in range(4):
            h = fluid.layers.fc(h, 64, act="relu")
        out = fluid.layers.fc(h, 8, act="softmax")
    exe = fluid.Executor()
    scope = core.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        fluid.io.save_inference_model(model_dir, ["x"], [out], exe,
                                      main_program=main)

records = []
h = logging.Handler()
h.emit = lambda r: records.append(r.getMessage())
logging.getLogger("jax._src.compiler").addHandler(h)
logging.getLogger("jax._src.compiler").setLevel(logging.DEBUG)

cfg = infer.Config(model_dir)
cfg.set_optim_cache_dir(cache_dir)
pred = infer.create_predictor(cfg)
X = np.linspace(0, 1, 2 * 64, dtype="float32").reshape(2, 64)
(y,) = pred.run([X])
hit = any("compilation cache hit" in m for m in records)
print(json.dumps({"hit": hit, "y": np.asarray(y).ravel().tolist()}))
"""
    build = build.replace("MODEL_DIR", repr(model_dir)) \
                 .replace("CACHE_DIR", repr(cache_dir))
    env = dict(__import__("os").environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)  # cache_dir is the API then
    out1 = subprocess.run([sys.executable, "-c",
                           build.replace("MAKE", "True")],
                          capture_output=True, text=True, env=env,
                          timeout=300)
    assert out1.returncode == 0, out1.stderr[-2000:]
    r1 = json.loads(out1.stdout.strip().splitlines()[-1])
    assert __import__("os").listdir(cache_dir), "no cache entries written"
    out2 = subprocess.run([sys.executable, "-c",
                           build.replace("MAKE", "False")],
                          capture_output=True, text=True, env=env,
                          timeout=300)
    assert out2.returncode == 0, out2.stderr[-2000:]
    r2 = json.loads(out2.stdout.strip().splitlines()[-1])
    assert r2["hit"], "second process recompiled instead of cache hit"
    np.testing.assert_allclose(r1["y"], r2["y"], rtol=1e-6)
