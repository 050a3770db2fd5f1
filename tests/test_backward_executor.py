"""append_backward + executor + optimizer end-to-end tests (reference:
unittests/test_backward.py, test_optimizer.py, tests/book/test_recognize_digits
convergence oracle)."""
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import core
from paddle_tpu.fluid.framework import Program, program_guard


def _build_mlp():
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.data("x", shape=[8], dtype="float32")
        label = fluid.data("y", shape=[1], dtype="int64")
        h = fluid.layers.fc(x, 16, act="relu")
        pred = fluid.layers.fc(h, 4, act="softmax")
        loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, label))
    return main, startup, x, label, loss


def test_append_backward_creates_grads():
    main, startup, x, label, loss = _build_mlp()
    with program_guard(main, startup):
        params_grads = fluid.append_backward(loss)
    assert len(params_grads) == 4  # 2 weights + 2 biases
    names = {p.name for p, g in params_grads}
    grads = {g.name for p, g in params_grads}
    for p, g in params_grads:
        assert g.name == p.name + "@GRAD"
    types = [op.type for op in main.global_block().ops]
    assert "mul_grad" in types
    assert "elementwise_add_grad" in types


def test_sgd_training_converges():
    np.random.seed(1)
    main, startup, x, label, loss = _build_mlp()
    with program_guard(main, startup):
        opt = fluid.optimizer.SGD(learning_rate=0.5)
        opt.minimize(loss)
    scope = core.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        X = np.random.rand(512, 8).astype("float32")
        W = np.random.rand(8, 4).astype("float32")
        Y = (X @ W).argmax(1).astype("int64").reshape(-1, 1)
        losses = []
        for i in range(40):
            idx = np.random.randint(0, 512, 64)
            lv, = exe.run(main, feed={"x": X[idx], "y": Y[idx]},
                          fetch_list=[loss])
            losses.append(float(lv[0]))
    assert losses[-1] < losses[0] * 0.7, (losses[0], losses[-1])


@pytest.mark.parametrize("opt_name", ["SGD", "Momentum", "Adam"])
def test_the_first_step_and_the_second_are_one_signature(opt_name):
    """The start-up program's state goes into step 1 committed, as the
    state a step hands back is: jit traces the step once, not once more
    on step 2 (a second compile of the same computation, a second entry
    in the compile cache)."""
    main, startup, x, label, loss = _build_mlp()
    with program_guard(main, startup):
        kwargs = {"momentum": 0.9} if opt_name == "Momentum" else {}
        getattr(fluid.optimizer, opt_name)(learning_rate=0.1,
                                           **kwargs).minimize(loss)
    scope = core.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    feed = {"x": np.ones((4, 8), "float32"), "y": np.zeros((4, 1), "int64")}
    exe.run(startup, scope=scope)
    for _ in range(3):
        exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    step = list(exe._compiled_cache.values())[-1]
    assert step._jitted._cache_size() == 1


@pytest.mark.parametrize("opt_name", ["Adam", "Momentum", "Adagrad",
                                      "RMSProp", "Lamb", "Adamax",
                                      "Adadelta", "DecayedAdagrad", "Ftrl",
                                      "LarsMomentum"])
def test_all_optimizers_step(opt_name):
    np.random.seed(2)
    main, startup, x, label, loss = _build_mlp()
    with program_guard(main, startup):
        kw = {}
        if opt_name in ("Momentum", "LarsMomentum"):
            kw["momentum"] = 0.9
        lr = 0.01 if opt_name in ("RMSProp", "Adam", "Lamb") else 0.1
        opt = getattr(fluid.optimizer, opt_name)(learning_rate=lr, **kw)
        opt.minimize(loss)
    scope = core.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        X = np.random.rand(64, 8).astype("float32")
        Y = np.random.randint(0, 4, (64, 1)).astype("int64")
        losses = []
        for i in range(8):
            lv, = exe.run(main, feed={"x": X, "y": Y}, fetch_list=[loss])
            losses.append(float(lv[0]))
        l0 = losses[0]
        assert np.isfinite(lv[0])
        # same batch repeated → the update must move the loss DOWN for
        # well-conditioned optimizers (Ftrl/Adadelta move slowly, so
        # just require change + no blowup). The horizon is 8 steps, not
        # 5: Adagrad's early lr/sqrt(moment) steps OSCILLATE on this
        # trajectory (1.4034 → 1.2950 → 1.4040 at step 5 — an
        # oscillation peak 6e-4 ABOVE the start — → 1.2352 by step 8,
        # compiled and interpreted paths bit-identical; op-level math
        # is pinned by test_op_battery_extra::test_adagrad), so a
        # 5-step endpoint read a descending-but-ringing trajectory as a
        # regression. This was the standing tier-1 "Adagrad flake".
        if opt_name in ("SGD", "Adam", "Momentum", "Adagrad", "RMSProp"):
            assert losses[-1] < l0, losses
            assert min(losses[1:]) < l0, losses
        else:
            assert losses[-1] != l0 and losses[-1] < l0 * 3


def test_lookahead_and_dgc_momentum():
    """Lookahead (reference optimizer.py:4138) + DGCMomentum (:1071)."""
    np.random.seed(7)
    for make in (lambda: fluid.optimizer.LookaheadOptimizer(
                     fluid.optimizer.SGD(0.3), alpha=0.5, k=3),
                 lambda: fluid.optimizer.DGCMomentumOptimizer(
                     0.1, momentum=0.9, rampup_begin_step=0)):
        main, startup, x, label, loss = _build_mlp()
        with program_guard(main, startup):
            make().minimize(loss)
        scope = core.Scope()
        exe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(scope):
            exe.run(startup)
            X = np.random.rand(64, 8).astype("float32")
            Y = np.random.randint(0, 4, (64, 1)).astype("int64")
            l0 = None
            for _ in range(7):
                lv, = exe.run(main, feed={"x": X, "y": Y},
                              fetch_list=[loss])
                if l0 is None:
                    l0 = float(lv[0])
            assert np.isfinite(lv[0]) and float(lv[0]) < l0


def test_interpreted_matches_compiled():
    """The eager interpreter is the correctness oracle for the jit path."""
    np.random.seed(3)
    results = {}
    for mode in ("compiled", "interpreted"):
        core.set_flag("FLAGS_executor_mode", mode)
        try:
            main, startup, x, label, loss = _build_mlp()
            main.random_seed = 7
            startup.random_seed = 7
            with program_guard(main, startup):
                fluid.optimizer.SGD(0.1).minimize(loss)
            scope = core.Scope()
            exe = fluid.Executor(fluid.CPUPlace())
            with fluid.scope_guard(scope):
                exe.run(startup)
                X = np.random.RandomState(0).rand(32, 8).astype("float32")
                Y = np.random.RandomState(1).randint(
                    0, 4, (32, 1)).astype("int64")
                ls = []
                for _ in range(3):
                    lv, = exe.run(main, feed={"x": X, "y": Y},
                                  fetch_list=[loss])
                    ls.append(float(lv[0]))
                results[mode] = ls
        finally:
            core.set_flag("FLAGS_executor_mode", "compiled")
    np.testing.assert_allclose(results["compiled"], results["interpreted"],
                               rtol=1e-5)


def test_gradient_accumulation_fanin():
    """var consumed by two ops gets summed grads (reference
    _addup_repetitive_outputs_)."""
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.data("x", shape=[4], dtype="float32")
        x.stop_gradient = False
        a = fluid.layers.relu(x)
        b1 = a * a
        b2 = a + a
        loss = fluid.layers.mean(b1 + b2)
        fluid.append_backward(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = core.Scope()
    with fluid.scope_guard(scope):
        xv = np.asarray([[1.0, 2.0, -1.0, 3.0]], np.float32)
        g, = exe.run(main, feed={"x": xv}, fetch_list=["x@GRAD"])
    # d/dx mean(x^2 + 2x) for x>0 = (2x + 2)/4 ; 0 for x<0
    expect = np.where(xv > 0, (2 * xv + 2) / 4.0, 0.0)
    np.testing.assert_allclose(g, expect, rtol=1e-5)


def test_lr_scheduler_in_program():
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.data("x", shape=[4], dtype="float32")
        h = fluid.layers.fc(x, 2)
        loss = fluid.layers.mean(h)
        lr = fluid.layers.exponential_decay(0.1, decay_steps=1,
                                            decay_rate=0.5)
        opt = fluid.optimizer.SGD(learning_rate=lr)
        opt.minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = core.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        X = np.random.rand(4, 4).astype("float32")
        lrs = []
        for _ in range(3):
            lv = exe.run(main, feed={"x": X}, fetch_list=[lr])
            lrs.append(float(lv[0][0]))
    # counter starts at 0 on first run? first value 0.1*0.5^1 since counter
    # increments before read (prepend increment). Just check halving:
    assert abs(lrs[1] / lrs[0] - 0.5) < 1e-5
    assert abs(lrs[2] / lrs[1] - 0.5) < 1e-5


def test_save_load_persistables(tmp_path):
    np.random.seed(4)
    main, startup, x, label, loss = _build_mlp()
    with program_guard(main, startup):
        fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = core.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        X = np.random.rand(16, 8).astype("float32")
        Y = np.random.randint(0, 4, (16, 1)).astype("int64")
        exe.run(main, feed={"x": X, "y": Y}, fetch_list=[loss])
        fluid.save_persistables(exe, str(tmp_path), main)
        l1, = exe.run(main, feed={"x": X, "y": Y}, fetch_list=[loss])
    scope2 = core.Scope()
    with fluid.scope_guard(scope2):
        fluid.load_persistables(exe, str(tmp_path), main)
        l2, = exe.run(main, feed={"x": X, "y": Y}, fetch_list=[loss])
    np.testing.assert_allclose(l1, l2, rtol=1e-5)


def test_use_prune_skips_untargeted_branches():
    """exe.run(use_prune=True) backward-slices to the fetch targets: a side
    branch writing a counter var must not execute (reference executor.py
    prune semantics)."""
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import core

    main, st = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, st), fluid.unique_name.guard():
        x = fluid.data("x", shape=[4], dtype="float32")
        kept = fluid.layers.scale(x, scale=2.0)
        # side branch: increments a persistable counter when executed
        blk = main.global_block()
        cnt = blk.create_var(name="side_counter", shape=[1],
                             dtype="float32", persistable=True)
        blk.append_op(type="increment", inputs={"X": [cnt.name]},
                      outputs={"Out": [cnt.name]}, attrs={"step": 1.0})
    exe = fluid.Executor()
    scope = core.Scope()
    xv = np.ones((2, 4), np.float32)
    with fluid.scope_guard(scope):
        exe.run(st)
        scope.var("side_counter").set_value(
            core.LoDTensor(np.zeros(1, np.float32)))
        (o,) = exe.run(main, feed={"x": xv}, fetch_list=[kept.name],
                       use_prune=True)
        after_pruned = float(np.asarray(
            scope.find_var("side_counter").get_tensor().array)[0])
        exe.run(main, feed={"x": xv}, fetch_list=[kept.name])
        after_full = float(np.asarray(
            scope.find_var("side_counter").get_tensor().array)[0])
    np.testing.assert_allclose(np.asarray(o), xv * 2.0)
    assert after_pruned == 0.0, "pruned run must skip the side branch"
    assert after_full == 1.0, "full run executes the side branch"


def _train_two_steps(build_mid):
    """fc1 → <mid> → fc2 → loss, SGD, 2 steps; returns fc1's weight
    before/after (the canary for grads flowing PAST a custom-grad op)."""
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import core

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", shape=[4], dtype="float32")
        h = fluid.layers.fc(x, 8)
        canary = main.all_parameters()[0].name  # fc1's weight
        h = build_mid(fluid, h)
        loss = fluid.layers.mean(fluid.layers.fc(h, 2))
        fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor()
    scope = core.Scope()
    X = np.random.RandomState(0).rand(3, 4).astype("float32")
    with fluid.scope_guard(scope):
        exe.run(startup)
        w0 = np.asarray(
            scope.find_var(canary).get_tensor().array).copy()
        for _ in range(2):
            (l,) = exe.run(main, feed={"x": X}, fetch_list=[loss])
        assert np.isfinite(np.asarray(l)).all()
        w1 = np.asarray(scope.find_var(canary).get_tensor().array)
    return w0, w1


def test_grads_flow_past_dropout():
    """Custom grad makers (dropout_grad has no "X" input slot) must
    still record their input's grad in grad_map — round-4 fix: before
    it, every op upstream of a dropout silently received EMPTY
    cotangents and models trained only their heads."""
    import numpy as np
    w0, w1 = _train_two_steps(
        lambda fluid, h: fluid.layers.dropout(
            h, 0.3, dropout_implementation="upscale_in_train"))
    assert np.abs(w1 - w0).max() > 0, \
        "fc upstream of dropout got no gradient"


def test_grads_flow_past_two_dropouts_in_series():
    """TWO custom-grad ops in series was the crash shape: the first
    (in reverse order) broke the grad chain, the second's maker then
    consumed an @EMPTY@ cotangent and the kernel crashed on None."""
    import numpy as np

    def mid(fluid, h):
        h = fluid.layers.dropout(h, 0.3,
                                 dropout_implementation="upscale_in_train")
        h = fluid.layers.fc(h, 8)
        return fluid.layers.dropout(
            h, 0.3, dropout_implementation="upscale_in_train")

    w0, w1 = _train_two_steps(mid)
    assert np.abs(w1 - w0).max() > 0


def test_grads_flow_past_quant_ste():
    """The quant STE maker emits a plain `assign` (grad input in slot
    "X", output in slot "Out") — both the desc-level grad recording and
    any *@GRAD-slot filter miss it; upstream params must still train."""
    import numpy as np

    def mid(fluid, h):
        helper = fluid.layer_helper.LayerHelper("fq", name="fq")
        out = helper.create_variable_for_type_inference("float32")
        out.shape = tuple(h.shape)
        scale = helper.create_variable_for_type_inference("float32")
        scale.shape = (1,)
        helper.append_op(type="fake_quantize_dequantize_abs_max",
                         inputs={"X": [h]},
                         outputs={"Out": [out], "OutScale": [scale]},
                         attrs={"bit_length": 8})
        return out

    w0, w1 = _train_two_steps(mid)
    assert np.abs(w1 - w0).max() > 0, \
        "fc upstream of fake_quantize got no gradient"


def test_static_gradients_of_gradients_penalty():
    """Static double grad (reference partial_grad_engine.cc role):
    penalty = mean((|d(sum tanh(x@w))/dx|_2 - 1)^2); minimizing it must
    update w with d(penalty)/dw matching central finite differences —
    the grad ops from fluid.gradients() are differentiated by the
    second append_backward sweep (*_grad_grad nested vjp)."""
    import numpy as np
    import jax.numpy as jnp
    import jax as _jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import core

    rng = np.random.RandomState(0)
    X = rng.rand(4, 3).astype("float32")
    W0 = (rng.rand(3, 2).astype("float32") - 0.5)
    lr = 0.5

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", shape=[3], dtype="float32")
        w = fluid.layers.create_parameter(
            [3, 2], "float32", name="critic_w",
            default_initializer=fluid.initializer.NumpyArrayInitializer(W0))
        d_out = fluid.layers.reduce_sum(
            fluid.layers.tanh(fluid.layers.matmul(x, w)))
        (gx,) = fluid.gradients([d_out], [x])
        nrm = fluid.layers.sqrt(fluid.layers.reduce_sum(
            fluid.layers.elementwise_mul(gx, gx), dim=1) + 1e-12)
        pen = fluid.layers.reduce_mean(fluid.layers.square(nrm - 1.0))
        fluid.optimizer.SGD(lr).minimize(pen)

    exe = fluid.Executor()
    scope = core.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        (p0,) = exe.run(main, feed={"x": X}, fetch_list=[pen])
        w1 = np.asarray(
            scope.find_var("critic_w").get_tensor().array).copy()
    step = (W0 - w1) / lr  # the applied gradient

    def penalty_value(Wnp):
        def p(W):
            def D(xv):
                return jnp.sum(jnp.tanh(xv @ W))
            g = _jax.vmap(_jax.grad(D))(jnp.asarray(X))
            nr = jnp.sqrt(jnp.sum(g * g, axis=1) + 1e-12)
            return jnp.mean((nr - 1.0) ** 2)
        return float(p(jnp.asarray(Wnp)))

    eps = 1e-3
    fd = np.zeros_like(W0)
    for i in range(W0.shape[0]):
        for j in range(W0.shape[1]):
            Wp, Wm = W0.copy(), W0.copy()
            Wp[i, j] += eps
            Wm[i, j] -= eps
            fd[i, j] = (penalty_value(Wp) - penalty_value(Wm)) / (2 * eps)
    np.testing.assert_allclose(step, fd, rtol=5e-3, atol=5e-4)
    np.testing.assert_allclose(float(np.asarray(p0).ravel()[0]),
                               penalty_value(W0), rtol=1e-5)


def test_rng_op_inside_cond_routes_to_interpreter():
    """Compiled conditional_block traces BOTH branches and mask-merges;
    an rng op (dropout) in a branch would draw in the untaken branch
    too. Such programs must take the interpreter's single-branch
    semantics (round-4 fix, VERDICT r03 item 4; reference
    conditional_block_op.cc runs only the taken branch) — and the
    untaken dropout must not perturb the taken branch's value."""
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import core
    from paddle_tpu.fluid.executor import _ops_compilable

    def build(with_dropout_in_cond):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.data("x", shape=[4], dtype="float32")
            pred = fluid.data("p", shape=[1], dtype="bool")

            def tbranch():
                return fluid.layers.scale(x, scale=2.0)

            def fbranch():
                h = fluid.layers.dropout(x, 0.5) \
                    if with_dropout_in_cond else x
                return fluid.layers.scale(h, scale=-1.0)

            out = fluid.layers.cond(pred, tbranch, fbranch)
        return main, startup, out

    main, startup, out = build(True)
    assert not _ops_compilable(main.global_block().ops)
    mainc, startupc, outc = build(False)
    assert _ops_compilable(mainc.global_block().ops)

    X = np.arange(8, dtype="float32").reshape(2, 4)
    P = np.array([True])
    exe = fluid.Executor()
    scope = core.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        (o,) = exe.run(main, feed={"x": X, "p": P}, fetch_list=[out])
    # taken (true) branch: exact 2x regardless of the dropout in the
    # untaken branch
    np.testing.assert_allclose(np.asarray(o), 2 * X)
    # the rng-in-cond block must NOT take the whole-block compiled path
    # (both-branch tracing would draw rng in the untaken branch); the
    # segmented path is fine — its conditional runs as an interpreted
    # island with single-branch semantics
    from paddle_tpu.fluid.executor import _CompiledBlock
    for k, v in exe._compiled_cache.items():
        if k[0] == id(main):
            assert not (type(v) is _CompiledBlock), \
                "program with rng-in-cond was whole-block compiled"


def test_run_n_steps_scanned_matches_loop():
    """exe.run(n_steps=K) executes K optimizer steps inside ONE
    dispatched lax.scan; the stacked per-step losses and the final
    weights must match K separate run() calls (same feeds)."""
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import core

    def build():
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 11
        with fluid.program_guard(main, startup):
            x = fluid.data("x", shape=[6], dtype="float32")
            y = fluid.data("y", shape=[1], dtype="float32")
            h = fluid.layers.fc(x, 8, act="tanh")
            pred = fluid.layers.fc(h, 1)
            loss = fluid.layers.mean(
                fluid.layers.square_error_cost(pred, y))
            fluid.optimizer.Momentum(0.05, 0.9).minimize(loss)
        return main, startup, loss

    rng = np.random.RandomState(0)
    X = rng.rand(8, 6).astype("float32")
    Y = rng.rand(8, 1).astype("float32")
    K = 6

    main, startup, loss = build()
    exe = fluid.Executor()
    s1 = core.Scope()
    loop_losses = []
    with fluid.scope_guard(s1):
        exe.run(startup)
        for _ in range(K):
            (l,) = exe.run(main, feed={"x": X, "y": Y}, fetch_list=[loss])
            loop_losses.append(float(np.asarray(l).ravel()[0]))
        w_loop = np.asarray(
            s1.find_var(main.all_parameters()[0].name)
            .get_tensor().array).copy()

    main2, startup2, loss2 = build()
    exe2 = fluid.Executor()
    s2 = core.Scope()
    with fluid.scope_guard(s2):
        exe2.run(startup2)
        (stacked,) = exe2.run(main2, feed={"x": X, "y": Y},
                              fetch_list=[loss2], n_steps=K)
        w_scan = np.asarray(
            s2.find_var(main2.all_parameters()[0].name)
            .get_tensor().array)
    stacked = np.asarray(stacked).ravel()
    assert stacked.shape == (K,)
    np.testing.assert_allclose(stacked, loop_losses, rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(w_scan, w_loop, rtol=2e-5, atol=1e-6)


def test_recompute_optimizer_remat_segments():
    """RecomputeOptimizer checkpoints lower onto jax.checkpoint + vjp
    span replacement (reference optimizer.py:3850 rematerialization):
    per-step losses and trained weights must match the plain run, the
    compiled step must carry remat barriers in its jaxpr, and a weight
    two segments share is lowered too (since PR 32: each segment's vjp
    gives its part, summed where the backward's fan-in sums them)."""
    import warnings as _w
    import numpy as np
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import core

    def build(use_remat, tied=False):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 5
        with fluid.program_guard(main, startup):
            x = fluid.data("x", shape=[6], dtype="float32")
            y = fluid.data("y", shape=[1], dtype="float32")
            h = fluid.layers.fc(x, 16, act="tanh")
            ck = []
            for i in range(3):
                nm = "rm_shared" if tied else f"rm_{i}"
                h = fluid.layers.fc(
                    h, 16, act="tanh",
                    param_attr=fluid.ParamAttr(name=nm + "_w"),
                    bias_attr=False)
                ck.append(h)
            pred = fluid.layers.fc(h, 1)
            loss = fluid.layers.mean(
                fluid.layers.square_error_cost(pred, y))
            if use_remat:
                opt = fluid.optimizer.RecomputeOptimizer(
                    fluid.optimizer.SGD(0.1))
                opt._set_checkpoints(ck[:-1])  # 2 boundaries -> 2 segs
                opt.minimize(loss)
            else:
                fluid.optimizer.SGD(0.1).minimize(loss)
        return main, startup, loss

    rng = np.random.RandomState(0)
    X = rng.rand(8, 6).astype("float32")
    Y = rng.rand(8, 1).astype("float32")

    def train(main, startup, loss, steps=5):
        exe = fluid.Executor()
        scope = core.Scope()
        out = []
        with fluid.scope_guard(scope):
            exe.run(startup)
            for _ in range(steps):
                (l,) = exe.run(main, feed={"x": X, "y": Y},
                               fetch_list=[loss])
                out.append(float(np.asarray(l).ravel()[0]))
            name = next(n for n in ("rm_1_w", "rm_shared_w")
                        if scope.find_var(n))
            w = np.asarray(scope.find_var(name).get_tensor().array).copy()
        return out, w, exe, scope

    plain, w_plain, _, _ = train(*build(False))
    with _w.catch_warnings():
        _w.simplefilter("error")  # a fallback warning fails the test
        remat, w_remat, exe, scope = train(*build(True))
    np.testing.assert_allclose(remat, plain, rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(w_remat, w_plain, rtol=2e-5, atol=1e-6)
    # the compiled step really contains remat barriers
    cb = list(exe._compiled_cache.values())[-1]
    assert cb._remat_plan is not None
    mut = {n: scope.find_var(n).get_tensor().array
           for n in cb.mut_state}
    ro = {n: scope.find_var(n).get_tensor().array
          for n in cb.ro_state}
    feeds = {"x": X, "y": Y}
    jaxpr = jax.make_jaxpr(cb._step)(mut, ro, feeds, jax.random.key(0))
    assert "remat" in str(jaxpr)

    # tied weights across segments: lowered, and trained as the plain run
    tied_plain, w_tied_plain, _, _ = train(*build(False, tied=True))
    with _w.catch_warnings():
        _w.simplefilter("error")
        tied_losses, w_tied, exe2, _ = train(*build(True, tied=True))
    assert list(exe2._compiled_cache.values())[-1]._remat_plan is not None
    np.testing.assert_allclose(tied_losses, tied_plain, rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(w_tied, w_tied_plain, rtol=2e-5, atol=1e-6)


def test_recompute_segment_keeps_state_writebacks():
    """A mutable-state write INSIDE a remat segment (batch_norm running
    stats) must reach the scope — segment boundaries include state
    writebacks, not just forward-consumed activations."""
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import core

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", shape=[6], dtype="float32")
        y = fluid.data("y", shape=[1], dtype="float32")
        h = fluid.layers.fc(x, 8, act="tanh")
        ck1 = h
        h = fluid.layers.fc(h, 8, bias_attr=False)
        h = fluid.layers.batch_norm(h)   # running stats write in-segment
        h = fluid.layers.tanh(h)
        ck2 = h
        h = fluid.layers.fc(h, 8, act="tanh")
        pred = fluid.layers.fc(h, 1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        opt = fluid.optimizer.RecomputeOptimizer(fluid.optimizer.SGD(0.1))
        opt._set_checkpoints([ck1, ck2])
        opt.minimize(loss)
    bn_op = next(op for op in main.global_block().ops
                 if op.type == "batch_norm")
    mean_name = bn_op.output("MeanOut")[0]
    exe = fluid.Executor()
    scope = core.Scope()
    rng = np.random.RandomState(0)
    X = rng.rand(8, 6).astype("float32") + 3.0  # nonzero mean
    Y = rng.rand(8, 1).astype("float32")
    with fluid.scope_guard(scope):
        exe.run(startup)
        m0 = np.asarray(scope.find_var(mean_name)
                        .get_tensor().array).copy()
        for _ in range(3):
            exe.run(main, feed={"x": X, "y": Y}, fetch_list=[loss])
        m1 = np.asarray(scope.find_var(mean_name).get_tensor().array)
    cb = list(exe._compiled_cache.values())[-1]
    assert cb._remat_plan is not None, "remat plan did not engage"
    assert np.abs(m1 - m0).max() > 1e-6, \
        "running mean froze — in-segment state write was dropped"
