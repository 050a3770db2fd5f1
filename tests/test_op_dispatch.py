"""The one op dispatch (ops/registry.py ``run_op``): which kernel runs and
with which random key, the same on the compiled trace, the interpreter
and the dygraph tracer.

Each case of the table runs on the compiled path, on the interpreter and,
where the op exists there, in dygraph, and checks the result each gives
against what the rule says it must be."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import core, framework
from paddle_tpu.fluid.dygraph.base import VarBase
from paddle_tpu.ops.registry import OPS, out, register_op

MODES = ("compiled", "interpreted")
P = 0.5


@pytest.fixture
def dispatch_ops():
    """Two ops of this file's own: ``dispatch_noise`` (Out = X * U, U
    uniform from the op's key; generic grad dX = dOut * U, which is right
    only if the grad draws the forward's U) and ``dispatch_square`` with a
    registered ``dispatch_square_grad`` that answers 7 where the vjp
    would answer 2 X."""
    register_op("dispatch_noise", needs_rng=True, inputs=("X",))(
        lambda ins, attrs: out(Out=ins["X"][0] * jax.random.uniform(
            attrs["_rng"], ins["X"][0].shape)))
    register_op("dispatch_square", inputs=("X",))(
        lambda ins, attrs: out(Out=ins["X"][0] * ins["X"][0]))
    register_op("dispatch_square_grad")(
        lambda ins, attrs: {"X@GRAD": [jnp.full_like(ins["X"][0], 7.0)]})
    yield
    for t in ("dispatch_noise", "dispatch_square", "dispatch_square_grad"):
        OPS._map.pop(t)


def _append(op_type, x, attrs=None, slots=("Out",)):
    block = fluid.default_main_program().current_block()
    outs = {}
    for s in slots:
        v = block.create_var(name=framework.unique_name.generate(op_type),
                             dtype=x.dtype, shape=x.shape)
        v.stop_gradient = x.stop_gradient
        outs[s] = [v]
    block.append_op(type=op_type, inputs={"X": [x]},
                    outputs={s: [v.name for v in vs]
                             for s, vs in outs.items()},
                    attrs=dict(attrs or {}))
    return [outs[s][0] for s in slots]


def _static(mode, build, feed):
    """Build with ``build() -> fetch vars``, run start-up and one step
    under executor mode ``mode``; the fetched arrays."""
    core.set_flag("FLAGS_executor_mode", mode)
    try:
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 7
        with fluid.program_guard(main, startup):
            fetch = build()
        exe = fluid.Executor(fluid.CPUPlace())
        scope = core.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            return [np.asarray(a) for a in
                    exe.run(main, feed=feed, fetch_list=fetch)]
    finally:
        core.set_flag("FLAGS_executor_mode", "compiled")


def _dygraph(fn):
    """``fn(tracer)`` in a fresh dygraph guard; its result and how far
    it moved the tracer's random stream."""
    with fluid.dygraph.guard():
        tracer = framework._dygraph_tracer()
        start = tracer._rng_counter
        res = fn(tracer)
        return res, tracer._rng_counter - start


def _dy_dropout(attrs):
    def fn(tracer):
        x = VarBase(jnp.ones((4, 8), jnp.float32))
        outs = tracer.trace_op(
            "dropout", {"X": [x]},
            {"Out": [VarBase(None)], "Mask": [VarBase(None)]},
            dict(attrs, dropout_prob=P))
        return np.asarray(outs["Mask"][0]._array)
    return fn


def _static_dropout(attrs):
    def build():
        x = fluid.data("x", shape=[8], dtype="float32")
        return _append("dropout", x, dict(attrs, dropout_prob=P),
                       ("Out", "Mask"))[1:]
    return build


FEED = {"x": np.ones((4, 8), np.float32)}


def _keep(key):
    return np.asarray(jax.random.bernoulli(key, 1.0 - P, (4, 8)),
                      np.uint8)


def case_fix_seed():
    """``fix_seed`` draws from ``key(seed)`` everywhere; dygraph's stream
    does not move."""
    attrs = {"fix_seed": True, "seed": 5}
    want = _keep(jax.random.key(5))
    for mode in MODES:
        (mask,) = _static(mode, _static_dropout(attrs), FEED)
        np.testing.assert_array_equal(mask, want)
    mask, moved = _dygraph(_dy_dropout(attrs))
    np.testing.assert_array_equal(mask, want)
    assert moved == 0


def case_unseeded():
    """An unseeded rng op draws the stream's key at its index: the
    compiled trace and the interpreter draw the same mask, dygraph draws
    ``fold_in(key(FLAGS_seed), counter)`` and moves its stream by one."""
    masks = [_static(mode, _static_dropout({}), FEED)[0] for mode in MODES]
    np.testing.assert_array_equal(masks[0], masks[1])
    assert 0 < masks[0].sum() < masks[0].size

    def fn(tracer):
        key = jax.random.fold_in(
            jax.random.key(core.globals_["FLAGS_seed"]), tracer._rng_counter)
        return _dy_dropout({})(tracer), _keep(key)
    (mask, want), moved = _dygraph(fn)
    np.testing.assert_array_equal(mask, want)
    assert moved == 1


def case_generic_grad_replays_forward_key():
    """A generic ``<op>_grad`` of an rng op draws the forward's key
    (``_fwd_idx``): with X = 1, d sum(X * U) / dX = U = Out."""
    def build():
        x = fluid.data("x", shape=[8], dtype="float32")
        x.stop_gradient = False
        (o,) = _append("dispatch_noise", x)
        (gx,) = fluid.gradients([fluid.layers.reduce_sum(o)], [x])
        return [o, gx]
    for mode in MODES:
        o, gx = _static(mode, build, FEED)
        assert 0 < o.min() < o.max() < 1
        np.testing.assert_array_equal(gx, o)

    def fn(tracer):
        x = VarBase(jnp.ones((4, 8), jnp.float32), stop_gradient=False)
        o = tracer.trace_op("dispatch_noise", {"X": [x]},
                            {"Out": [VarBase(None)]}, {})
        tracer.trace_op("reduce_sum", {"X": [o]}, {"Out": [VarBase(None)]},
                        {"reduce_all": True}).backward()
        return np.asarray(o._array), np.asarray(x._grad)
    (o, gx), moved = _dygraph(fn)
    np.testing.assert_array_equal(gx, o)
    assert moved == 1


def case_registered_grad_kernel_wins():
    """A registered ``<op>_grad`` kernel runs, not the vjp of the
    forward (which would give 2 X = 2)."""
    def build():
        x = fluid.data("x", shape=[8], dtype="float32")
        x.stop_gradient = False
        (o,) = _append("dispatch_square", x)
        return fluid.gradients([fluid.layers.reduce_sum(o)], [x])
    for mode in MODES:
        (gx,) = _static(mode, build, FEED)
        np.testing.assert_array_equal(gx, np.full((4, 8), 7.0))

    def fn(tracer):
        x = VarBase(jnp.ones((4, 8), jnp.float32), stop_gradient=False)
        o = tracer.trace_op("dispatch_square", {"X": [x]},
                            {"Out": [VarBase(None)]}, {})
        tracer.trace_op("reduce_sum", {"X": [o]}, {"Out": [VarBase(None)]},
                        {"reduce_all": True}).backward()
        return np.asarray(x._grad)
    gx, _ = _dygraph(fn)
    np.testing.assert_array_equal(gx, np.full((4, 8), 7.0))


def case_grad_grad():
    """``<op>_grad_grad`` runs the vjp of the generic grad: one SGD step
    on a gradient penalty, mean((|d sum tanh(x W) / dx| - 1)^2), applies
    jax's own d penalty / dW."""
    X = np.random.RandomState(0).rand(4, 3).astype("float32")
    W0 = np.random.RandomState(1).rand(3, 2).astype("float32") - 0.5

    def build():
        x = fluid.data("x", shape=[3], dtype="float32")
        w = fluid.layers.create_parameter(
            [3, 2], "float32", name="gg_w",
            default_initializer=fluid.initializer.NumpyArrayInitializer(W0))
        d = fluid.layers.reduce_sum(
            fluid.layers.tanh(fluid.layers.matmul(x, w)))
        (gx,) = fluid.gradients([d], [x])
        nrm = fluid.layers.sqrt(fluid.layers.reduce_sum(
            fluid.layers.elementwise_mul(gx, gx), dim=1) + 1e-12)
        pen = fluid.layers.reduce_mean(fluid.layers.square(nrm - 1.0))
        fluid.optimizer.SGD(1.0).minimize(pen)
        return [w]

    def penalty(W):
        g = jax.vmap(jax.grad(lambda xv: jnp.sum(jnp.tanh(xv @ W))))(X)
        return jnp.mean((jnp.sqrt(jnp.sum(g * g, axis=1) + 1e-12) - 1) ** 2)
    want = W0 - np.asarray(jax.grad(penalty)(jnp.asarray(W0)))
    for mode in MODES:
        (w1,) = _static(mode, build, {"x": X})
        np.testing.assert_allclose(w1, want, rtol=1e-4, atol=1e-6)


def case_grad_grad_of_lod_op():
    """The ``_grad_grad`` of a needs_lod op hands the base kernel the
    LoD of its inputs (``_lod``), as its ``_grad`` does: a gradient
    penalty through ``sequence_pool`` (SQRT, sequences of 2 and 3) applies
    jax's own d penalty / dW."""
    X = np.random.RandomState(0).rand(5, 3).astype("float32")
    W0 = np.arange(9, dtype=np.float32).reshape(3, 3) / 10
    feed = core.LoDTensor(X)
    feed.set_recursive_sequence_lengths([[2, 3]])

    def build():
        x = fluid.data("x", shape=[3], dtype="float32", lod_level=1)
        x.stop_gradient = False
        h = fluid.layers.fc(x, 3, bias_attr=False, param_attr=fluid.ParamAttr(
            name="lod_w",
            initializer=fluid.initializer.NumpyArrayInitializer(W0)))
        o = fluid.layers.reduce_sum(fluid.layers.tanh(
            fluid.layers.sequence_pool(h, "sqrt")))
        (gx,) = fluid.gradients([o], [x])
        pen = fluid.layers.reduce_sum(fluid.layers.elementwise_mul(gx, gx))
        fluid.optimizer.SGD(1.0).minimize(pen)
        return ["lod_w"]

    seg = np.asarray([0, 0, 1, 1, 1])

    def penalty(W):
        def o(xv):
            p = jax.ops.segment_sum(xv @ W, seg, 2) / jnp.sqrt(
                jnp.asarray([[2.0], [3.0]]))
            return jnp.sum(jnp.tanh(p))
        g = jax.grad(o)(jnp.asarray(X))
        return jnp.sum(g * g)
    want = W0 - np.asarray(jax.grad(penalty)(jnp.asarray(W0)))
    for mode in MODES:
        (w1,) = _static(mode, build, {"x": feed})
        np.testing.assert_allclose(w1, want, rtol=1e-4, atol=1e-6)


def case_rng_already_set_is_kept():
    """An ``_rng`` the op already holds is the key it draws from; dygraph
    still moves its stream as for any unseeded rng op."""
    attrs = {"_rng": jax.random.key(11)}
    want = _keep(jax.random.key(11))
    for mode in MODES:
        (mask,) = _static(mode, _static_dropout(attrs), FEED)
        np.testing.assert_array_equal(mask, want)
    mask, moved = _dygraph(_dy_dropout(attrs))
    np.testing.assert_array_equal(mask, want)
    assert moved == 1


def case_unknown_op():
    """An op type no kernel serves raises one message on every path."""
    def build():
        x = fluid.data("x", shape=[8], dtype="float32")
        return _append("no_such_op", x)
    for mode in MODES:
        with pytest.raises(NotImplementedError,
                           match="operator 'no_such_op' is not registered"):
            _static(mode, build, FEED)

    def fn(tracer):
        tracer.trace_op("no_such_op", {"X": [VarBase(jnp.ones(2))]},
                        {"Out": [VarBase(None)]}, {})
    with pytest.raises(NotImplementedError,
                       match="operator 'no_such_op' is not registered"):
        _dygraph(fn)


CASES = [case_fix_seed, case_unseeded, case_generic_grad_replays_forward_key,
         case_registered_grad_kernel_wins, case_grad_grad,
         case_grad_grad_of_lod_op, case_rng_already_set_is_kept,
         case_unknown_op]


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.__name__[5:])
def test_op_dispatch(case, dispatch_ops):
    case()
