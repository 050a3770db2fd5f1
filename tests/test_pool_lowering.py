"""pool2d / pool3d as XLA's windowed reduction (`nn_ops._window_pool`):
forward and gradient against a plain NumPy loop, the tie rule of the max
form's gradient, and the lowered text that pins the mechanism.

The tie rule stated here is the reference's (PaddlePaddle v1.7,
paddle/fluid/operators/math/pooling.cc, MaxPool2dGradFunctor): the FIRST
input of a window, in row-major order, that equals the window's output
takes the whole gradient; padding takes none."""
import itertools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.ops import nn_ops
from paddle_tpu.ops.registry import OPS, run_generic_grad


def _pool_np(x, ptype, exclusive, ksize, strides, pads, g=None):
    """x: [N, C, *spatial]. Returns (out, dX for the cotangent g or None)."""
    nd = len(ksize)
    sp = x.shape[2:]
    osp = [(sp[i] + sum(pads[i]) - ksize[i]) // strides[i] + 1
           for i in range(nd)]
    out = np.zeros(x.shape[:2] + tuple(osp), x.dtype)
    dx = None if g is None else np.zeros(x.shape, np.float64)
    for n, c in itertools.product(range(x.shape[0]), range(x.shape[1])):
        for o in itertools.product(*[range(s) for s in osp]):
            taps = [p for p in itertools.product(*[
                range(o[i] * strides[i] - pads[i][0],
                      o[i] * strides[i] - pads[i][0] + ksize[i])
                for i in range(nd)])
                if all(0 <= p[i] < sp[i] for i in range(nd))]
            vals = [x[(n, c) + p] for p in taps]
            if ptype == "max":
                best = 0
                for t in range(1, len(vals)):  # strict: the first max stays
                    if vals[t] > vals[best]:
                        best = t
                out[(n, c) + o] = vals[best]
                if g is not None:
                    dx[(n, c) + taps[best]] += g[(n, c) + o]
            else:
                div = max(len(taps), 1) if exclusive else int(np.prod(ksize))
                out[(n, c) + o] = np.sum(vals, dtype=np.float64) / div
                if g is not None:
                    for p in taps:
                        dx[(n, c) + p] += g[(n, c) + o] / div
    return out, dx


def _run(op, x, attrs, g=None):
    """The op's registered kernel, and its grad op's path (run_generic_grad)."""
    full = dict(OPS.get(op).attr_defaults or {})
    full.update(attrs)
    o = OPS.get(op).kernel({"X": [jnp.asarray(x)]}, full)["Out"][0]
    if g is None:
        return np.asarray(o), None
    dx = run_generic_grad(
        op, {"X": [jnp.asarray(x)], "Out": [o], "Out@GRAD": [jnp.asarray(g)]},
        full, ["X@GRAD"], ["X"])["X@GRAD"][0]
    return np.asarray(o), np.asarray(dx)


def _distinct(shape, seed):
    """Well-separated values: no window holds a tie."""
    r = np.random.RandomState(seed)
    return (r.permutation(int(np.prod(shape))).reshape(shape) * 0.01
            - 1.0).astype("float32")


# (id, op, input shape as the op takes it, attrs, the pads the attrs mean)
CASES = [
    ("stem_3x3_s2_p1", "pool2d", (2, 3, 8, 8),
     dict(ksize=[3, 3], strides=[2, 2], paddings=[1, 1]),
     [(1, 1), (1, 1)]),
    ("2x2_s2_p0", "pool2d", (2, 3, 6, 6),
     dict(ksize=[2, 2], strides=[2, 2], paddings=[0, 0]),
     [(0, 0), (0, 0)]),
    # SAME on 8 and 7 rows under 3x3 stride 2: one odd pad (0, 1), one (1, 1)
    ("same_odd_side", "pool2d", (1, 2, 8, 7),
     dict(ksize=[3, 3], strides=[2, 2], padding_algorithm="SAME"),
     [(0, 1), (1, 1)]),
    ("asymmetric_4_pads", "pool2d", (1, 2, 7, 6),
     dict(ksize=[3, 2], strides=[2, 1], paddings=[0, 2, 1, 0]),
     [(0, 2), (1, 0)]),
    ("nhwc", "pool2d", (2, 8, 7, 3),
     dict(ksize=[3, 3], strides=[2, 2], paddings=[1, 1],
          data_format="NHWC"),
     [(1, 1), (1, 1)]),
    ("pool3d_overlap_pad", "pool3d", (1, 2, 5, 6, 5),
     dict(ksize=[3, 2, 2], strides=[2, 2, 1], paddings=[1, 0, 1]),
     [(1, 1), (0, 0), (1, 1)]),
]


@pytest.mark.parametrize("ptype,exclusive",
                         [("max", True), ("avg", True), ("avg", False)],
                         ids=["max", "avg_exclusive", "avg_inclusive"])
@pytest.mark.parametrize("case", CASES, ids=[c[0] for c in CASES])
def test_pool_matches_numpy_loop_forward_and_gradient(case, ptype, exclusive):
    _, op, shape, attrs, pads = case
    attrs = dict(attrs, pooling_type=ptype, exclusive=exclusive)
    x = _distinct(shape, seed=len(shape) + shape[-1])
    ch_last = attrs.get("data_format") == "NHWC"
    to_ref = (lambda a: a.transpose(0, 3, 1, 2)) if ch_last else (lambda a: a)
    from_ref = (lambda a: a.transpose(0, 2, 3, 1)) if ch_last else (lambda a: a)
    ref_o, _ = _pool_np(to_ref(x), ptype, exclusive, attrs["ksize"],
                        attrs["strides"], pads)
    g = np.random.RandomState(7).randn(*from_ref(ref_o).shape).astype("float32")
    ref_o, ref_dx = _pool_np(to_ref(x), ptype, exclusive, attrs["ksize"],
                             attrs["strides"], pads, to_ref(g))
    o, dx = _run(op, x, attrs, g)
    np.testing.assert_allclose(o, from_ref(ref_o), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(dx, from_ref(ref_dx), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("dtype", ["int32", "int64"])
def test_max_pool_of_integers_forward(dtype):
    r = np.random.RandomState(3)
    x = r.randint(-50, 50, (2, 3, 8, 8)).astype(dtype)
    attrs = dict(pooling_type="max", ksize=[3, 3], strides=[2, 2],
                 paddings=[1, 1])
    ref, _ = _pool_np(x, "max", True, [3, 3], [2, 2], [(1, 1), (1, 1)])
    o, _ = _run("pool2d", x, attrs)
    assert o.dtype == jnp.asarray(x).dtype  # int64 narrows as jax is set up
    np.testing.assert_array_equal(o, ref.astype(o.dtype))


def test_a_tie_sends_the_whole_gradient_to_the_windows_first_element():
    x = np.ones((1, 1, 4, 4), "float32")
    g = np.arange(1, 5, dtype="float32").reshape(1, 1, 2, 2)
    _, dx = _run("pool2d", x, dict(pooling_type="max", ksize=[2, 2],
                                   strides=[2, 2], paddings=[0, 0]), g)
    want = np.zeros((4, 4), "float32")
    want[0, 0], want[0, 2], want[2, 0], want[2, 2] = 1, 2, 3, 4
    np.testing.assert_array_equal(dx[0, 0], want)


def test_a_border_window_sends_no_gradient_to_padding():
    """3x3 stride 2 pad 1 over equal values: each window's first element
    INSIDE the input takes its gradient, so dX sums to dOut's sum."""
    x = np.zeros((1, 1, 4, 4), "float32")  # what follows a ReLU
    g = np.arange(1, 5, dtype="float32").reshape(1, 1, 2, 2)
    attrs = dict(pooling_type="max", ksize=[3, 3], strides=[2, 2],
                 paddings=[1, 1])
    _, dx = _run("pool2d", x, attrs, g)
    want = np.zeros((4, 4), "float32")
    # windows start at rows / columns -1 and 1: their first input element
    want[0, 0], want[0, 1], want[1, 0], want[1, 1] = 1, 2, 3, 4
    np.testing.assert_array_equal(dx[0, 0], want)
    assert dx.sum() == g.sum()
    _, ref_dx = _pool_np(x, "max", True, [3, 3], [2, 2], [(1, 1), (1, 1)], g)
    np.testing.assert_array_equal(dx, ref_dx)


def test_tied_maxima_among_distinct_values_follow_the_loop():
    """Few distinct values, overlapping windows: many ties, and an input
    that is the first maximum of several windows sums their gradients."""
    r = np.random.RandomState(11)
    x = r.randint(0, 3, (2, 2, 9, 9)).astype("float32")
    g = r.randn(2, 2, 5, 5).astype("float32")
    attrs = dict(pooling_type="max", ksize=[3, 3], strides=[2, 2],
                 paddings=[1, 1])
    o, dx = _run("pool2d", x, attrs, g)
    ref_o, ref_dx = _pool_np(x, "max", True, [3, 3], [2, 2],
                             [(1, 1), (1, 1)], g)
    np.testing.assert_array_equal(o, ref_o)
    np.testing.assert_allclose(dx, ref_dx, rtol=1e-6, atol=1e-6)


def test_the_gradient_of_the_gradient_exists():
    """A gradient penalty differentiates pool2d_grad again."""
    x = jnp.asarray(_distinct((1, 2, 6, 6), 5))
    attrs = dict(OPS.get("pool2d").attr_defaults, pooling_type="max",
                 ksize=[3, 3], strides=[2, 2], paddings=[1, 1])

    def penalty(x):
        dx = jax.grad(lambda x: (nn_ops._pool2d_impl(x, attrs) ** 2).sum())(x)
        return (dx ** 2).sum()

    # an input that is the maximum of m windows gets dx = 2 m x, so the
    # penalty is the sum of 4 m^2 x^2 and its gradient 8 m^2 x
    _, m = _pool_np(np.asarray(x), "max", True, [3, 3], [2, 2],
                    [(1, 1), (1, 1)], np.ones((1, 2, 3, 3), "float32"))
    assert m.sum() == 18 and m.max() > 1  # overlap: some input wins twice
    np.testing.assert_allclose(np.asarray(jax.grad(penalty)(x)),
                               8 * m ** 2 * np.asarray(x), rtol=1e-5)


def _vjp_text(op, shape, attrs):
    full = dict(OPS.get(op).attr_defaults, **attrs)
    kernel = OPS.get(op).kernel

    def both(x, g):
        o, vjp = jax.vjp(lambda x: kernel({"X": [x]}, full)["Out"][0], x)
        return o, vjp(g)[0]

    x = jnp.zeros(shape, jnp.float32)
    o = jax.eval_shape(lambda x: kernel({"X": [x]}, full)["Out"][0], x)
    return jax.jit(both).lower(x, jnp.zeros(o.shape, o.dtype)).as_text()


def _count(text, hlo_op):
    return len(re.findall(r"stablehlo\." + hlo_op + r"\b", text))


@pytest.mark.parametrize("fmt,shape", [("NCHW", (2, 4, 8, 8)),
                                       ("NHWC", (2, 8, 8, 4))])
def test_max_pool_lowers_to_one_windowed_reduction_and_one_scatter(fmt, shape):
    text = _vjp_text("pool2d", shape,
                     dict(pooling_type="max", ksize=[3, 3], strides=[2, 2],
                          paddings=[1, 1], data_format=fmt))
    assert _count(text, "reduce_window") == 1
    assert _count(text, "select_and_scatter") == 1
    # one -inf-padded copy for the scatter and one crop of its result: no
    # interior padding, no strided slices of a padded copy, no running maximum
    assert _count(text, "pad") == 1 and "interior = [0, 0, 0, 0]" in text
    assert _count(text, "slice") == 1
    assert _count(text, "maximum") == 1    # the reduction's own body
    assert _count(text, "transpose") == 0  # NHWC windows sit on dims 1, 2


@pytest.mark.parametrize("op,shape,attrs", [
    ("pool2d", (2, 4, 8, 8), dict(ksize=[3, 3], strides=[2, 2],
                                  paddings=[1, 1])),
    ("pool3d", (1, 2, 6, 6, 6), dict(ksize=[3, 3, 3], strides=[2, 2, 2],
                                     paddings=[1, 1, 1])),
], ids=["pool2d", "pool3d"])
def test_avg_pool_lowers_to_windowed_sums(op, shape, attrs):
    text = _vjp_text(op, shape, dict(attrs, pooling_type="avg"))
    # the forward's sum, its divisor (the same sum over ones: padding is
    # excluded) and its transpose, a windowed sum of padded dOut
    assert _count(text, "reduce_window") == 3
    assert _count(text, "slice") == 0


def test_pool3d_max_lowers_like_pool2d():
    text = _vjp_text("pool3d", (1, 2, 6, 6, 6),
                     dict(pooling_type="max", ksize=[3, 3, 3],
                          strides=[2, 2, 2], paddings=[1, 1, 1]))
    assert _count(text, "reduce_window") == 1
    assert _count(text, "select_and_scatter") == 1
    assert _count(text, "slice") == 1


def test_the_slice_forms_are_gone():
    assert not hasattr(nn_ops, "_max_pool_slices")
    assert not hasattr(nn_ops, "_avg_pool_slices")


def test_the_stem_pool_trains_through_a_program():
    """fluid.layers.pool2d + append_backward: the grad op reaches the same
    select_and_scatter through run_generic_grad, ties after a ReLU included."""
    r = np.random.RandomState(2)
    xv = r.randn(2, 3, 8, 8).astype("float32")
    wv = r.randn(2, 3, 4, 4).astype("float32")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [3, 8, 8], dtype="float32")
        w = fluid.layers.data("w", [3, 4, 4], dtype="float32")
        x.stop_gradient = False
        y = fluid.layers.pool2d(fluid.layers.relu(x), pool_size=3,
                                pool_type="max", pool_stride=2,
                                pool_padding=1)
        loss = fluid.layers.reduce_sum(y * w)
        (dx,) = fluid.backward.gradients([loss], [x])
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    yv, dxv = exe.run(main, feed={"x": xv, "w": wv}, fetch_list=[y, dx])
    relu = np.maximum(xv, 0)
    ref_y, ref_d = _pool_np(relu, "max", True, [3, 3], [2, 2],
                            [(1, 1), (1, 1)], wv)
    np.testing.assert_allclose(yv, ref_y, rtol=1e-6)
    np.testing.assert_allclose(dxv, ref_d * (xv > 0), rtol=1e-5, atol=1e-6)
