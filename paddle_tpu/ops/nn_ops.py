"""Neural-network op kernels: conv, pooling, normalization, softmax/losses,
embedding, dropout, interpolation (reference: paddle/fluid/operators/
conv_op.cc + conv_cudnn_op.cu, pool_op.cc, batch_norm_op.cc, layer_norm_op.cc,
softmax_op.cc, softmax_with_cross_entropy_op.cc, cross_entropy_op.cc,
lookup_table_op.cc, dropout_op.cc, interpolate_op.cc …).

conv maps to lax.conv_general_dilated so XLA tiles it onto the MXU; pool2d /
pool3d are one lax.reduce_window each (_window_pool), differentiated by JAX
(select_and_scatter for max, a windowed sum for avg); dropout keeps its
reference Mask-output contract so its grad is mask-multiply (custom grad op
below) rather than a replayed RNG.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from .registry import register_op, register_grad_maker, first, seq, out
from ..fluid.core import dtype_to_jnp


# --------------------------------------------------------------------------
# softmax & cross entropy
# --------------------------------------------------------------------------
@register_op("softmax", inputs=("X",), attr_defaults={"axis": -1})
def _softmax(ins, attrs):
    return out(Out=jax.nn.softmax(first(ins, "X"), axis=attrs.get("axis", -1)))


@register_op("log_softmax", inputs=("X",), attr_defaults={"axis": -1})
def _log_softmax(ins, attrs):
    return out(Out=jax.nn.log_softmax(first(ins, "X"), axis=attrs.get("axis", -1)))


@register_op("cross_entropy", inputs=("X", "Label"), diff_inputs=("X",),
             attr_defaults={"soft_label": False, "ignore_index": -100})
def _cross_entropy(ins, attrs):
    x, label = first(ins, "X"), first(ins, "Label")
    eps = 1e-20
    if attrs.get("soft_label", False):
        loss = -jnp.sum(label * jnp.log(x + eps), axis=-1, keepdims=True)
    else:
        lbl = label
        if lbl.ndim == x.ndim and lbl.shape[-1] == 1:
            lbl = jnp.squeeze(lbl, -1)
        picked = jnp.take_along_axis(x, lbl[..., None].astype(jnp.int32), axis=-1)
        loss = -jnp.log(picked + eps)
        ign = attrs.get("ignore_index", -100)
        loss = jnp.where(lbl[..., None] == ign, 0.0, loss)
    return out(Y=loss)


@register_op("cross_entropy2", inputs=("X", "Label"), diff_inputs=("X",),
             attr_defaults={"ignore_index": -100})
def _cross_entropy2(ins, attrs):
    x, label = first(ins, "X"), first(ins, "Label")
    lbl = jnp.squeeze(label, -1) if label.ndim == x.ndim else label
    picked = jnp.take_along_axis(x, lbl[..., None].astype(jnp.int32), axis=-1)
    loss = -jnp.log(picked + 1e-20)
    return out(Y=loss, XShape=jnp.zeros((0,) + x.shape, x.dtype),
               MatchX=picked)


@register_op("softmax_with_cross_entropy", inputs=("Logits", "Label"),
             diff_inputs=("Logits",),
             attr_defaults={"soft_label": False, "ignore_index": -100,
                            "numeric_stable_mode": True, "axis": -1})
def _softmax_with_cross_entropy(ins, attrs):
    logits, label = first(ins, "Logits"), first(ins, "Label")
    axis = attrs.get("axis", -1) % logits.ndim
    logp = jax.nn.log_softmax(logits, axis=axis)
    softmax = jnp.exp(logp)
    if attrs.get("soft_label", False):
        loss = -jnp.sum(label * logp, axis=axis, keepdims=True)
    else:
        lbl = label
        if lbl.ndim == logits.ndim and lbl.shape[axis] == 1:
            lbl = jnp.squeeze(lbl, axis)
        picked = jnp.take_along_axis(
            logp, jnp.expand_dims(lbl.astype(jnp.int32), axis), axis=axis)
        loss = -picked
        ign = attrs.get("ignore_index", -100)
        loss = jnp.where(jnp.expand_dims(lbl, axis) == ign, 0.0, loss)
    return out(Softmax=softmax, Loss=loss)


@register_op("sigmoid_cross_entropy_with_logits", inputs=("X", "Label"),
             diff_inputs=("X",),
             attr_defaults={"ignore_index": -100, "normalize": False})
def _sigmoid_ce(ins, attrs):
    x, label = first(ins, "X"), first(ins, "Label")
    loss = jnp.maximum(x, 0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    ign = attrs.get("ignore_index", -100)
    mask = label != ign
    loss = jnp.where(mask, loss, 0.0)
    if attrs.get("normalize", False):
        loss = loss / jnp.maximum(jnp.sum(mask.astype(x.dtype)), 1.0)
    return out(Out=loss)


@register_op("bce_loss", inputs=("X", "Label"), diff_inputs=("X",))
def _bce_loss(ins, attrs):
    x, label = first(ins, "X"), first(ins, "Label")
    eps = 1e-12
    return out(Out=-(label * jnp.log(x + eps) + (1 - label) * jnp.log(1 - x + eps)))


@register_op("square_error_cost", inputs=("X", "Y"))
def _square_error_cost(ins, attrs):
    d = first(ins, "X") - first(ins, "Y")
    return out(Out=jnp.square(d))


@register_op("log_loss", inputs=("Predicted", "Labels"),
             diff_inputs=("Predicted",), attr_defaults={"epsilon": 1e-4})
def _log_loss(ins, attrs):
    p, l = first(ins, "Predicted"), first(ins, "Labels")
    eps = attrs.get("epsilon", 1e-4)
    return out(Loss=-l * jnp.log(p + eps) - (1 - l) * jnp.log(1 - p + eps))


@register_op("huber_loss", inputs=("X", "Y"), diff_inputs=("X",),
             attr_defaults={"delta": 1.0})
def _huber_loss(ins, attrs):
    x, y = first(ins, "X"), first(ins, "Y")
    d = attrs.get("delta", 1.0)
    r = y - x
    ar = jnp.abs(r)
    loss = jnp.where(ar <= d, 0.5 * r * r, d * (ar - 0.5 * d))
    return out(Out=loss, Residual=r)


@register_op("smooth_l1_loss", inputs=("X", "Y", "InsideWeight", "OutsideWeight"),
             diff_inputs=("X",), attr_defaults={"sigma": 1.0})
def _smooth_l1(ins, attrs):
    x, y = first(ins, "X"), first(ins, "Y")
    iw, ow = first(ins, "InsideWeight"), first(ins, "OutsideWeight")
    sigma2 = attrs.get("sigma", 1.0) ** 2
    d = x - y
    if iw is not None:
        d = d * iw
    ad = jnp.abs(d)
    l = jnp.where(ad < 1.0 / sigma2, 0.5 * d * d * sigma2, ad - 0.5 / sigma2)
    if ow is not None:
        l = l * ow
    return out(Out=jnp.sum(l.reshape(l.shape[0], -1), -1, keepdims=True), Diff=d)


@register_op("kldiv_loss", inputs=("X", "Target"), diff_inputs=("X",),
             attr_defaults={"reduction": "mean"})
def _kldiv_loss(ins, attrs):
    x, t = first(ins, "X"), first(ins, "Target")
    loss = jnp.where(t > 0, t * (jnp.log(t) - x), 0.0)
    red = attrs.get("reduction", "mean")
    if red == "mean":
        loss = jnp.mean(loss).reshape((1,))
    elif red == "sum":
        loss = jnp.sum(loss).reshape((1,))
    elif red == "batchmean":
        loss = (jnp.sum(loss) / x.shape[0]).reshape((1,))
    return out(Loss=loss)


@register_op("hinge_loss", inputs=("Logits", "Labels"), diff_inputs=("Logits",))
def _hinge_loss(ins, attrs):
    logits, labels = first(ins, "Logits"), first(ins, "Labels")
    return out(Loss=jnp.maximum(1.0 - (2.0 * labels - 1.0) * logits, 0.0))


@register_op("rank_loss", inputs=("Label", "Left", "Right"),
             diff_inputs=("Left", "Right"))
def _rank_loss(ins, attrs):
    label, left, right = first(ins, "Label"), first(ins, "Left"), first(ins, "Right")
    d = left - right
    return out(Out=jnp.log1p(jnp.exp(d)) - label * d)


@register_op("margin_rank_loss", inputs=("Label", "X1", "X2"),
             diff_inputs=("X1", "X2"), attr_defaults={"margin": 0.0})
def _margin_rank_loss(ins, attrs):
    label, x1, x2 = first(ins, "Label"), first(ins, "X1"), first(ins, "X2")
    o = jnp.maximum(-label * (x1 - x2) + attrs.get("margin", 0.0), 0.0)
    return out(Out=o, Activated=(o > 0).astype(x1.dtype))


@register_op("nll_loss", inputs=("X", "Label", "Weight"), diff_inputs=("X",),
             attr_defaults={"ignore_index": -100, "reduction": "mean"})
def _nll_loss(ins, attrs):
    x, label = first(ins, "X"), first(ins, "Label")
    w = first(ins, "Weight")
    lbl = label.astype(jnp.int32)
    picked = -jnp.take_along_axis(x, lbl[:, None], axis=1)[:, 0]
    wt = jnp.ones_like(picked) if w is None else w[lbl]
    ign = attrs.get("ignore_index", -100)
    wt = jnp.where(label == ign, 0.0, wt)
    loss = picked * wt
    total_w = jnp.sum(wt)
    red = attrs.get("reduction", "mean")
    if red == "mean":
        return out(Out=(jnp.sum(loss) / jnp.maximum(total_w, 1e-10)).reshape((1,)),
                   Total_weight=total_w.reshape((1,)))
    if red == "sum":
        return out(Out=jnp.sum(loss).reshape((1,)), Total_weight=total_w.reshape((1,)))
    return out(Out=loss, Total_weight=total_w.reshape((1,)))


@register_op("mse_loss", inputs=("X", "Y"))
def _mse_loss(ins, attrs):
    return out(Out=jnp.mean(jnp.square(first(ins, "X") - first(ins, "Y"))).reshape((1,)))


@register_op("bpr_loss", inputs=("X", "Label"), diff_inputs=("X",))
def _bpr_loss(ins, attrs):
    x, label = first(ins, "X"), first(ins, "Label")
    lbl = jnp.squeeze(label, -1) if label.ndim == x.ndim else label
    lbl = lbl.astype(jnp.int32)
    pos = jnp.take_along_axis(x, lbl[:, None], axis=1)
    terms = -jnp.log(jax.nn.sigmoid(pos - x) + 1e-8)
    # exclude the positive column itself; average over the N-1 negatives
    # (reference: operators/bpr_loss_op.h)
    mask = jax.nn.one_hot(lbl, x.shape[1], dtype=x.dtype)
    loss = jnp.sum(terms * (1.0 - mask), axis=1, keepdims=True) \
        / (x.shape[1] - 1)
    return out(Y=loss)


# --------------------------------------------------------------------------
# embedding
# --------------------------------------------------------------------------
def _lookup(w, ids, padding_idx):
    o = jnp.take(w, ids.astype(jnp.int32), axis=0)
    if padding_idx is not None and padding_idx >= 0:
        o = jnp.where((ids == padding_idx)[..., None], 0.0, o)
    return o


@register_op("lookup_table", inputs=("W", "Ids"), diff_inputs=("W",),
             attr_defaults={"padding_idx": -1, "is_sparse": False,
                            "is_distributed": False, "remote_prefetch": False})
def _lookup_table(ins, attrs):
    w, ids = first(ins, "W"), first(ins, "Ids")
    ids2 = jnp.squeeze(ids, -1)  # v1 contract: Ids shape [..., 1]
    pad = attrs.get("padding_idx", -1)
    return out(Out=_lookup(w, ids2, pad if pad >= 0 else None))


@register_op("lookup_table_v2", inputs=("W", "Ids"), diff_inputs=("W",),
             attr_defaults={"padding_idx": -1, "is_sparse": False,
                            "is_distributed": False, "remote_prefetch": False})
def _lookup_table_v2(ins, attrs):
    w, ids = first(ins, "W"), first(ins, "Ids")
    pad = attrs.get("padding_idx", -1)
    return out(Out=_lookup(w, ids, pad if pad >= 0 else None))


# --------------------------------------------------------------------------
# dropout — Mask output contract kept so grad = mask multiply
# --------------------------------------------------------------------------
@register_op("dropout", inputs=("X", "Seed"), needs_rng=True,
             attr_defaults={"dropout_prob": 0.5, "is_test": False,
                            "dropout_implementation": "downgrade_in_infer",
                            "fix_seed": False, "seed": 0})
def _dropout(ins, attrs):
    x = first(ins, "X")
    p = attrs.get("dropout_prob", 0.5)
    impl = attrs.get("dropout_implementation", "downgrade_in_infer")
    if attrs.get("is_test", False):
        o = x if impl == "upscale_in_train" else x * (1.0 - p)
        return out(Out=o, Mask=jnp.ones_like(x, jnp.uint8))
    keep = jax.random.bernoulli(attrs["_rng"], 1.0 - p, x.shape)
    if impl == "upscale_in_train":
        o = jnp.where(keep, x / max(1.0 - p, 1e-10), 0.0) if p < 1.0 else jnp.zeros_like(x)
    else:
        o = jnp.where(keep, x, 0.0)
    return out(Out=o, Mask=keep.astype(jnp.uint8))


@register_op("dropout_grad", no_grad=True)
def _dropout_grad(ins, attrs):
    g = first(ins, "Out@GRAD")
    mask = first(ins, "Mask")
    p = attrs.get("dropout_prob", 0.5)
    impl = attrs.get("dropout_implementation", "downgrade_in_infer")
    gx = g * mask.astype(g.dtype)
    if impl == "upscale_in_train" and p < 1.0:
        gx = gx / (1.0 - p)
    return out(**{"X@GRAD": gx})


@register_grad_maker("dropout")
def _dropout_grad_maker(op, grad_map):
    return [{
        "type": "dropout_grad",
        "inputs": {"Out@GRAD": [grad_map[op.output("Out")[0]]],
                   "Mask": op.output("Mask")},
        "outputs": {"X@GRAD": [grad_map[op.input("X")[0]]]},
        "attrs": {k: v for k, v in op.attrs.items() if not k.startswith("_")},
    }]


# --------------------------------------------------------------------------
# normalization
# --------------------------------------------------------------------------
@register_op("batch_norm",
             inputs=("X", "Scale", "Bias", "Mean", "Variance", "MomentumTensor"),
             diff_inputs=("X", "Scale", "Bias"),
             attr_defaults={"momentum": 0.9, "epsilon": 1e-5,
                            "data_layout": "NCHW", "is_test": False,
                            "use_global_stats": False, "trainable_statistics": False,
                            "fuse_with_relu": False})
def _batch_norm(ins, attrs):
    x = first(ins, "X")
    scale, bias = first(ins, "Scale"), first(ins, "Bias")
    mean, var = first(ins, "Mean"), first(ins, "Variance")
    eps = attrs.get("epsilon", 1e-5)
    momentum = attrs.get("momentum", 0.9)
    layout = attrs.get("data_layout", "NCHW")
    c_axis = 1 if layout == "NCHW" else x.ndim - 1
    axes = tuple(i for i in range(x.ndim) if i != c_axis)
    use_stats = attrs.get("is_test", False) or attrs.get("use_global_stats", False)
    if use_stats:
        bm, bv = mean, var
        new_mean, new_var = mean, var
        saved_var_inv = lax.rsqrt(bv + eps)
    else:
        x32 = x.astype(jnp.float32)
        bm = jnp.mean(x32, axes)
        bv = jnp.mean(jnp.square(x32), axes) - jnp.square(bm)
        bm, bv = bm.astype(x.dtype), bv.astype(x.dtype)
        new_mean = momentum * mean + (1 - momentum) * bm
        new_var = momentum * var + (1 - momentum) * bv
        saved_var_inv = lax.rsqrt(bv + eps)
    bshape = [1] * x.ndim
    bshape[c_axis] = x.shape[c_axis]
    y = (x - bm.reshape(bshape)) * saved_var_inv.reshape(bshape)
    y = y * scale.reshape(bshape) + bias.reshape(bshape)
    if attrs.get("fuse_with_relu", False):
        y = jnp.maximum(y, 0)
    return out(Y=y, MeanOut=new_mean, VarianceOut=new_var,
               SavedMean=bm, SavedVariance=saved_var_inv)


@register_op("layer_norm", inputs=("X", "Scale", "Bias"),
             diff_inputs=("X", "Scale", "Bias"),
             attr_defaults={"epsilon": 1e-5, "begin_norm_axis": 1})
def _layer_norm(ins, attrs):
    x = first(ins, "X")
    scale, bias = first(ins, "Scale"), first(ins, "Bias")
    eps = attrs.get("epsilon", 1e-5)
    bna = attrs.get("begin_norm_axis", 1)
    axes = tuple(range(bna, x.ndim))
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axes, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axes, keepdims=True)
    y = ((x32 - mean) * lax.rsqrt(var + eps)).astype(x.dtype)
    d = int(np.prod(x.shape[bna:]))
    if scale is not None:
        y = y * scale.reshape((1,) * bna + x.shape[bna:])
    if bias is not None:
        y = y + bias.reshape((1,) * bna + x.shape[bna:])
    flat = x.shape[:bna]
    return out(Y=y, Mean=mean.reshape(flat).astype(x.dtype),
               Variance=var.reshape(flat).astype(x.dtype))


@register_op("instance_norm", inputs=("X", "Scale", "Bias"),
             diff_inputs=("X", "Scale", "Bias"),
             attr_defaults={"epsilon": 1e-5})
def _instance_norm(ins, attrs):
    x = first(ins, "X")
    scale, bias = first(ins, "Scale"), first(ins, "Bias")
    eps = attrs.get("epsilon", 1e-5)
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axes, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axes, keepdims=True)
    inv = lax.rsqrt(var + eps)
    y = (x - mean) * inv
    c = x.shape[1]
    bshape = (1, c) + (1,) * (x.ndim - 2)
    if scale is not None:
        y = y * scale.reshape(bshape)
    if bias is not None:
        y = y + bias.reshape(bshape)
    n = x.shape[0]
    return out(Y=y, SavedMean=mean.reshape(n * c),
               SavedVariance=inv.reshape(n * c))


@register_op("group_norm", inputs=("X", "Scale", "Bias"),
             diff_inputs=("X", "Scale", "Bias"),
             attr_defaults={"epsilon": 1e-5, "groups": 1, "data_layout": "NCHW"})
def _group_norm(ins, attrs):
    x = first(ins, "X")
    scale, bias = first(ins, "Scale"), first(ins, "Bias")
    g = attrs.get("groups", 1)
    eps = attrs.get("epsilon", 1e-5)
    n, c = x.shape[0], x.shape[1]
    xg = x.reshape((n, g, c // g) + x.shape[2:])
    axes = tuple(range(2, xg.ndim))
    mean = jnp.mean(xg, axes, keepdims=True)
    var = jnp.mean(jnp.square(xg - mean), axes, keepdims=True)
    y = ((xg - mean) * lax.rsqrt(var + eps)).reshape(x.shape)
    bshape = (1, c) + (1,) * (x.ndim - 2)
    if scale is not None:
        y = y * scale.reshape(bshape)
    if bias is not None:
        y = y + bias.reshape(bshape)
    return out(Y=y, Mean=mean.reshape(n, g), Variance=var.reshape(n, g))


@register_op("norm", inputs=("X",), attr_defaults={"axis": -1, "epsilon": 1e-10})
def _norm(ins, attrs):
    x = first(ins, "X")
    ax = attrs.get("axis", -1)
    norm = jnp.sqrt(jnp.sum(jnp.square(x), ax, keepdims=True)
                    + attrs.get("epsilon", 1e-10))
    return out(Out=x / norm, Norm=norm)


@register_op("data_norm", inputs=("X", "BatchSize", "BatchSum", "BatchSquareSum"),
             diff_inputs=("X",), attr_defaults={"epsilon": 1e-4})
def _data_norm(ins, attrs):
    x = first(ins, "X")
    bsize = first(ins, "BatchSize")
    bsum = first(ins, "BatchSum")
    bsq = first(ins, "BatchSquareSum")
    means = bsum / bsize
    scales = jnp.sqrt(bsize / bsq)
    return out(Y=(x - means) * scales, Means=means, Scales=scales)


@register_op("lrn", inputs=("X",),
             attr_defaults={"n": 5, "k": 2.0, "alpha": 1e-4, "beta": 0.75,
                            "data_format": "NCHW"})
def _lrn(ins, attrs):
    x = first(ins, "X")
    nhwc = attrs.get("data_format", "NCHW") == "NHWC"
    if nhwc:
        x = jnp.transpose(x, (0, 3, 1, 2))
    n, k = attrs.get("n", 5), attrs.get("k", 2.0)
    alpha, beta = attrs.get("alpha", 1e-4), attrs.get("beta", 0.75)
    sq = jnp.square(x)
    half = n // 2
    pad = jnp.pad(sq, [(0, 0), (half, half), (0, 0), (0, 0)])
    mid = sum(pad[:, i:i + x.shape[1]] for i in range(n))
    mid = k + alpha * mid
    o = x / (mid ** beta)
    if nhwc:
        o = jnp.transpose(o, (0, 2, 3, 1))
        mid = jnp.transpose(mid, (0, 2, 3, 1))
    return out(Out=o, MidOut=mid)


# --------------------------------------------------------------------------
# conv / pool
# --------------------------------------------------------------------------
def _conv_padding(paddings, algo, ndim, ksize, strides, dilations, in_shape):
    if algo == "SAME":
        pads = []
        for i in range(ndim):
            o = -(-in_shape[i] // strides[i])
            eff = (ksize[i] - 1) * dilations[i] + 1
            total = max((o - 1) * strides[i] + eff - in_shape[i], 0)
            pads.append((total // 2, total - total // 2))
        return pads
    if algo == "VALID":
        return [(0, 0)] * ndim
    p = list(paddings)
    if len(p) == ndim:
        return [(x, x) for x in p]
    return [(p[2 * i], p[2 * i + 1]) for i in range(ndim)]


@register_op("conv2d", inputs=("Input", "Filter", "Bias", "ResidualData"),
             diff_inputs=("Input", "Filter", "Bias"),
             attr_defaults={"strides": [1, 1], "paddings": [0, 0],
                            "dilations": [1, 1], "groups": 1,
                            "padding_algorithm": "EXPLICIT",
                            "data_format": "NCHW", "use_cudnn": True,
                            "exhaustive_search": False})
def _conv2d(ins, attrs):
    x, w = first(ins, "Input"), first(ins, "Filter")
    fmt = attrs.get("data_format", "NCHW")
    if fmt in ("NCHW", "AnyLayout"):
        dn = ("NCHW", "OIHW", "NCHW")
        spatial = x.shape[2:]
    else:
        dn = ("NHWC", "OIHW", "NHWC")
        spatial = x.shape[1:3]
    strides = [int(s) for s in attrs.get("strides", [1, 1])]
    dil = [int(d) for d in attrs.get("dilations", [1, 1])]
    pads = _conv_padding(attrs.get("paddings", [0, 0]),
                         attrs.get("padding_algorithm", "EXPLICIT"),
                         2, w.shape[2:], strides, dil, spatial)
    from ..fluid import core as _core
    from .math_ops import mxu_available
    orig_dtype = x.dtype
    if _core.globals_["FLAGS_use_bf16_matmul"] and x.dtype == jnp.float32 \
            and mxu_available():
        # bf16 in AND out: a mixed-dtype conv (preferred_element_type=f32)
        # has no transpose rule in this jax version, which breaks the
        # generic vjp grad path; the MXU still accumulates in f32
        # internally, the output just rounds to bf16 once
        x, w = x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
    o = lax.conv_general_dilated(
        x, w, window_strides=strides, padding=pads, rhs_dilation=dil,
        dimension_numbers=dn, feature_group_count=attrs.get("groups", 1))
    o = o.astype(orig_dtype)
    b = first(ins, "Bias")
    if b is not None:
        c_axis = 1 if fmt in ("NCHW", "AnyLayout") else 3
        bshape = [1] * o.ndim
        bshape[c_axis] = b.shape[0]
        o = o + b.reshape(bshape)
    return out(Output=o)


@register_op("depthwise_conv2d", inputs=("Input", "Filter", "Bias"),
             diff_inputs=("Input", "Filter", "Bias"),
             attr_defaults={"strides": [1, 1], "paddings": [0, 0],
                            "dilations": [1, 1], "groups": 1,
                            "padding_algorithm": "EXPLICIT",
                            "data_format": "NCHW", "use_cudnn": False})
def _depthwise_conv2d(ins, attrs):
    return _conv2d(ins, attrs)


@register_op("conv3d", inputs=("Input", "Filter", "Bias"),
             diff_inputs=("Input", "Filter", "Bias"),
             attr_defaults={"strides": [1, 1, 1], "paddings": [0, 0, 0],
                            "dilations": [1, 1, 1], "groups": 1,
                            "padding_algorithm": "EXPLICIT",
                            "data_format": "NCDHW", "use_cudnn": True})
def _conv3d(ins, attrs):
    x, w = first(ins, "Input"), first(ins, "Filter")
    strides = [int(s) for s in attrs.get("strides", [1, 1, 1])]
    dil = [int(d) for d in attrs.get("dilations", [1, 1, 1])]
    pads = _conv_padding(attrs.get("paddings", [0, 0, 0]),
                         attrs.get("padding_algorithm", "EXPLICIT"),
                         3, w.shape[2:], strides, dil, x.shape[2:])
    o = lax.conv_general_dilated(
        x, w, window_strides=strides, padding=pads, rhs_dilation=dil,
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"),
        feature_group_count=attrs.get("groups", 1))
    return out(Output=o)


@register_op("conv2d_transpose", inputs=("Input", "Filter", "Bias"),
             diff_inputs=("Input", "Filter", "Bias"),
             attr_defaults={"strides": [1, 1], "paddings": [0, 0],
                            "dilations": [1, 1], "groups": 1,
                            "output_size": [], "padding_algorithm": "EXPLICIT",
                            "data_format": "NCHW", "use_cudnn": True})
def _conv2d_transpose(ins, attrs):
    x, w = first(ins, "Input"), first(ins, "Filter")  # w: [in_c, out_c/g, kh, kw]
    strides = [int(s) for s in attrs.get("strides", [1, 1])]
    dil = [int(d) for d in attrs.get("dilations", [1, 1])]
    pads = _conv_padding(attrs.get("paddings", [0, 0]),
                         attrs.get("padding_algorithm", "EXPLICIT"),
                         2, w.shape[2:], strides, dil, x.shape[2:])
    g = attrs.get("groups", 1)
    kh, kw = w.shape[2], w.shape[3]
    # grad-of-conv formulation: transposed conv = lhs-dilated conv with
    # flipped, transposed kernel
    w_t = jnp.swapaxes(w, 0, 1)[:, :, ::-1, ::-1]  # [out_c/g, in_c, kh, kw]
    if g > 1:
        w_t = w_t.reshape(w.shape[1], g, w.shape[0] // g, kh, kw)
        w_t = jnp.concatenate([w_t[:, i] for i in range(g)], axis=0)
    tp = [((kh - 1) * dil[0] - pads[0][0], (kh - 1) * dil[0] - pads[0][1]),
          ((kw - 1) * dil[1] - pads[1][0], (kw - 1) * dil[1] - pads[1][1])]
    o = lax.conv_general_dilated(
        x, w_t, window_strides=(1, 1), padding=tp, lhs_dilation=strides,
        rhs_dilation=dil, dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=g)
    osize = attrs.get("output_size") or []
    if osize:
        # paddle allows any size in [natural, natural+stride): pad up or
        # crop down to the requested size
        grow = [max(0, osize[i] - o.shape[2 + i]) for i in (0, 1)]
        if any(grow):
            o = jnp.pad(o, [(0, 0), (0, 0), (0, grow[0]), (0, grow[1])])
        o = o[:, :, :osize[0], :osize[1]]
    b = first(ins, "Bias")
    if b is not None:
        o = o + b.reshape(1, -1, 1, 1)
    return out(Output=o)


def _window_pool(x, ptype, exclusive, ksize, strides, pads, lead):
    """Max / avg pooling over the len(ksize) dims that follow the first
    `lead` ones, as ONE lax.reduce_window: XLA's windowed reduction forward,
    and under jax.vjp select_and_scatter (max: the whole gradient goes to a
    window's FIRST maximum in row-major order, never to padding, as the
    reference's MaxPool2dGradFunctor does) or a padded windowed sum (avg)."""
    nd = len(ksize)
    tail = x.ndim - lead - nd

    def spread(pooled, other):
        return (other,) * lead + tuple(pooled) + (other,) * tail

    window = (spread(ksize, 1), spread(strides, 1),
              spread(map(tuple, pads), (0, 0)))
    if ptype == "max":
        init = -np.inf if jnp.issubdtype(x.dtype, jnp.floating) \
            else jnp.iinfo(x.dtype).min
        return lax.reduce_window(x, np.array(init, x.dtype), lax.max, *window)
    zero = np.array(0, x.dtype)
    o = lax.reduce_window(x, zero, lax.add, *window)
    if not (exclusive and any(sum(p) for p in pads)):
        return o / float(np.prod(ksize))
    # exclusive: pad elements don't count; the divisor is the same windowed
    # sum over ones, a constant of the shapes
    ones = jnp.ones(spread(x.shape[lead:lead + nd], 1), x.dtype)
    return o / jnp.maximum(lax.reduce_window(ones, zero, lax.add, *window), 1)


def _pool2d_impl(x, attrs):
    ptype = attrs.get("pooling_type", "max")
    ksize = [int(k) for k in attrs.get("ksize", [1, 1])]
    strides = [int(s) for s in attrs.get("strides", [1, 1])]
    fmt = attrs.get("data_format", "NCHW")
    ch_last = fmt == "NHWC"
    hw = x.shape[2:4] if not ch_last else x.shape[1:3]
    if attrs.get("global_pooling", False) or (
            attrs.get("adaptive", False) and ksize == [1, 1]):
        axes = (2, 3) if not ch_last else (1, 2)
        red = jnp.max if ptype == "max" else jnp.mean
        return red(x, axis=axes, keepdims=True)
    if attrs.get("adaptive", False):
        axes = (2, 3) if not ch_last else (1, 2)
        oh, ow = ksize
        n, c = x.shape[0], (x.shape[1] if not ch_last else x.shape[3])
        assert hw[0] % oh == 0 and hw[1] % ow == 0, \
            "adaptive pool requires divisible sizes in this build"
        xr = (x.reshape(n, c, oh, hw[0] // oh, ow, hw[1] // ow)
              if not ch_last else
              x.reshape(n, oh, hw[0] // oh, ow, hw[1] // ow, c))
        rax = (3, 5) if not ch_last else (2, 4)
        red = jnp.max if ptype == "max" else jnp.mean
        return red(xr, axis=rax)
    pads = _conv_padding(attrs.get("paddings", [0, 0]),
                         attrs.get("padding_algorithm", "EXPLICIT"),
                         2, ksize, strides, [1, 1], hw)
    return _window_pool(x, ptype, attrs.get("exclusive", True), ksize,
                        strides, pads, 1 if ch_last else 2)


@register_op("pool2d", inputs=("X",),
             attr_defaults={"pooling_type": "max", "ksize": [1, 1],
                            "global_pooling": False, "strides": [1, 1],
                            "paddings": [0, 0], "exclusive": True,
                            "adaptive": False, "ceil_mode": False,
                            "use_cudnn": True, "data_format": "NCHW",
                            "padding_algorithm": "EXPLICIT"})
def _pool2d(ins, attrs):
    return out(Out=_pool2d_impl(first(ins, "X"), attrs))


@register_op("pool3d", inputs=("X",),
             attr_defaults={"pooling_type": "max", "ksize": [1, 1, 1],
                            "global_pooling": False, "strides": [1, 1, 1],
                            "paddings": [0, 0, 0], "exclusive": True,
                            "adaptive": False, "ceil_mode": False,
                            "use_cudnn": True, "data_format": "NCDHW",
                            "padding_algorithm": "EXPLICIT"})
def _pool3d(ins, attrs):
    x = first(ins, "X")
    ksize = [int(k) for k in attrs.get("ksize")]
    strides = [int(s) for s in attrs.get("strides")]
    if attrs.get("global_pooling", False) or (
            attrs.get("adaptive", False) and ksize == [1, 1, 1]):
        red = jnp.max if attrs.get("pooling_type") == "max" else jnp.mean
        return out(Out=red(x, axis=(2, 3, 4), keepdims=True))
    if attrs.get("adaptive", False):
        od, oh, ow = ksize
        n, c = x.shape[:2]
        d, h, w = x.shape[2:]
        assert d % od == 0 and h % oh == 0 and w % ow == 0, \
            "adaptive pool3d requires divisible sizes in this build"
        xr = x.reshape(n, c, od, d // od, oh, h // oh, ow, w // ow)
        red = jnp.max if attrs.get("pooling_type") == "max" else jnp.mean
        return out(Out=red(xr, axis=(3, 5, 7)))
    pads = _conv_padding(attrs.get("paddings"), attrs.get("padding_algorithm"),
                         3, ksize, strides, [1, 1, 1], x.shape[2:])
    return out(Out=_window_pool(x, attrs.get("pooling_type", "max"),
                                attrs.get("exclusive", True), ksize, strides,
                                pads, 2))


@register_op("max_pool2d_with_index", inputs=("X",),
             attr_defaults={"ksize": [1, 1], "strides": [1, 1],
                            "paddings": [0, 0], "global_pooling": False,
                            "adaptive": False})
def _max_pool2d_with_index(ins, attrs):
    x = first(ins, "X")
    kh, kw = [int(k) for k in attrs.get("ksize", [1, 1])]
    sh, sw = [int(s) for s in attrs.get("strides", [1, 1])]
    ph, pw = [int(p) for p in attrs.get("paddings", [0, 0])]
    if attrs.get("global_pooling", False):
        kh, kw = x.shape[2], x.shape[3]
        sh, sw, ph, pw = kh, kw, 0, 0
    n, c, H, W = x.shape
    neg = jnp.asarray(-jnp.inf, x.dtype)
    xp = jnp.pad(x, [(0, 0), (0, 0), (ph, ph), (pw, pw)],
                 constant_values=neg)
    # flat input index (within the unpadded HxW plane, reference Mask
    # contract: operators/math/pooling.cc MaxPool2dWithIndex)
    flat_idx = (jnp.arange(H + 2 * ph)[:, None] - ph) * W \
        + (jnp.arange(W + 2 * pw)[None, :] - pw)
    oh = (H + 2 * ph - kh) // sh + 1
    ow = (W + 2 * pw - kw) // sw + 1
    patches, idx_patches = [], []
    for i in range(kh):
        for j in range(kw):
            patches.append(lax.slice(
                xp, (0, 0, i, j),
                (n, c, i + (oh - 1) * sh + 1, j + (ow - 1) * sw + 1),
                (1, 1, sh, sw)))
            idx_patches.append(lax.slice(
                flat_idx, (i, j),
                (i + (oh - 1) * sh + 1, j + (ow - 1) * sw + 1), (sh, sw)))
    stacked = jnp.stack(patches, axis=-1)            # [n,c,oh,ow,kh*kw]
    sidx = jnp.stack(idx_patches, axis=-1)           # [oh,ow,kh*kw]
    arg = jnp.argmax(stacked, axis=-1)
    o = jnp.max(stacked, axis=-1)
    mask = jnp.take_along_axis(
        jnp.broadcast_to(sidx, stacked.shape), arg[..., None], -1)[..., 0]
    return out(Out=o, Mask=mask.astype(jnp.int32))


@register_op("max_pool3d_with_index", inputs=("X",),
             attr_defaults={"ksize": [1, 1, 1], "strides": [1, 1, 1],
                            "paddings": [0, 0, 0], "global_pooling": False,
                            "adaptive": False})
def _max_pool3d_with_index(ins, attrs):
    """3d max pool returning the flat DxHxW argmax per window (reference
    math/pooling.cc MaxPool3dWithIndex). Adaptive mode needs divisible
    sizes (static-shape TPU build)."""
    x = first(ins, "X")
    n, c, D, H, W = x.shape
    if attrs.get("adaptive", False):
        od, oh, ow = [int(k) for k in attrs.get("ksize")]
        assert D % od == 0 and H % oh == 0 and W % ow == 0
        kd, kh, kw = D // od, H // oh, W // ow
        xr = x.reshape(n, c, od, kd, oh, kh, ow, kw)
        xr = jnp.transpose(xr, (0, 1, 2, 4, 6, 3, 5, 7)).reshape(
            n, c, od, oh, ow, kd * kh * kw)
        arg = jnp.argmax(xr, axis=-1)
        o = jnp.max(xr, axis=-1)
        # local (di,hi,wi) within the bin -> flat index in the full plane
        di = arg // (kh * kw)
        hi = (arg // kw) % kh
        wi = arg % kw
        gd = jnp.arange(od)[None, None, :, None, None] * kd + di
        gh = jnp.arange(oh)[None, None, None, :, None] * kh + hi
        gw = jnp.arange(ow)[None, None, None, None, :] * kw + wi
        return out(Out=o, Mask=(gd * H * W + gh * W + gw).astype(jnp.int32))
    kd, kh, kw = [int(k) for k in attrs.get("ksize")]
    sd, sh, sw = [int(s) for s in attrs.get("strides")]
    pd, ph, pw = [int(p) for p in attrs.get("paddings")]
    if attrs.get("global_pooling", False):
        kd, kh, kw = D, H, W
        sd, sh, sw, pd, ph, pw = kd, kh, kw, 0, 0, 0
    neg = jnp.asarray(-jnp.inf, x.dtype)
    xp = jnp.pad(x, [(0, 0), (0, 0), (pd, pd), (ph, ph), (pw, pw)],
                 constant_values=neg)
    flat_idx = ((jnp.arange(D + 2 * pd)[:, None, None] - pd) * H * W
                + (jnp.arange(H + 2 * ph)[None, :, None] - ph) * W
                + (jnp.arange(W + 2 * pw)[None, None, :] - pw))
    od = (D + 2 * pd - kd) // sd + 1
    oh = (H + 2 * ph - kh) // sh + 1
    ow = (W + 2 * pw - kw) // sw + 1
    patches, idx_patches = [], []
    for a in range(kd):
        for i in range(kh):
            for j in range(kw):
                patches.append(lax.slice(
                    xp, (0, 0, a, i, j),
                    (n, c, a + (od - 1) * sd + 1, i + (oh - 1) * sh + 1,
                     j + (ow - 1) * sw + 1), (1, 1, sd, sh, sw)))
                idx_patches.append(lax.slice(
                    flat_idx, (a, i, j),
                    (a + (od - 1) * sd + 1, i + (oh - 1) * sh + 1,
                     j + (ow - 1) * sw + 1), (sd, sh, sw)))
    stacked = jnp.stack(patches, axis=-1)
    sidx = jnp.stack(idx_patches, axis=-1)
    arg = jnp.argmax(stacked, axis=-1)
    o = jnp.max(stacked, axis=-1)
    mask = jnp.take_along_axis(
        jnp.broadcast_to(sidx, stacked.shape), arg[..., None], -1)[..., 0]
    return out(Out=o, Mask=mask.astype(jnp.int32))


# --------------------------------------------------------------------------
# interpolation / image
# --------------------------------------------------------------------------
def _interp_size(ins, attrs, x):
    ost = first(ins, "OutSize")
    if ost is not None:
        v = np.asarray(ost)
        return int(v[0]), int(v[1])
    st = seq(ins, "SizeTensor")
    if st:
        return (int(np.asarray(st[0]).reshape(())),
                int(np.asarray(st[1]).reshape(())))
    sc = first(ins, "Scale")
    scale = (float(np.asarray(sc).reshape(())) if sc is not None
             else attrs.get("scale", 0.0))
    if scale and scale > 0:
        return int(x.shape[2] * scale), int(x.shape[3] * scale)
    return attrs.get("out_h", -1), attrs.get("out_w", -1)


@register_op("nearest_interp", inputs=("X", "OutSize", "SizeTensor", "Scale"),
             diff_inputs=("X",),
             attr_defaults={"out_h": -1, "out_w": -1, "scale": 0.0,
                            "interp_method": "nearest", "align_corners": True,
                            "align_mode": 1, "data_layout": "NCHW"})
def _nearest_interp(ins, attrs):
    x = first(ins, "X")
    oh, ow = _interp_size(ins, attrs, x)
    h, w = x.shape[2], x.shape[3]
    if attrs.get("align_corners", True) and oh > 1 and ow > 1:
        hi = jnp.round(jnp.arange(oh) * (h - 1) / (oh - 1)).astype(jnp.int32)
        wi = jnp.round(jnp.arange(ow) * (w - 1) / (ow - 1)).astype(jnp.int32)
    else:
        hi = jnp.floor(jnp.arange(oh) * h / oh).astype(jnp.int32)
        wi = jnp.floor(jnp.arange(ow) * w / ow).astype(jnp.int32)
    return out(Out=x[:, :, hi][:, :, :, wi])


@register_op("bilinear_interp", inputs=("X", "OutSize", "SizeTensor", "Scale"),
             diff_inputs=("X",),
             attr_defaults={"out_h": -1, "out_w": -1, "scale": 0.0,
                            "interp_method": "bilinear", "align_corners": True,
                            "align_mode": 1, "data_layout": "NCHW"})
def _bilinear_interp(ins, attrs):
    x = first(ins, "X")
    oh, ow = _interp_size(ins, attrs, x)
    h, w = x.shape[2], x.shape[3]
    ac = attrs.get("align_corners", True)
    am = attrs.get("align_mode", 1)
    if ac:
        hs = jnp.arange(oh) * ((h - 1) / max(oh - 1, 1))
        ws = jnp.arange(ow) * ((w - 1) / max(ow - 1, 1))
    elif am == 0:
        hs = jnp.clip((jnp.arange(oh) + 0.5) * h / oh - 0.5, 0, h - 1)
        ws = jnp.clip((jnp.arange(ow) + 0.5) * w / ow - 0.5, 0, w - 1)
    else:
        hs = jnp.clip(jnp.arange(oh) * h / oh, 0, h - 1)
        ws = jnp.clip(jnp.arange(ow) * w / ow, 0, w - 1)
    h0 = jnp.floor(hs).astype(jnp.int32)
    w0 = jnp.floor(ws).astype(jnp.int32)
    h1 = jnp.minimum(h0 + 1, h - 1)
    w1 = jnp.minimum(w0 + 1, w - 1)
    ah = (hs - h0)[None, None, :, None]
    aw = (ws - w0)[None, None, None, :]
    v00 = x[:, :, h0][:, :, :, w0]
    v01 = x[:, :, h0][:, :, :, w1]
    v10 = x[:, :, h1][:, :, :, w0]
    v11 = x[:, :, h1][:, :, :, w1]
    o = (v00 * (1 - ah) * (1 - aw) + v01 * (1 - ah) * aw
         + v10 * ah * (1 - aw) + v11 * ah * aw)
    return out(Out=o.astype(x.dtype))


@register_op("pixel_shuffle", inputs=("X",), attr_defaults={"upscale_factor": 1})
def _pixel_shuffle(ins, attrs):
    x = first(ins, "X")
    r = attrs.get("upscale_factor", 1)
    n, c, h, w = x.shape
    o = x.reshape(n, c // (r * r), r, r, h, w)
    o = jnp.transpose(o, (0, 1, 4, 2, 5, 3))
    return out(Out=o.reshape(n, c // (r * r), h * r, w * r))


@register_op("space_to_depth", inputs=("X",), attr_defaults={"blocksize": 1})
def _space_to_depth(ins, attrs):
    x = first(ins, "X")
    b = attrs.get("blocksize", 1)
    n, c, h, w = x.shape
    o = x.reshape(n, c, h // b, b, w // b, b)
    o = jnp.transpose(o, (0, 3, 5, 1, 2, 4))
    return out(Out=o.reshape(n, c * b * b, h // b, w // b))


@register_op("shuffle_channel", inputs=("X",), attr_defaults={"group": 1})
def _shuffle_channel(ins, attrs):
    x = first(ins, "X")
    g = attrs.get("group", 1)
    n, c, h, w = x.shape
    return out(Out=jnp.transpose(x.reshape(n, g, c // g, h, w),
                                 (0, 2, 1, 3, 4)).reshape(x.shape))


# --------------------------------------------------------------------------
# metrics
# --------------------------------------------------------------------------
@register_op("accuracy", inputs=("Out", "Indices", "Label"), no_grad=True)
def _accuracy(ins, attrs):
    idx, label = first(ins, "Indices"), first(ins, "Label")
    lbl = label.reshape(-1, 1)
    correct = jnp.any(idx == lbl, axis=1)
    num_correct = jnp.sum(correct.astype(jnp.float32))
    total = idx.shape[0]
    return out(Accuracy=(num_correct / total).reshape((1,)),
               Correct=num_correct.astype(jnp.int32).reshape((1,)),
               Total=jnp.asarray([total], jnp.int32))


@register_op("auc", inputs=("Predict", "Label", "StatPos", "StatNeg"),
             no_grad=True, stateful=True,
             attr_defaults={"curve": "ROC", "num_thresholds": 4095,
                            "slide_steps": 1})
def _auc(ins, attrs):
    pred = np.asarray(first(ins, "Predict"))
    label = np.asarray(first(ins, "Label")).reshape(-1)
    stat_pos = np.asarray(first(ins, "StatPos")).copy().reshape(-1)
    stat_neg = np.asarray(first(ins, "StatNeg")).copy().reshape(-1)
    nt = attrs.get("num_thresholds", 4095)
    buckets = np.minimum((pred[:, 1] * nt).astype(np.int64), nt)
    for b, l in zip(buckets, label):
        if l:
            stat_pos[b] += 1
        else:
            stat_neg[b] += 1
    from ..utils.metrics import auc_from_histograms
    auc_val = auc_from_histograms(stat_pos, stat_neg)
    return out(AUC=jnp.asarray([auc_val], jnp.float32),
               StatPosOut=jnp.asarray(stat_pos),
               StatNegOut=jnp.asarray(stat_neg))


# sync_batch_norm (reference: operators/sync_batch_norm_op.cu + the
# sync_batch_norm BuildStrategy flag, pybind.cc:2266): the reference
# hand-inserts NCCL allreduces of batch statistics. Here the batch is
# SHARDED over the dp mesh axis inside ONE jitted computation, so the
# kernel's plain batch-axis mean/var reductions are already global — XLA
# inserts the cross-replica psum. Same kernel as batch_norm, by design.
register_op("sync_batch_norm",
            inputs=("X", "Scale", "Bias", "Mean", "Variance",
                    "MomentumTensor"),
            diff_inputs=("X", "Scale", "Bias"),
            attr_defaults={"momentum": 0.9, "epsilon": 1e-5,
                           "data_layout": "NCHW", "is_test": False,
                           "use_global_stats": False,
                           "trainable_statistics": False,
                           "fuse_with_relu": False})(_batch_norm)
