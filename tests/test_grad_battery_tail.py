"""Finite-difference grad battery for the differentiable-op long tail
(VERDICT r2 #4). Every op in the registry that is differentiable
(no_grad=False, non-stateful) must either have a central-FD check_grad
case — here, in test_op_battery.GRAD_CASES, or in test_op_grad_checks.py
— or an explicit justified exemption in GRAD_EXEMPT below;
test_registry_coverage.py enforces the union.

Contract matched: reference op_test.py get_numeric_gradient:57 /
check_grad:170 — central finite differences of sum(output) vs the
framework's analytic grad path (append_backward over the one-op
program).

Harness notes: ONE executor and ONE forward program are reused across
every FD evaluation (as op_test.py's check_grad also does since round
5), so each perturbed run is a compiled-cache hit — this keeps ~200
cases tractable. Inputs are tiny (≤ ~30 elements) and chosen away from
kinks/ties so the FD quotient is meaningful.
"""
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import core
from paddle_tpu.fluid.framework import Program, program_guard
from paddle_tpu.fluid.backward import append_backward

rng = np.random.RandomState(7)

# central FD costs two forward runs per probed element; for big inputs a
# deterministic spread of MAX_FD_PROBES elements keeps the check honest
# (every probe still compares FD vs analytic) at bounded suite time
MAX_FD_PROBES = 12


def _fd_probe_indices(n):
    if n <= MAX_FD_PROBES:
        return list(range(n))
    # evenly spread + endpoints: catches per-axis/per-row grad bugs
    return sorted(set(np.linspace(0, n - 1, MAX_FD_PROBES).astype(int)
                      .tolist()))


def fd_check(op_type, inputs, attrs=None, out="Out", wrt=None,
             lod=None, delta=5e-3, tol=2e-2, seq_outs=(), atol=1e-7):
    """inputs: {slot: array | [(name, array), ...]}; wrt: input slots to
    grad-check (float slots only); lod: {feed_name: lod} recursive seq
    lengths for LoD feeds; out: output slot the sum-loss reads;
    seq_outs: extra output slots to declare (multi-output ops)."""
    attrs = dict(attrs or {})
    wrt = list(wrt or [])
    lod = dict(lod or {})

    def build(with_grad):
        prog = Program()
        with program_guard(prog, Program()):
            block = prog.global_block()
            in_map, feed = {}, {}
            for slot, val in inputs.items():
                entries = val if (isinstance(val, list) and val
                                  and isinstance(val[0], tuple)) \
                    else [(f"{slot}_in", val)]
                names = []
                for name, arr in entries:
                    arr = np.asarray(arr)
                    v = block.create_var(
                        name=name, shape=arr.shape,
                        dtype=core.np_to_dtype(arr.dtype),
                        lod_level=1 if name in lod else 0)
                    v.stop_gradient = slot not in wrt
                    names.append(name)
                    if name in lod:
                        t = core.LoDTensor(arr)
                        t.set_recursive_sequence_lengths(lod[name])
                        feed[name] = t
                    else:
                        feed[name] = arr
                in_map[slot] = names
            out_map = {out: [f"{out}_out"]}
            block.create_var(name=f"{out}_out")
            for extra in seq_outs:
                out_map[extra] = [f"{extra}_out"]
                block.create_var(name=f"{extra}_out")
            block.append_op(type=op_type, inputs=in_map, outputs=out_map,
                            attrs=dict(attrs))
            from paddle_tpu.fluid import layers
            target = block.var(f"{out}_out")
            target.dtype = core.VarDesc.VarType.FP32
            loss = layers.reduce_sum(target)
            if with_grad:
                append_backward(loss)
        return prog, feed, loss

    fwd_prog, feed, loss = build(False)
    grad_prog, gfeed, gloss = build(True)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = core.Scope()

    grad_fetch = []
    for slot in wrt:
        entries = inputs[slot] if (isinstance(inputs[slot], list)
                                   and isinstance(inputs[slot][0], tuple)) \
            else [(f"{slot}_in", inputs[slot])]
        grad_fetch.extend((slot, name, np.asarray(arr))
                          for name, arr in entries)
    analytic = exe.run(grad_prog, feed=gfeed,
                       fetch_list=[f"{n}@GRAD" for _, n, _ in grad_fetch],
                       scope=scope)

    def forward_sum(feed_override):
        (v,) = exe.run(fwd_prog, feed=feed_override, fetch_list=[loss],
                       scope=core.Scope())
        return float(np.asarray(v, np.float64).ravel()[0])

    for (slot, name, base), ag in zip(grad_fetch, analytic):
        x0 = base.astype(np.float64).copy()
        flat = x0.reshape(-1)

        def refeed():
            arr = x0.astype(base.dtype)
            if name in lod:
                t = core.LoDTensor(arr)
                t.set_recursive_sequence_lengths(lod[name])
                return {**feed, name: t}
            return {**feed, name: arr}

        a = np.asarray(ag, np.float64).reshape(-1)
        assert a.shape == flat.shape, \
            f"{op_type}.{slot}: grad shape {a.shape} vs input {flat.shape}"
        probe = _fd_probe_indices(flat.size)
        numeric = np.zeros(len(probe), np.float64)
        for j, i in enumerate(probe):
            orig = flat[i]
            flat[i] = orig + delta
            f_plus = forward_sum(refeed())
            flat[i] = orig - delta
            f_minus = forward_sum(refeed())
            flat[i] = orig
            numeric[j] = (f_plus - f_minus) / (2 * delta)
        ap = a[probe]
        denom = np.maximum(np.maximum(np.abs(numeric), np.abs(ap)), 1.0)
        rel = (np.abs(ap - numeric) / denom).max() if ap.size else 0.0
        assert rel <= tol, (
            f"grad check failed for {slot} of {op_type}: max rel err "
            f"{rel:.5f} > {tol}\nanalytic={ap[:8]}\nnumeric={numeric[:8]}")


# --------------------------------------------------------------------------
# case tables (family batches). Each entry:
#   (op_type, inputs, attrs, kwargs-for-fd_check)
# --------------------------------------------------------------------------
X = rng.uniform(-0.8, 0.8, (2, 3)).astype(np.float32)
POS = rng.uniform(0.4, 1.6, (2, 3)).astype(np.float32)
Y = rng.uniform(-0.8, 0.8, (2, 3)).astype(np.float32)

ELEMENTWISE = [
    ("abs", {"X": POS}, {}, {}),                  # away from the 0 kink
    ("acos", {"X": X * 0.6}, {}, {}),
    ("asin", {"X": X * 0.6}, {}, {}),
    ("atan", {"X": X}, {}, {}),
    ("cos", {"X": X}, {}, {}),
    ("cosh", {"X": X}, {}, {}),
    ("sin", {"X": X}, {}, {}),
    ("sinh", {"X": X}, {}, {}),
    ("exp", {"X": X}, {}, {}),
    ("log", {"X": POS}, {}, {}),
    ("sqrt", {"X": POS}, {}, {}),
    ("square", {"X": X}, {}, {}),
    ("sigmoid", {"X": X}, {}, {}),
    ("tanh", {"X": X}, {}, {}),
    ("relu", {"X": POS}, {}, {}),                 # away from the 0 kink
    ("leaky_relu", {"X": POS}, {"alpha": 0.1}, {}),
    ("gelu", {"X": X}, {"approximate": False}, {}),
    ("brelu", {"X": X * 0.3}, {"t_min": -0.4, "t_max": 0.4}, {}),
    ("relu6", {"X": POS}, {"threshold": 6.0}, {}),
    ("soft_relu", {"X": X}, {"threshold": 40.0}, {}),
    ("softshrink", {"X": POS}, {"lambda": 0.1}, {}),
    ("hard_shrink", {"X": POS}, {"threshold": 0.1}, {}),
    ("hard_sigmoid", {"X": X * 0.3}, {"slope": 0.2, "offset": 0.5}, {}),
    ("hard_swish", {"X": POS},
     {"threshold": 6.0, "scale": 6.0, "offset": 3.0}, {}),
    ("thresholded_relu", {"X": POS}, {"threshold": 0.2}, {}),
    ("elementwise_add", {"X": X, "Y": Y}, {}, {"wrt": ["X", "Y"]}),
    ("elementwise_min", {"X": X, "Y": Y}, {}, {}),
    ("scale", {"X": X}, {"scale": 2.5, "bias": 0.5}, {}),
    ("sum", {"X": [("sa", X), ("sb", Y)]}, {}, {}),
    ("cast", {"X": X}, {"in_dtype": 5, "out_dtype": 5}, {}),
    ("assign", {"X": X}, {}, {}),
]
for i, (n, ins, at, kw) in enumerate(ELEMENTWISE):
    kw.setdefault("wrt", ["X"])
    ELEMENTWISE[i] = (n, ins, at, kw)

MOVEMENT = [
    ("reshape2", {"X": X}, {"shape": [3, 2]}, {"wrt": ["X"]}),
    ("flatten", {"X": rng.rand(2, 2, 2).astype(np.float32)}, {"axis": 1},
     {"wrt": ["X"]}),
    ("flatten2", {"X": rng.rand(2, 2, 2).astype(np.float32)}, {"axis": 1},
     {"wrt": ["X"]}),
    ("squeeze2", {"X": X[:, None]}, {"axes": [1]}, {"wrt": ["X"]}),
    ("unsqueeze2", {"X": X}, {"axes": [0]}, {"wrt": ["X"]}),
    ("transpose2", {"X": X}, {"axis": [1, 0]}, {"wrt": ["X"]}),
    ("stack", {"X": [("ta", X), ("tb", Y)]}, {"axis": 0},
     {"out": "Y", "wrt": ["X"]}),
    ("unstack", {"X": X}, {"axis": 0, "num": 2},
     {"out": "Y", "wrt": ["X"], "multi_out_names": 2}),
    ("split", {"X": X}, {"num": 0, "sections": [1, 2], "axis": 1},
     {"wrt": ["X"], "multi_out_names": 2}),
    ("crop", {"X": X}, {"offsets": [0, 1], "shape": [2, 2]},
     {"wrt": ["X"]}),
    ("crop_tensor", {"X": X}, {"offsets": [0, 1], "shape": [2, 2]},
     {"wrt": ["X"]}),
    ("flip", {"X": X}, {"axis": [0]}, {"wrt": ["X"]}),
    ("reverse", {"X": X}, {"axis": [1]}, {"wrt": ["X"]}),
    ("expand_as", {"X": X[:1], "target_tensor": X}, {}, {"wrt": ["X"]}),
    ("pad2d", {"X": rng.rand(1, 2, 2, 2).astype(np.float32)},
     {"paddings": [1, 0, 0, 1], "mode": "constant", "pad_value": 0.0},
     {"wrt": ["X"]}),
    ("pad_constant_like",
     {"X": np.zeros((3, 4), np.float32), "Y": X}, {}, {"wrt": ["Y"]}),
    ("space_to_depth", {"X": rng.rand(1, 1, 2, 2).astype(np.float32)},
     {"blocksize": 2}, {"wrt": ["X"]}),
    ("pixel_shuffle", {"X": rng.rand(1, 4, 2, 2).astype(np.float32)},
     {"upscale_factor": 2}, {"wrt": ["X"]}),
    ("shuffle_channel", {"X": rng.rand(1, 4, 2, 2).astype(np.float32)},
     {"group": 2}, {"wrt": ["X"]}),
    ("where", {"Condition": np.asarray([[True, False, True],
                                        [False, True, False]]),
               "X": X, "Y": Y}, {}, {"wrt": ["X", "Y"]}),
    ("meshgrid", {"X": [("mga", np.asarray([1., 2.], np.float32)),
                        ("mgb", np.asarray([3., 4., 5.], np.float32))]},
     {}, {"wrt": ["X"], "multi_out_names": 2}),
    ("tril_triu", {"X": X}, {"diagonal": 0, "lower": False},
     {"wrt": ["X"]}),
    ("diag_embed", {"Input": X},
     {"offset": 0, "dim1": -2, "dim2": -1}, {"wrt": ["Input"]}),
    ("strided_slice", {"Input": X},
     {"axes": [1], "starts": [0], "ends": [3], "strides": [2]},
     {"wrt": ["Input"]}),
    ("scatter", {"X": X.copy(), "Ids": np.asarray([1], np.int32),
                 "Updates": np.ones((1, 3), np.float32)},
     {"overwrite": True}, {"wrt": ["X"]}),
    ("scatter_nd_add",
     {"X": X.copy(), "Index": np.asarray([[0]], np.int32),
      "Updates": np.ones((1, 3), np.float32)}, {}, {"wrt": ["X"]}),
    ("increment", {"X": np.asarray([1.5], np.float32)}, {"step": 1.0},
     {"wrt": ["X"]}),
    ("partial_concat", {"X": [("pca", X), ("pcb", Y)]},
     {"start_index": 0, "length": 2}, {"wrt": ["X"]}),
    ("partial_sum", {"X": [("psa", X), ("psb", Y)]},
     {"start_index": 0, "length": 2}, {"wrt": ["X"]}),
]

REDUCE_LINALG = [
    ("reduce_sum", {"X": X}, {"dim": [1]}, {"wrt": ["X"]}),
    ("reduce_mean", {"X": X}, {"dim": [0]}, {"wrt": ["X"]}),
    ("reduce_max", {"X": rng.permutation(6).reshape(2, 3).astype(
        np.float32)}, {"dim": [1]}, {"wrt": ["X"]}),
    ("reduce_min", {"X": rng.permutation(6).reshape(2, 3).astype(
        np.float32) + 10}, {"dim": [1]}, {"wrt": ["X"]}),
    ("mean", {"X": X}, {}, {"wrt": ["X"]}),
    ("matmul", {"X": X, "Y": Y.T}, {"transpose_X": False,
                                    "transpose_Y": False, "alpha": 1.0},
     {"wrt": ["X", "Y"]}),
    ("mul", {"X": X, "Y": Y.T}, {"x_num_col_dims": 1,
                                 "y_num_col_dims": 1},
     {"wrt": ["X", "Y"]}),
    ("dot", {"X": X[0], "Y": Y[0]}, {}, {"wrt": ["X", "Y"]}),
    ("l1_norm", {"X": POS}, {}, {"wrt": ["X"]}),
    ("inverse", {"Input": (np.eye(3) * 2 + 0.1 * rng.rand(3, 3)).astype(
        np.float32)}, {}, {"out": "Output", "wrt": ["Input"]}),
    ("cholesky", {"X": None}, {"upper": False}, {"wrt": ["X"]}),
    ("cross", {"X": X, "Y": Y}, {"dim": -1}, {"wrt": ["X", "Y"]}),
    ("bilinear_tensor_product",
     {"X": X[:1], "Y": Y[:1], "Weight": rng.rand(2, 3, 3).astype(
         np.float32)}, {}, {"wrt": ["X", "Y", "Weight"]}),
    ("fc", {"Input": X, "W": rng.rand(3, 2).astype(np.float32),
            "Bias": rng.rand(2).astype(np.float32)},
     {"in_num_col_dims": 1, "activation_type": ""},
     {"wrt": ["Input", "W", "Bias"]}),
    ("batch_fc", {"Input": rng.rand(2, 2, 3).astype(np.float32),
                  "W": rng.rand(2, 3, 2).astype(np.float32),
                  "Bias": rng.rand(2, 1, 2).astype(np.float32)}, {},
     {"wrt": ["Input", "W", "Bias"]}),
    ("fsp", {"X": rng.rand(1, 2, 3, 3).astype(np.float32),
             "Y": rng.rand(1, 3, 3, 3).astype(np.float32)}, {},
     {"wrt": ["X", "Y"]}),
]
# cholesky needs an SPD matrix built from the same rng stream
_a = rng.rand(3, 3).astype(np.float32)
REDUCE_LINALG[10] = ("cholesky",
                     {"X": (_a @ _a.T + 3 * np.eye(3)).astype(np.float32)},
                     {"upper": False}, {"wrt": ["X"]})


# The FD battery's long-tail heavyweights (recurrent/fused while-loop
# ops, detection kernels, 30-power-iter spectral_norm): each costs
# 6-20s of COMPILE-dominated wall time for an op nothing on the hot
# paths touches — together ~140s of the tier-1 window (measured
# --durations, PR 13 suite-time buyback; the PR 8 precedent). They
# carry `slow` so the FULL tier still FD-checks every one of them;
# the per-commit tier keeps the battery's ~190 fast cases, and
# test_registry_coverage still enforces the union.
_SLOW_TAIL = {"spectral_norm", "fusion_lstm", "fusion_gru", "roi_align",
              "yolov3_loss", "linear_chain_crf", "dynamic_lstm",
              "dynamic_lstmp", "dynamic_gru", "gru", "lstm",
              "deformable_conv", "bicubic_interp",
              # r19 buyback: the next ~53s of the same compile-dominated
              # class (3-6s each, --durations measured) — off-hot-path
              # fused/detection/sampling kernels whose op math stays
              # pinned per-commit by test_op_battery*; hierarchical_
              # sigmoid additionally trains end-to-end per-commit in
              # test_loss_extra_ops
              "fusion_seqpool_cvm_concat", "hierarchical_sigmoid",
              "warpctc", "fused_embedding_eltwise_layernorm",
              "trilinear_interp", "gru_unit", "grid_sampler",
              "fusion_seqpool_concat", "deformable_conv_v1",
              "deformable_psroi_pooling", "rank_attention",
              "sample_logits",
              # r19 second buyback (fleet PR): the suite regrew past the
              # 870s window (launch parity now RUNS instead of failing,
              # fleet suite added, box slower) — the next ~60s of the
              # same compile-dominated off-hot-path class (2-5s each,
              # --durations measured). Hot-path ops (batch_norm, plain
              # conv2d/pool, bilinear_interp, nll_loss) deliberately
              # stay; everything here keeps forward/op-math coverage in
              # test_op_battery* per-commit and full-tier FD checks.
              "fused_fc_elementwise_layernorm", "skip_layernorm",
              "multihead_matmul", "fusion_repeated_fc_relu",
              "conv2d_fusion", "fusion_seqconv_eltadd_relu",
              "conv_shift", "depthwise_conv2d_transpose", "conv3d",
              "conv3d_transpose", "sequence_conv", "prroi_pool",
              "psroi_pool", "fused_embedding_seq_pool", "bpr_loss",
              "polygon_box_transform", "fsp", "batch_fc", "inverse",
              "var_conv_2d"}


def _mark_slow_tail(cases):
    return [pytest.param(c, marks=pytest.mark.slow)
            if c[0] in _SLOW_TAIL else c for c in cases]



CASES_BATCH1 = _mark_slow_tail(ELEMENTWISE + MOVEMENT + REDUCE_LINALG)


def _ids(c):
    return c[0]


@pytest.mark.parametrize("case", CASES_BATCH1, ids=_ids)
def test_grad_tail_batch1(case):
    name, inputs, attrs, kw = case
    kw = dict(kw)
    n_outs = kw.pop("multi_out_names", 0)
    if n_outs:
        # multi-output slot: declare n named outputs, sum the first
        out_slot = kw.pop("out", "Out")
        fd_check_multi(name, inputs, attrs, out_slot, n_outs, **kw)
    else:
        fd_check(name, inputs, attrs, **kw)


def fd_check_multi(op_type, inputs, attrs, out_slot, n_outs, wrt=None,
                   **kw):
    """Variant for ops whose output slot carries N vars (split/unstack/
    meshgrid): loss sums ALL of them so every path is grad-checked."""
    wrt = list(wrt or [])
    attrs = dict(attrs or {})

    def build(with_grad):
        prog = Program()
        with program_guard(prog, Program()):
            block = prog.global_block()
            in_map, feed = {}, {}
            for slot, val in inputs.items():
                entries = val if (isinstance(val, list) and val
                                  and isinstance(val[0], tuple)) \
                    else [(f"{slot}_in", val)]
                names = []
                for name, arr in entries:
                    arr = np.asarray(arr)
                    v = block.create_var(name=name, shape=arr.shape,
                                         dtype=core.np_to_dtype(arr.dtype))
                    v.stop_gradient = slot not in wrt
                    names.append(name)
                    feed[name] = arr
                in_map[slot] = names
            out_names = [f"{out_slot}_out{i}" for i in range(n_outs)]
            for n in out_names:
                block.create_var(name=n)
            block.append_op(type=op_type, inputs=in_map,
                            outputs={out_slot: out_names},
                            attrs=dict(attrs))
            from paddle_tpu.fluid import layers
            parts = []
            for n in out_names:
                v = block.var(n)
                v.dtype = core.VarDesc.VarType.FP32
                parts.append(layers.reduce_sum(v))
            loss = layers.reduce_sum(
                layers.concat([layers.reshape(p, [1]) for p in parts], 0))
            if with_grad:
                append_backward(loss)
        return prog, feed, loss

    fwd_prog, feed, loss = build(False)
    grad_prog, gfeed, gloss = build(True)
    exe = fluid.Executor(fluid.CPUPlace())

    grad_fetch = []
    for slot in wrt:
        entries = inputs[slot] if (isinstance(inputs[slot], list)
                                   and isinstance(inputs[slot][0], tuple)) \
            else [(f"{slot}_in", inputs[slot])]
        grad_fetch.extend((name, np.asarray(arr)) for name, arr in entries)
    analytic = exe.run(grad_prog, feed=gfeed,
                       fetch_list=[f"{n}@GRAD" for n, _ in grad_fetch],
                       scope=core.Scope())

    delta, tol = kw.get("delta", 5e-3), kw.get("tol", 2e-2)
    for (name, base), ag in zip(grad_fetch, analytic):
        x0 = base.astype(np.float64).copy()
        flat = x0.reshape(-1)
        a = np.asarray(ag, np.float64).reshape(-1)
        probe = _fd_probe_indices(flat.size)
        numeric = np.zeros(len(probe), np.float64)
        for j, i in enumerate(probe):
            orig = flat[i]
            for sgn in (1, -1):
                flat[i] = orig + sgn * delta
                (v,) = exe.run(fwd_prog,
                               feed={**feed, name: x0.astype(base.dtype)},
                               fetch_list=[loss], scope=core.Scope())
                if sgn == 1:
                    fp = float(np.asarray(v).ravel()[0])
                else:
                    fm = float(np.asarray(v).ravel()[0])
            flat[i] = orig
            numeric[j] = (fp - fm) / (2 * delta)
        ap = a[probe]
        denom = np.maximum(np.maximum(np.abs(numeric), np.abs(ap)), 1.0)
        rel = (np.abs(ap - numeric) / denom).max() if ap.size else 0.0
        assert rel <= tol, (
            f"grad check failed for {name} of {op_type}: {rel:.5f}\n"
            f"analytic={ap[:8]}\nnumeric={numeric[:8]}")


# --------------------------------------------------------------------------
# batch 2: conv / pool / interp / norm / losses / embedding / fused
# --------------------------------------------------------------------------
def _conv_cases():
    x4 = rng.rand(1, 2, 3, 3).astype(np.float32)
    cases = [
        ("conv2d_transpose",
         {"Input": x4, "Filter": rng.rand(2, 2, 2, 2).astype(np.float32)},
         {"strides": [1, 1], "paddings": [0, 0], "dilations": [1, 1],
          "groups": 1}, {"out": "Output", "wrt": ["Input", "Filter"]}),
        ("depthwise_conv2d_transpose",
         {"Input": x4, "Filter": rng.rand(2, 1, 2, 2).astype(np.float32)},
         {"strides": [1, 1], "paddings": [0, 0], "dilations": [1, 1],
          "groups": 2}, {"out": "Output", "wrt": ["Input", "Filter"]}),
        ("conv3d",
         {"Input": rng.rand(1, 1, 2, 3, 3).astype(np.float32),
          "Filter": rng.rand(1, 1, 2, 2, 2).astype(np.float32)},
         {"strides": [1, 1, 1], "paddings": [0, 0, 0],
          "dilations": [1, 1, 1], "groups": 1},
         {"out": "Output", "wrt": ["Input", "Filter"]}),
        ("conv3d_transpose",
         {"Input": rng.rand(1, 1, 2, 2, 2).astype(np.float32),
          "Filter": rng.rand(1, 1, 2, 2, 2).astype(np.float32)},
         {"strides": [1, 1, 1], "paddings": [0, 0, 0],
          "dilations": [1, 1, 1], "groups": 1},
         {"out": "Output", "wrt": ["Input", "Filter"]}),
        ("conv_shift",
         {"X": rng.rand(2, 5).astype(np.float32),
          "Y": rng.rand(2, 3).astype(np.float32)}, {},
         {"wrt": ["X", "Y"]}),
        ("conv2d_fusion",
         {"Input": x4, "Filter": rng.rand(2, 2, 2, 2).astype(np.float32),
          "Bias": np.full((2,), 3.0, np.float32)},  # relu stays linear
         {"strides": [1, 1], "paddings": [0, 0], "dilations": [1, 1],
          "activation": "relu"},
         {"out": "Output", "wrt": ["Input", "Filter", "Bias"]}),
    ]
    return cases


def _pool_interp_cases():
    xd = (rng.permutation(16).reshape(1, 1, 4, 4) * 0.1 + 0.05).astype(
        np.float32)
    x3 = rng.rand(1, 1, 3, 3).astype(np.float32)
    return [
        ("max_pool2d_with_index", {"X": xd},
         {"ksize": [2, 2], "strides": [2, 2], "paddings": [0, 0]},
         {"wrt": ["X"], "seq_outs": ["Mask"]}),
        ("max_pool3d_with_index",
         {"X": (rng.permutation(8).reshape(1, 1, 2, 2, 2) * 0.1
                + 0.05).astype(np.float32)},
         {"ksize": [2, 2, 2], "strides": [2, 2, 2], "paddings": [0, 0, 0]},
         {"wrt": ["X"], "seq_outs": ["Mask"]}),
        ("spp", {"X": rng.rand(1, 2, 4, 4).astype(np.float32)},
         {"pyramid_height": 2, "pooling_type": "avg"}, {"wrt": ["X"]}),
        ("maxout",
         {"X": (rng.permutation(16).reshape(1, 4, 2, 2) * 0.1).astype(
             np.float32)}, {"groups": 2, "axis": 1}, {"wrt": ["X"]}),
        ("bilinear_interp", {"X": x3},
         {"out_h": 5, "out_w": 5, "interp_method": "bilinear",
          "align_corners": True}, {"wrt": ["X"]}),
        ("nearest_interp", {"X": x3},
         {"out_h": 5, "out_w": 5, "interp_method": "nearest",
          "align_corners": True}, {"wrt": ["X"]}),
        ("bicubic_interp", {"X": x3},
         {"out_h": 5, "out_w": 5, "interp_method": "bicubic",
          "align_corners": True}, {"wrt": ["X"]}),
        ("trilinear_interp",
         {"X": rng.rand(1, 1, 2, 3, 3).astype(np.float32)},
         {"out_d": 3, "out_h": 4, "out_w": 4,
          "interp_method": "trilinear", "align_corners": True},
         {"wrt": ["X"]}),
        ("unfold", {"X": rng.rand(1, 2, 3, 3).astype(np.float32)},
         {"kernel_sizes": [2, 2], "strides": [1, 1],
          "paddings": [0, 0, 0, 0], "dilations": [1, 1]},
         {"out": "Y", "wrt": ["X"]}),
        ("temporal_shift", {"X": rng.rand(2, 2, 2, 2).astype(np.float32)},
         {"seg_num": 2, "shift_ratio": 0.25}, {"wrt": ["X"]}),
        ("unpool",
         {"X": rng.rand(1, 1, 2, 2).astype(np.float32),
          "Indices": np.asarray([[[[0, 3], [8, 15]]]], np.int32)},
         {"unpooling_type": "max", "ksize": [2, 2], "strides": [2, 2],
          "paddings": [0, 0]}, {"wrt": ["X"]}),
        ("grid_sampler",
         {"X": rng.rand(1, 1, 3, 3).astype(np.float32),
          "Grid": (rng.uniform(-0.7, 0.7, (1, 3, 3, 2)) + 0.02).astype(
              np.float32)},
         {"mode": "bilinear", "padding_mode": "zeros",
          "align_corners": True},
         {"out": "Output", "wrt": ["X", "Grid"]}),
        ("affine_grid",
         {"Theta": rng.rand(1, 2, 3).astype(np.float32)},
         {"output_shape": [1, 1, 3, 3], "align_corners": True},
         {"out": "Output", "wrt": ["Theta"]}),
        ("pixel_shuffle", {"X": rng.rand(1, 4, 2, 2).astype(np.float32)},
         {"upscale_factor": 2}, {"wrt": ["X"]}),
    ]


def _norm_cases():
    c = 3
    return [
        ("batch_norm",
         {"X": rng.rand(4, c).astype(np.float32),
          "Scale": rng.rand(c).astype(np.float32) + 0.5,
          "Bias": rng.rand(c).astype(np.float32),
          "Mean": np.zeros(c, np.float32),
          "Variance": np.ones(c, np.float32)},
         {"momentum": 0.9, "epsilon": 1e-5, "is_test": False,
          "data_layout": "NCHW"},
         {"out": "Y", "wrt": ["X", "Scale", "Bias"],
          "seq_outs": ["MeanOut", "VarianceOut", "SavedMean",
                       "SavedVariance"], "tol": 3e-2}),
        ("lrn", {"X": rng.rand(1, 3, 2, 2).astype(np.float32)},
         {"n": 3, "k": 1.0, "alpha": 1e-2, "beta": 0.75},
         {"wrt": ["X"], "seq_outs": ["MidOut"]}),
        ("affine_channel",
         {"X": rng.rand(1, 2, 2, 2).astype(np.float32),
          "Scale": rng.rand(2).astype(np.float32) + 0.5,
          "Bias": rng.rand(2).astype(np.float32)},
         {"data_layout": "NCHW"}, {"wrt": ["X", "Scale", "Bias"]}),
        # the analytic grad treats u/v as constants (the reference's
        # buffer semantics) — FD agrees only once power iteration has
        # converged, hence the high power_iters
        ("spectral_norm",
         {"Weight": rng.randn(3, 4).astype(np.float32),
          "U": rng.randn(3).astype(np.float32),
          "V": rng.randn(4).astype(np.float32)},
         {"dim": 0, "power_iters": 30, "eps": 1e-12},
         {"wrt": ["Weight"], "tol": 5e-2}),
        ("data_norm",
         {"X": rng.rand(3, 2).astype(np.float32),
          "BatchSize": np.full(2, 10.0, np.float32),
          "BatchSum": np.full(2, 5.0, np.float32),
          "BatchSquareSum": np.full(2, 12.0, np.float32)},
         {"epsilon": 1e-4}, {"out": "Y", "wrt": ["X"]}),
        ("l1_norm", {"X": POS}, {}, {"wrt": ["X"]}),
        ("dgc_clip_by_norm",
         {"X": X, "current_step": np.asarray([5.0], np.float32)},
         {"rampup_begin_step": 0.0, "max_norm": 0.1}, {"wrt": ["X"]}),
    ]


def _loss_cases():
    sm = rng.uniform(0.2, 0.8, (3, 4)).astype(np.float32)
    sm = sm / sm.sum(-1, keepdims=True)
    ilab = rng.randint(0, 4, (3, 1)).astype(np.int64)
    return [
        ("cross_entropy", {"X": sm, "Label": ilab},
         {"soft_label": False, "ignore_index": -100},
         {"out": "Y", "wrt": ["X"]}),
        ("cross_entropy2", {"X": sm, "Label": ilab}, {},
         {"out": "Y", "wrt": ["X"],
          "seq_outs": ["XShape", "MatchX"]}),
        ("bpr_loss", {"X": rng.rand(3, 4).astype(np.float32),
                      "Label": ilab}, {}, {"out": "Y", "wrt": ["X"]}),
        ("nll_loss", {"X": np.log(sm), "Label": ilab[:, 0]},
         {"reduction": "mean", "ignore_index": -100},
         {"wrt": ["X"], "seq_outs": ["Total_weight"]}),
        ("sigmoid_focal_loss",
         {"X": rng.uniform(-1, 1, (3, 2)).astype(np.float32),
          "Label": rng.randint(0, 2, (3, 1)).astype(np.int32),
          "FgNum": np.asarray([2], np.int32)},
         {"gamma": 2.0, "alpha": 0.25}, {"wrt": ["X"]}),
        ("modified_huber_loss",
         {"X": rng.uniform(-0.5, 0.5, (3, 1)).astype(np.float32),
          "Y": np.asarray([[0.], [1.], [1.]], np.float32)}, {},
         {"wrt": ["X"], "seq_outs": ["IntermediateVal"]}),
        ("margin_rank_loss",
         {"Label": np.ones((2, 1), np.float32),
          "X1": np.asarray([[0.2], [0.1]], np.float32),
          "X2": np.asarray([[0.9], [1.0]], np.float32)},
         {"margin": 0.1},
         {"wrt": ["X1", "X2"], "seq_outs": ["Activated"]}),
        ("hinge_loss",
         {"Logits": np.asarray([[0.3], [0.2]], np.float32),
          "Labels": np.ones((2, 1), np.float32)}, {},
         {"out": "Loss", "wrt": ["Logits"]}),
        ("teacher_student_sigmoid_loss",
         {"X": rng.uniform(-0.5, 0.5, (3, 1)).astype(np.float32),
          "Label": rng.uniform(0.1, 0.9, (3, 1)).astype(np.float32)},
         {}, {"out": "Y", "wrt": ["X"]}),
        ("smooth_l1_loss",
         {"X": X * 0.1, "Y": Y * 0.1,
          "InsideWeight": np.ones_like(X),
          "OutsideWeight": np.ones_like(X)},
         {"sigma": 1.0}, {"wrt": ["X"], "seq_outs": ["Diff"]}),
        ("center_loss",
         {"X": rng.rand(2, 3).astype(np.float32),
          "Label": np.asarray([[0], [1]], np.int64),
          "Centers": rng.rand(2, 3).astype(np.float32),
          "CenterUpdateRate": np.asarray([0.5], np.float32)},
         {"cluster_num": 2, "need_update": False},
         {"out": "Loss", "wrt": ["X"],
          "seq_outs": ["SampleCenterDiff", "CentersOut"]}),
        ("cvm",
         {"X": rng.rand(2, 5).astype(np.float32) + 0.5,
          "CVM": np.ones((2, 2), np.float32)},
         {"use_cvm": True}, {"out": "Y", "wrt": ["X"]}),
        ("add_position_encoding",
         {"X": rng.rand(1, 3, 4).astype(np.float32)},
         {"alpha": 1.0, "beta": 1.0}, {"wrt": ["X"]}),
        ("polygon_box_transform",
         {"Input": (rng.uniform(0.3, 1.0, (1, 8, 2, 2))).astype(
             np.float32)}, {}, {"out": "Output", "wrt": ["Input"]}),
    ]


def _embed_fused_cases():
    ids = np.asarray([[1], [3], [0], [2]], np.int64)
    W5 = rng.rand(5, 3).astype(np.float32)
    return [
        ("lookup_table", {"W": W5, "Ids": ids}, {"padding_idx": -1},
         {"wrt": ["W"]}),
        ("lookup_table_v2", {"W": W5, "Ids": ids[:, 0]},
         {"padding_idx": -1}, {"wrt": ["W"]}),
        ("top_k", {"X": (rng.permutation(8).reshape(2, 4) * 0.1).astype(
            np.float32)}, {"k": 2},
         {"wrt": ["X"], "seq_outs": ["Indices"]}),
        ("top_k_v2",
         {"X": (rng.permutation(8).reshape(2, 4) * 0.1).astype(
             np.float32)}, {"k": 2, "axis": -1, "largest": True,
                            "sorted": True},
         {"wrt": ["X"], "seq_outs": ["Indices"]}),
        ("multihead_matmul",
         {"Input": rng.rand(1, 2, 3, 2, 2).astype(np.float32)},
         {"head_number": 2, "alpha": 0.7},
         {"wrt": ["Input"]}),
        ("skip_layernorm",
         {"X": rng.rand(1, 2, 4).astype(np.float32),
          "Y": rng.rand(1, 2, 4).astype(np.float32),
          "Scale": rng.rand(4).astype(np.float32) + 0.5,
          "Bias": rng.rand(4).astype(np.float32)},
         {"epsilon": 1e-5}, {"wrt": ["X", "Y", "Scale", "Bias"],
                             "tol": 3e-2}),
        ("fused_fc_elementwise_layernorm",
         {"X": rng.rand(2, 3).astype(np.float32),
          "W": rng.rand(3, 4).astype(np.float32),
          "Bias0": rng.rand(4).astype(np.float32),
          "Y": rng.rand(2, 4).astype(np.float32),
          "Scale": rng.rand(4).astype(np.float32) + 0.5,
          "Bias1": rng.rand(4).astype(np.float32)},
         {"epsilon": 1e-5, "begin_norm_axis": 1},
         {"wrt": ["X", "W", "Y"], "tol": 3e-2}),
        ("fusion_squared_mat_sub",
         {"X": rng.rand(2, 3).astype(np.float32),
          "Y": rng.rand(3, 2).astype(np.float32)},
         {"scalar": 0.5},
         {"wrt": ["X", "Y"],
          "seq_outs": ["SquaredX", "SquaredY", "SquaredXY"]}),
        ("fusion_repeated_fc_relu",
         {"X": rng.rand(2, 3).astype(np.float32),
          "W": [("frw0", rng.rand(3, 4).astype(np.float32)),
                ("frw1", rng.rand(4, 2).astype(np.float32))],
          "Bias": [("frb0", np.full(4, 2.0, np.float32)),
                   ("frb1", np.full(2, 2.0, np.float32))]},
         {}, {"wrt": ["X", "W"], "seq_outs": ["ReluOut"]}),
        ("fusion_transpose_flatten_concat",
         {"X": [("ftfa", rng.rand(1, 2, 2).astype(np.float32)),
                ("ftfb", rng.rand(1, 2, 2).astype(np.float32))]},
         {"trans_axis": [0, 2, 1], "flatten_axis": 1, "concat_axis": 1},
         {"wrt": ["X"]}),
        ("rnn_memory_helper", {"X": X}, {}, {"wrt": ["X"]}),
        ("gru_unit",
         {"Input": rng.rand(2, 6).astype(np.float32),
          "HiddenPrev": rng.rand(2, 2).astype(np.float32),
          "Weight": rng.rand(2, 6).astype(np.float32)},
         {"activation": "tanh", "gate_activation": "sigmoid"},
         {"out": "Hidden", "wrt": ["Input", "HiddenPrev", "Weight"],
          "seq_outs": ["Gate", "ResetHiddenPrev"]}),
        ("lstm_unit",
         {"X": rng.rand(2, 8).astype(np.float32),
          "C_prev": rng.rand(2, 2).astype(np.float32)},
         {"forget_bias": 0.0},
         {"out": "H", "wrt": ["X", "C_prev"], "seq_outs": ["C"]}),
    ]


CASES_BATCH2 = _mark_slow_tail(
    _conv_cases() + _pool_interp_cases() + _norm_cases()
    + _loss_cases() + _embed_fused_cases())


@pytest.mark.parametrize("case", CASES_BATCH2, ids=_ids)
def test_grad_tail_batch2(case):
    name, inputs, attrs, kw = case
    fd_check(name, inputs, attrs, **kw)


# --------------------------------------------------------------------------
# batch 3: LoD/sequence ops, RNN family, ROI/detection, sampled losses
# --------------------------------------------------------------------------
def _seq_cases():
    T, D = 5, 2
    xs = rng.rand(T, D).astype(np.float32)
    lod = [[2, 3]]
    H = 2
    return [
        ("sequence_pool", {"X": xs}, {"pooltype": "SUM"},
         {"lod": {"X_in": lod}, "wrt": ["X"], "seq_outs": ["MaxIndex"]}),
        ("sequence_softmax", {"X": rng.rand(T, 1).astype(np.float32)},
         {}, {"lod": {"X_in": lod}, "wrt": ["X"]}),
        ("sequence_reverse", {"X": xs}, {},
         {"out": "Y", "lod": {"X_in": lod}, "wrt": ["X"]}),
        ("sequence_concat",
         {"X": [("sca", xs), ("scb", rng.rand(4, D).astype(np.float32))]},
         {}, {"lod": {"sca": lod, "scb": [[1, 3]]}, "wrt": ["X"]}),
        ("sequence_expand",
         {"X": rng.rand(2, D).astype(np.float32), "Y": np.zeros((5, 1),
                                                               np.float32)},
         {"ref_level": 0},
         {"lod": {"X_in": [[1, 1]], "Y_in": [[2, 3]]}, "wrt": ["X"]}),
        ("sequence_expand_as",
         {"X": rng.rand(2, D).astype(np.float32),
          "Y": np.zeros((5, 1), np.float32)}, {},
         {"lod": {"Y_in": [[2, 3]]}, "wrt": ["X"]}),
        ("sequence_pad",
         {"X": xs, "PadValue": np.zeros((1,), np.float32)},
         {"padded_length": 3},
         {"lod": {"X_in": lod}, "wrt": ["X"], "seq_outs": ["Length"]}),
        ("sequence_unpad",
         {"X": rng.rand(2, 3, D).astype(np.float32),
          "Length": np.asarray([2, 3], np.int64)}, {}, {"wrt": ["X"]}),
        ("sequence_reshape", {"X": rng.rand(4, 2).astype(np.float32)},
         {"new_dim": 4}, {"lod": {"X_in": [[2, 2]]}, "wrt": ["X"]}),
        ("sequence_slice",
         {"X": xs, "Offset": np.asarray([[0], [1]], np.int64),
          "Length": np.asarray([[2], [1]], np.int64)}, {},
         {"lod": {"X_in": lod}, "wrt": ["X"]}),
        ("sequence_scatter",
         {"X": rng.rand(2, 4).astype(np.float32),
          "Ids": np.asarray([[1], [2], [0]], np.int64),
          "Updates": rng.rand(3, 1).astype(np.float32)}, {},
         {"lod": {"Ids_in": [[2, 1]], "Updates_in": [[2, 1]]},
          "wrt": ["X", "Updates"]}),
        ("sequence_conv",
         {"X": xs, "Filter": rng.rand(3 * D, 2).astype(np.float32)},
         {"contextLength": 3, "contextStart": -1, "contextStride": 1},
         {"lod": {"X_in": lod}, "wrt": ["X", "Filter"]}),
        ("row_conv",
         {"X": xs, "Filter": rng.rand(2, D).astype(np.float32)}, {},
         {"lod": {"X_in": lod}, "wrt": ["X", "Filter"]}),
        ("sequence_topk_avg_pooling",
         {"X": (rng.permutation(10).astype(np.float32) * 0.1
                ).reshape(10, 1),
          "ROW": np.zeros((5, 1), np.float32),
          "COLUMN": np.zeros((2, 1), np.float32)},
         {"topks": [1], "channel_num": 1},
         {"lod": {"X_in": [[10]], "ROW_in": [[5]], "COLUMN_in": [[2]]},
          "wrt": ["X"], "seq_outs": ["pos"]}),
        ("match_matrix_tensor",
         {"X": rng.rand(2, D).astype(np.float32),
          "Y": rng.rand(3, D).astype(np.float32),
          "W": rng.rand(D, 1, D).astype(np.float32)},
         {"dim_t": 1},
         {"lod": {"X_in": [[2]], "Y_in": [[3]]},
          "wrt": ["X", "Y", "W"], "seq_outs": ["Tmp"]}),
        ("im2sequence", {"X": rng.rand(1, 1, 3, 3).astype(np.float32)},
         {"kernels": [2, 2], "strides": [1, 1], "paddings": [0, 0, 0, 0]},
         {"wrt": ["X"]}),
        ("lod_reset", {"X": xs}, {"target_lod": [2, 3]},
         {"lod": {"X_in": lod}, "wrt": ["X"]}),
        ("lod_append", {"X": xs}, {"level": [0, 2, 5]},
         {"wrt": ["X"]}),
        ("fused_embedding_seq_pool",
         {"W": rng.rand(5, 3).astype(np.float32),
          "Ids": np.asarray([[1], [3], [0], [2]], np.int64)},
         {"combiner": "sum"},
         {"lod": {"Ids_in": [[2, 2]]}, "wrt": ["W"]}),
        ("fusion_seqpool_concat",
         {"X": [("fspa", xs), ("fspb", rng.rand(T, D).astype(
             np.float32))]},
         {"pooltype": "SUM", "axis": 1},
         {"lod": {"fspa": lod, "fspb": lod}, "wrt": ["X"]}),
        ("fusion_seqpool_cvm_concat",
         {"X": [("fcva", xs + 0.5), ("fcvb", rng.rand(T, D).astype(
             np.float32) + 0.5)],
          "CVM": np.ones((2, 2), np.float32)},
         {"pooltype": "SUM", "axis": 1, "use_cvm": True},
         {"lod": {"fcva": lod, "fcvb": lod}, "wrt": ["X"]}),
        ("fusion_seqconv_eltadd_relu",
         {"X": xs, "Filter": rng.rand(3 * D, 2).astype(np.float32),
          "Bias": np.full((2,), 2.0, np.float32)},
         {"contextLength": 3, "contextStart": -1, "contextStride": 1},
         {"lod": {"X_in": lod}, "wrt": ["X", "Filter", "Bias"],
          "seq_outs": ["ColMat"]}),
        ("fusion_seqexpand_concat_fc",
         {"X": [("fsea", xs), ("fseb", rng.rand(2, 3).astype(
             np.float32))],
          "FCWeight": rng.rand(D + 3, 2).astype(np.float32),
          "FCBias": rng.rand(2).astype(np.float32)},
         {"fc_activation": "identity"},
         {"lod": {"fsea": lod}, "wrt": ["FCWeight", "FCBias"],
          "seq_outs": ["FCOut"]}),
        ("warpctc",
         {"Logits": rng.randn(4, 3).astype(np.float32),
          "Label": np.asarray([[1], [2]], np.int32)},
         {"blank": 0, "norm_by_times": False},
         {"out": "Loss", "lod": {"Logits_in": [[4]], "Label_in": [[2]]},
          "wrt": ["Logits"], "tol": 3e-2}),
        ("linear_chain_crf",
         {"Emission": rng.rand(4, 3).astype(np.float32),
          "Transition": rng.rand(5, 3).astype(np.float32),
          "Label": np.asarray([[0], [2], [1], [0]], np.int64)},
         {},
         {"out": "LogLikelihood",
          "lod": {"Emission_in": [[4]], "Label_in": [[4]]},
          "wrt": ["Emission", "Transition"],
          "seq_outs": ["Alpha", "EmissionExps", "TransitionExps"],
          "tol": 3e-2}),
    ]


def _rnn_cases():
    T, D, H = 5, 2, 2
    lod = [[2, 3]]
    xg = rng.rand(T, 3 * H).astype(np.float32)
    xl = rng.rand(T, 4 * H).astype(np.float32)
    w_flat_sz = D * 4 * H + H * 4 * H + 4 * H
    return [
        ("dynamic_gru",
         {"Input": xg, "Weight": rng.rand(H, 3 * H).astype(np.float32),
          "Bias": rng.rand(1, 3 * H).astype(np.float32)},
         {"activation": "tanh", "gate_activation": "sigmoid",
          "is_reverse": False},
         {"out": "Hidden", "lod": {"Input_in": lod},
          "wrt": ["Input", "Weight", "Bias"]}),
        ("gru",
         {"Input": xg, "Weight": rng.rand(H, 3 * H).astype(np.float32),
          "Bias": rng.rand(1, 3 * H).astype(np.float32)},
         {"activation": "tanh", "gate_activation": "sigmoid",
          "is_reverse": False},
         {"out": "Hidden", "lod": {"Input_in": lod},
          "wrt": ["Input", "Weight", "Bias"]}),
        ("dynamic_lstm",
         {"Input": xl, "Weight": rng.rand(H, 4 * H).astype(np.float32),
          "Bias": rng.rand(1, 4 * H).astype(np.float32)},
         {"use_peepholes": False, "is_reverse": False},
         {"out": "Hidden", "lod": {"Input_in": lod},
          "wrt": ["Input", "Weight", "Bias"], "seq_outs": ["Cell"]}),
        ("dynamic_lstmp",
         {"Input": xl, "Weight": rng.rand(1, 4 * H).astype(np.float32),
          "Bias": rng.rand(1, 4 * H).astype(np.float32),
          "ProjWeight": rng.rand(H, 1).astype(np.float32)},
         {"use_peepholes": False, "is_reverse": False,
          "proj_activation": "tanh"},
         {"out": "Projection", "lod": {"Input_in": lod},
          "wrt": ["Input", "Weight", "Bias", "ProjWeight"],
          "seq_outs": ["Cell"], "tol": 3e-2}),
        ("lstm",
         {"Input": rng.rand(2, 3, D).astype(np.float32),
          "W": rng.rand(w_flat_sz).astype(np.float32),
          "InitH": np.zeros((1, 2, H), np.float32),
          "InitC": np.zeros((1, 2, H), np.float32)},
         {"hidden_size": H, "num_layers": 1, "is_bidirec": False,
          "is_test": False, "dropout_prob": 0.0},
         {"wrt": ["Input", "W"],
          "seq_outs": ["LastH", "LastC"], "tol": 3e-2}),
        ("fusion_gru",
         {"X": rng.rand(T, D).astype(np.float32),
          "WeightX": rng.rand(D, 3 * H).astype(np.float32),
          "WeightH": rng.rand(H, 3 * H).astype(np.float32),
          "Bias": rng.rand(1, 3 * H).astype(np.float32)},
         {"activation": "tanh", "gate_activation": "sigmoid",
          "is_reverse": False},
         {"out": "Hidden", "lod": {"X_in": lod},
          "wrt": ["X", "WeightX", "WeightH", "Bias"],
          "seq_outs": ["XX"]}),
        ("fusion_lstm",
         {"X": rng.rand(T, D).astype(np.float32),
          "WeightX": rng.rand(D, 4 * H).astype(np.float32),
          "WeightH": rng.rand(H, 4 * H).astype(np.float32),
          "Bias": rng.rand(1, 4 * H).astype(np.float32)},
         {"use_peepholes": False, "is_reverse": False},
         {"out": "Hidden", "lod": {"X_in": lod},
          "wrt": ["X", "WeightX", "WeightH", "Bias"],
          "seq_outs": ["Cell", "XX"]}),
    ]


def _roi_det_cases():
    x6 = rng.rand(1, 1, 6, 6).astype(np.float32)
    rois = np.asarray([[0.5, 0.5, 4.5, 4.5], [1.0, 1.0, 5.0, 5.0]],
                      np.float32)
    return [
        ("roi_align",
         {"X": x6, "ROIs": rois},
         {"pooled_height": 2, "pooled_width": 2, "spatial_scale": 1.0,
          "sampling_ratio": 2},
         {"lod": {"ROIs_in": [[2]]}, "wrt": ["X"]}),
        ("psroi_pool",
         {"X": rng.rand(1, 4, 4, 4).astype(np.float32),
          "ROIs": rois[:1]},
         {"output_channels": 1, "group_size": 2, "spatial_scale": 1.0,
          "pooled_height": 2, "pooled_width": 2},
         {"lod": {"ROIs_in": [[1]]}, "wrt": ["X"]}),
        ("prroi_pool",
         {"X": x6, "ROIs": rois[:1],
          "BatchRoINums": np.asarray([1], np.int64)},
         {"pooled_height": 2, "pooled_width": 2, "spatial_scale": 1.0},
         {"lod": {"ROIs_in": [[1]]}, "wrt": ["X"]}),
        ("deformable_conv",
         {"Input": rng.rand(1, 1, 3, 3).astype(np.float32),
          "Offset": np.full((1, 8, 2, 2), 0.23, np.float32),
          "Mask": rng.uniform(0.4, 0.9, (1, 4, 2, 2)).astype(np.float32),
          "Filter": rng.rand(1, 1, 2, 2).astype(np.float32)},
         {"strides": [1, 1], "paddings": [0, 0], "dilations": [1, 1],
          "groups": 1, "deformable_groups": 1},
         {"out": "Output", "wrt": ["Input", "Filter", "Mask"],
          "tol": 3e-2}),
        ("deformable_conv_v1",
         {"Input": rng.rand(1, 1, 3, 3).astype(np.float32),
          "Offset": np.full((1, 8, 2, 2), 0.23, np.float32),
          "Filter": rng.rand(1, 1, 2, 2).astype(np.float32)},
         {"strides": [1, 1], "paddings": [0, 0], "dilations": [1, 1],
          "groups": 1, "deformable_groups": 1},
         {"out": "Output", "wrt": ["Input", "Filter"], "tol": 3e-2}),
        ("deformable_psroi_pooling",
         {"Input": rng.rand(1, 4, 4, 4).astype(np.float32),
          "ROIs": rois[:1],
          "Trans": np.zeros((1, 2, 2, 2), np.float32)},
         {"no_trans": True, "spatial_scale": 1.0, "output_dim": 1,
          "group_size": [2], "pooled_height": 2, "pooled_width": 2,
          "part_size": [2], "sample_per_part": 2, "trans_std": 0.1},
         {"out": "Output", "lod": {"ROIs_in": [[1]]},
          "wrt": ["Input"], "tol": 3e-2}),
        ("box_coder",
         {"PriorBox": np.asarray([[1., 1., 3., 3.], [2., 2., 5., 6.]],
                                 np.float32),
          "TargetBox": np.asarray([[1.5, 1.5, 3.5, 4.0],
                                   [2.5, 2.0, 4.5, 5.5]], np.float32)},
         {"code_type": "encode_center_size", "box_normalized": False},
         {"out": "OutputBox", "wrt": ["TargetBox"]}),
        ("box_clip",
         {"Input": np.asarray([[1., 1., 3., 3.], [2., 2., 5., 6.]],
                              np.float32),
          "ImInfo": np.asarray([[10., 10., 1.]], np.float32)},
         {}, {"out": "Output", "lod": {"Input_in": [[2]]},
              "wrt": ["Input"]}),
        ("yolov3_loss",
         {"X": rng.uniform(-0.5, 0.5, (1, 14, 2, 2)).astype(np.float32),
          "GTBox": np.asarray([[[0.5, 0.5, 0.3, 0.4]]], np.float32),
          "GTLabel": np.asarray([[1]], np.int32)},
         {"anchors": [10, 13, 16, 30], "anchor_mask": [0, 1],
          "class_num": 2, "ignore_thresh": 0.7, "downsample_ratio": 32,
          "use_label_smooth": False},
         {"out": "Loss", "wrt": ["X"], "tol": 5e-2,
          "seq_outs": ["ObjectnessMask", "GTMatchMask"]}),
        ("similarity_focus",
         {"X": (rng.permutation(8).reshape(1, 2, 2, 2) * 0.1 + 0.05
                ).astype(np.float32)},
         {"axis": 1, "indexes": [0]}, {"wrt": ["X"], "tol": 3e-2}),
    ]


def _sampled_cases():
    V, D_ = 6, 3
    return [
        ("hierarchical_sigmoid",
         {"X": rng.rand(2, D_).astype(np.float32),
          "W": rng.rand(V - 1, D_).astype(np.float32),
          "Label": np.asarray([[1], [4]], np.int64),
          "Bias": rng.rand(V - 1, 1).astype(np.float32)},
         {"num_classes": V},
         {"wrt": ["X", "W", "Bias"], "seq_outs": ["PreOut"]}),
        ("sample_logits",
         {"Logits": rng.rand(2, 5).astype(np.float32),
          "Labels": np.asarray([[1], [3]], np.int64)},
         {"num_samples": 3, "seed": 2, "uniq": True,
          "remove_accidental_hits": False,
          "use_customized_samples": False},
         {"out": "SampledLogits", "wrt": ["Logits"],
          "seq_outs": ["Samples", "Probabilities", "SampledLabels"]}),
        ("dropout", {"X": POS},
         {"dropout_prob": 0.3, "is_test": False, "fix_seed": True,
          "seed": 5, "dropout_implementation": "upscale_in_train"},
         {"wrt": ["X"], "seq_outs": ["Mask"]}),
        ("shuffle_batch",
         {"X": rng.rand(4, 2).astype(np.float32),
          "Seed": np.asarray([3], np.int64)},
         {}, {"wrt": ["X"], "seq_outs": ["ShuffleIdx", "SeedOut"]}),
        ("fused_elemwise_activation",
         {"X": X, "Y": Y},
         {"functor_list": ["elementwise_add", "scale"], "scale": 2.0},
         {"wrt": ["X", "Y"], "seq_outs": ["IntermediateOut"]}),
        ("fused_embedding_eltwise_layernorm",
         {"Ids": [("feia", np.asarray([[1, 0]], np.int64)),
                  ("feib", np.asarray([[2, 1]], np.int64))],
          "Embs": [("fembA", rng.rand(4, 4).astype(np.float32)),
                   ("fembB", rng.rand(4, 4).astype(np.float32))],
          "Bias": rng.rand(4).astype(np.float32),
          "Scale": rng.rand(4).astype(np.float32) + 0.5},
         {"epsilon": 1e-5},
         {"wrt": ["Embs", "Bias", "Scale"], "tol": 3e-2}),
    ]


CASES_BATCH3 = _mark_slow_tail(_seq_cases() + _rnn_cases()
                               + _roi_det_cases() + _sampled_cases())


@pytest.mark.parametrize("case", CASES_BATCH3, ids=_ids)
def test_grad_tail_batch3(case):
    name, inputs, attrs, kw = case
    fd_check(name, inputs, attrs, **kw)



STRAGGLERS = [
    ("index_sample",
     {"X": X, "Index": np.asarray([[2, 0], [1, 1]], np.int32)}, {},
     {"wrt": ["X"]}),
    ("log_loss",
     {"Predicted": rng.uniform(0.25, 0.75, (3, 1)).astype(np.float32),
      "Labels": np.asarray([[0.], [1.], [1.]], np.float32)},
     {"epsilon": 1e-4}, {"out": "Loss", "wrt": ["Predicted"]}),
    ("maximum",
     {"X": X, "Y": X + np.where(Y > 0, 0.3, -0.3).astype(np.float32)},
     {}, {"wrt": ["X", "Y"]}),
    ("multiplex",
     {"X": [("mpa", X), ("mpb", Y)],
      "Ids": np.asarray([[1], [0]], np.int32)}, {}, {"wrt": ["X"]}),
    ("pad_constant_batch_size_like",
     {"X": np.zeros((3, 3), np.float32), "Y": X}, {}, {"wrt": ["Y"]}),
    ("reshape", {"X": X}, {"shape": [3, 2]}, {"wrt": ["X"]}),
    ("rank_attention",
     {"X": rng.rand(2, 2).astype(np.float32),
      "RankOffset": np.asarray([[1, 1, 0, 2, 1, 0, 0],
                                [2, 1, 0, 0, 0, 3, 1]], np.int32),
      "RankParam": rng.rand(2 * 3 * 3, 2).astype(np.float32).reshape(
          18, 2)},
     {"MaxRank": 3},
     {"wrt": ["X", "RankParam"],
      "seq_outs": ["InputHelp", "InsRank"]}),
    ("var_conv_2d",
     {"X": rng.rand(16, 1).astype(np.float32),
      "ROW": np.zeros((4, 1), np.float32),
      "COLUMN": np.zeros((4, 1), np.float32),
      "W": rng.rand(1, 9).astype(np.float32)},
     {"InputChannel": 1, "OutputChannel": 1, "StrideH": 1, "StrideW": 1,
      "KernelH": 3, "KernelW": 3},
     {"lod": {"X_in": [[16]], "ROW_in": [[4]], "COLUMN_in": [[4]]},
      "wrt": ["X", "W"], "seq_outs": ["Col"]}),
    # ops/decoder_ops.py (forward + gradients against the float32
    # reference: tests/test_qwen3_next.py)
    ("rms_norm",
     {"X": rng.uniform(-1, 1, (2, 3, 8)).astype(np.float32),
      "Scale": rng.uniform(-0.5, 0.5, (4,)).astype(np.float32),
      "Gate": rng.uniform(-1, 1, (2, 3, 8)).astype(np.float32)},
     {"epsilon": 1e-6, "zero_centered": True},
     {"wrt": ["X", "Scale", "Gate"]}),
    ("rotary_embedding",
     {"X": rng.uniform(-1, 1, (1, 4, 8)).astype(np.float32)},
     {"num_heads": 2, "rotary_dim": 2, "theta": 100.0}, {"wrt": ["X"]}),
    ("causal_conv1d",
     {"X": rng.uniform(-1, 1, (1, 5, 3)).astype(np.float32),
      "Filter": rng.uniform(-0.5, 0.5, (3, 4)).astype(np.float32)},
     {}, {"wrt": ["X", "Filter"]}),
    ("gated_delta_rule",
     {"Q": rng.uniform(-1, 1, (1, 6, 4)).astype(np.float32),
      "K": rng.uniform(-1, 1, (1, 6, 4)).astype(np.float32),
      "V": rng.uniform(-1, 1, (1, 6, 8)).astype(np.float32),
      "A": rng.uniform(-1, 1, (1, 6, 2)).astype(np.float32),
      "B": rng.uniform(-1, 1, (1, 6, 2)).astype(np.float32),
      "ALog": np.asarray([0.0, 1.0], np.float32),
      "DtBias": np.ones((2,), np.float32)},
     {"num_key_heads": 1, "num_value_heads": 2, "chunk_size": 4},
     {"wrt": ["Q", "K", "V", "A", "B", "ALog", "DtBias"]}),
    ("selective_scan",
     {"X": rng.uniform(-1, 1, (1, 6, 3)).astype(np.float32),
      "Dt": rng.uniform(-1, 1, (1, 6, 3)).astype(np.float32),
      "B": rng.uniform(-1, 1, (1, 6, 2)).astype(np.float32),
      "C": rng.uniform(-1, 1, (1, 6, 2)).astype(np.float32),
      "ALog": np.log(np.tile(np.arange(1, 3, dtype=np.float32), (3, 1))),
      "D": np.ones((3,), np.float32),
      "DtBias": np.asarray([-1.0, 0.0, 0.5], np.float32)},
     {"chunk_size": 4},
     {"wrt": ["X", "Dt", "B", "C", "ALog", "D", "DtBias"]}),
    ("differential_combine",
     {"X": rng.uniform(-1, 1, (1, 3, 2 * 2 * 2 * 4)).astype(np.float32),
      "LambdaQ1": rng.uniform(-0.5, 0.5, (4,)).astype(np.float32),
      "LambdaK1": rng.uniform(-0.5, 0.5, (4,)).astype(np.float32),
      "LambdaQ2": rng.uniform(-0.5, 0.5, (4,)).astype(np.float32),
      "LambdaK2": rng.uniform(-0.5, 0.5, (4,)).astype(np.float32)},
     {"num_groups": 2, "lambda_init": 0.36},
     {"wrt": ["X", "LambdaQ1", "LambdaK1", "LambdaQ2", "LambdaK2"]}),
    # logits 0.6 apart: a finite difference moves no token's choice
    ("moe_router",
     {"X": np.ones((1, 3, 2), np.float32)
      + rng.uniform(-0.05, 0.05, (1, 3, 2)).astype(np.float32),
      "W": np.asarray([[0.0, 0.6, 1.2, 1.8, 2.4],
                       [0.1, -0.1, 0.05, 0.0, -0.05]], np.float32)},
     {"top_k": 2},
     {"out": "AuxLoss", "wrt": ["X", "W"],
      "seq_outs": ["TopkIdx", "TopkWeight"]}),
    ("moe_expert_ffn",
     {"X": rng.uniform(-1, 1, (1, 4, 6)).astype(np.float32),
      "TopkIdx": np.asarray([[[1, 4], [3, 2], [0, 1], [3, 1]]], np.int32),
      "TopkWeight": rng.uniform(0.2, 0.8, (1, 4, 2)).astype(np.float32),
      "WGateUp": rng.uniform(-0.5, 0.5, (3, 6, 8)).astype(np.float32),
      "WDown": rng.uniform(-0.5, 0.5, (3, 4, 6)).astype(np.float32)},
     {"expert_start": 1},
     {"wrt": ["X", "TopkWeight", "WGateUp", "WDown"]}),
]


@pytest.mark.parametrize("case", _mark_slow_tail(STRAGGLERS), ids=_ids)
def test_grad_tail_stragglers(case):
    name, inputs, attrs, kw = case
    fd_check(name, inputs, attrs, **kw)


def test_grad_tail_unbind_multi_out():
    fd_check_multi("unbind", {"X": X}, {"axis": 0}, "Out", 2, wrt=["X"])


# --------------------------------------------------------------------------
# exemptions + the enforcing meta-test
# --------------------------------------------------------------------------
# Every differentiable op NOT carrying a check_grad case must be here,
# with the reason FD is inapplicable and where its gradient behavior IS
# exercised.
GRAD_EXEMPT = {
    # collectives: need a device mesh; gradient flow is proven by the
    # DP/TP loss-parity oracles
    "allreduce": "collective; tests/test_parallel.py DP loss parity",
    "broadcast": "collective; tests/test_parallel.py",
    "c_allgather": "collective; tests/test_parallel.py shard_map tests",
    "c_allreduce_max": "collective; tests/test_parallel.py",
    "c_allreduce_min": "collective; tests/test_parallel.py",
    "c_allreduce_prod": "collective; tests/test_parallel.py",
    "c_allreduce_sum": "collective; tests/test_parallel.py DP grads",
    "c_broadcast": "collective; tests/test_parallel.py",
    "c_reducescatter": "collective; tests/test_parallel.py",
    "c_sync_calc_stream": "stream sync no-op on XLA; identity",
    "c_sync_comm_stream": "stream sync no-op on XLA; identity",
    "sync_batch_norm": "needs mesh; tests/test_parallel.py "
                       "test_sync_batch_norm parity",
    # straight-through estimators: the registered grad is BY DESIGN not
    # the derivative of the piecewise-constant forward — FD would
    # (correctly) disagree. STE contract tested in test_quant_amp.py.
    "fake_channel_wise_dequantize_max_abs": "STE; tests/test_quant_amp.py",
    "fake_channel_wise_quantize_abs_max": "STE; tests/test_quant_amp.py",
    "fake_dequantize_max_abs": "STE; tests/test_quant_amp.py",
    "fake_quantize_abs_max": "STE; tests/test_quant_amp.py",
    "fake_quantize_dequantize_abs_max": "STE; tests/test_quant_amp.py",
    "fake_quantize_dequantize_moving_average_abs_max":
        "STE; tests/test_quant_amp.py",
    "fake_quantize_moving_average_abs_max": "STE; tests/test_quant_amp.py",
    "fake_quantize_range_abs_max": "STE; tests/test_quant_amp.py",
    # misc
    "coalesce_tensor": "buffer-packing (identity on values); "
                       "tests/test_metrics_misc_ops.py::test_coalesce_tensor",
    "cudnn_lstm": "kernel shared with `lstm` (FD-checked here); alias "
                  "run tests/test_ps_quant_misc_ops.py::"
                  "test_cudnn_lstm_alias_runs",
    "distributed_lookup_table": "grad is an RPC push side effect; "
                                "multiprocess clusters tests/test_dist_ps.py",
    "fused_attention_qkv": "custom-vjp grads: tests/test_models.py::"
                           "test_fused_attention_op_grad",
    "reduce_all": "boolean reduction — bool output has no gradient",
    "reduce_any": "boolean reduction — bool output has no gradient",
    "run_program_dy": "dygraph bridge; autograd through it "
                      "tests/test_dygraph_to_static.py",
    "tdm_sampler": "integer tree-sampling outputs; no gradient contract",
    "elementwise_floordiv": "integer lattice op — derivative zero a.e.; "
                            "forward battery only",
    "elementwise_mod": "piecewise-constant jumps make FD invalid at "
                       "boundaries; forward battery only",
    "lstmp": "alias registration of dynamic_lstmp (FD-checked here)",
    "nce": "negatives are drawn from the per-step executor rng, so FD "
           "across separate runs is ill-defined; grads proven by "
           "tests/test_loss_extra_ops.py::"
           "test_nce_and_hsigmoid_and_sampled_softmax_train",
    "sampled_softmax_with_cross_entropy":
        "per-step sampled negatives (executor rng); grads proven by "
        "tests/test_loss_extra_ops.py::"
        "test_nce_and_hsigmoid_and_sampled_softmax_train",
}


def _grad_checked_names():
    import ast as _ast
    import os
    here = os.path.dirname(os.path.abspath(__file__))
    def case_name(c):
        # slow-marked heavyweights are wrapped in pytest.param — the
        # case tuple is .values[0]; they still COUNT as grad-checked
        # (the full tier runs them)
        return (c.values[0][0] if hasattr(c, "values") else c[0])
    names = set(case_name(c) for c in CASES_BATCH1 + CASES_BATCH2
                + CASES_BATCH3 + STRAGGLERS)
    names.add("unbind")
    import test_op_battery
    names |= set(c[0] for c in test_op_battery.GRAD_CASES)
    # classes in test_op_grad_checks.py that set op_type and call
    # check_grad
    tree = _ast.parse(open(os.path.join(
        here, "test_op_grad_checks.py")).read())
    for cls in tree.body:
        if not isinstance(cls, _ast.ClassDef):
            continue
        src = _ast.unparse(cls)
        if "check_grad" not in src:
            continue
        for sub in _ast.walk(cls):
            if isinstance(sub, _ast.Assign) \
                    and any(isinstance(t, _ast.Attribute)
                            and t.attr == "op_type"
                            for t in sub.targets) \
                    and isinstance(sub.value, _ast.Constant):
                names.add(sub.value.value)
    return names


def test_every_differentiable_op_has_grad_check_or_exemption():
    """VERDICT r2 #4: the check_grad contract covers the whole
    differentiable registry (reference: per-op check_grad discipline in
    unittests/op_test.py)."""
    from paddle_tpu.ops.registry import OPS
    import paddle_tpu.ops  # noqa: F401  (populate the registry)
    checked = _grad_checked_names()
    missing, stale_exempt = [], []
    for name in sorted(OPS.all_op_types()):
        info = OPS.get(name)
        if info.no_grad or info.stateful:
            continue
        if name in GRAD_EXEMPT:
            if name in checked:
                stale_exempt.append(name)
            continue
        if name not in checked:
            missing.append(name)
    assert not missing, (
        f"{len(missing)} differentiable ops have neither a finite-"
        f"difference check_grad case nor a justified GRAD_EXEMPT entry: "
        f"{missing}")
    assert not stale_exempt, (
        f"exempted ops now have FD cases — drop the stale exemptions: "
        f"{stale_exempt}")
