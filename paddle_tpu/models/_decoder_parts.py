"""What the decoder LMs' builders share (qwen3_next.py, phi4_flash.py,
laguna.py, smallthinker.py, joyai_flash.py, ling3_flash.py): a
parameter's attribute, a bias-free or biased projection, RMSNorm by a
named weight, the packed gated FFN, the training tail, the synthetic
batch and what a built program says of its attention and expert layers,
its routers' biases and where its head's logits are. Private to
``paddle_tpu.models``: each builder's parameter names, initialisers and
op order are its own and its reference's, so nothing here names a
parameter itself."""
from __future__ import annotations

import numpy as np

from .. import fluid
from ..fluid import layers
from ..fluid.initializer import Normal
from ..fluid.param_attr import ParamAttr


def attr(name, cfg, initializer=None):
    return ParamAttr(name=name,
                     initializer=initializer or Normal(0.0, cfg["init_std"]))


def linear(x, size, name, cfg, bias=None, initializer=None):
    """x W (+ b, a parameter named ``bias``, from 0)."""
    return layers.fc(x, size, num_flatten_dims=2,
                     bias_attr=ParamAttr(name=bias) if bias else False,
                     param_attr=attr(name, cfg, initializer))


def rms_norm(x, name, cfg, **kw):
    return layers.rms_norm(x, epsilon=cfg["eps"],
                           param_attr=ParamAttr(name=name), **kw)


def gated_ffn(x, width, prefix, cfg):
    """(SiLU(x W_gate) * x W_up) W_down, gate and up packed in one
    parameter ``w_gate_up`` [hidden, 2 * width]."""
    g, u = layers.split(linear(x, 2 * width, prefix + "w_gate_up", cfg), 2,
                        dim=-1)
    return linear(layers.elementwise_mul(layers.swish(g), u), cfg["hidden"],
                  prefix + "w_down", cfg)


def minimize(loss, lr, recompute, checkpoints):
    """Adam on ``loss``; ``recompute``: under a RecomputeOptimizer that
    keeps ``checkpoints`` and recomputes what lies between them while
    the backward runs."""
    opt = fluid.optimizer.Adam(lr)
    if recompute:
        opt = fluid.optimizer.RecomputeOptimizer(opt)
        opt._set_checkpoints(checkpoints)
    opt.minimize(loss)


def ops_by_site(program, op_type, value):
    """{an op's ``site`` (its gauges' label): value(op)} of the program's
    ops of one type, in layer order."""
    return {op.attr("site"): value(op)
            for op in program.global_block().ops if op.type == op_type}


def attention_sites(program):
    """{an attention op's ``site`` (its gauges' label): (query heads,
    window, 0 for none)}, in layer order."""
    return ops_by_site(
        program, "fused_attention_qkv",
        lambda op: (op.attr("num_heads"), op.attr("window")))


def expert_passes(program):
    """{an expert layer's ``site`` (its gauges' label): the name to fetch
    for the passes of its row bound it ran that step, [1] int32}, in
    layer order; 1 wherever the routing fitted twice the held share."""
    return ops_by_site(program, "moe_expert_ffn",
                       lambda op: op.output("Passes")[0])


def router_biases(program):
    """{a router's ``site``: the name of its bias persistable}, in layer
    order: what to fetch to read the biases after a step."""
    return {op.attr("site"): op.input("Bias")[0]
            for op in program.global_block().ops
            if op.type == "moe_router" and op.input("Bias")}


def head_logits(program):
    """The name of the head's logits [B, S, vocab] in ``program`` (the
    product with the parameter ``lm_head``): what to fetch to read them
    after a step."""
    return next(op.output("Out")[0] for op in program.global_block().ops
                if op.type == "mul" and op.input("Y") == ["lm_head"])


def synthetic_pretrain_batch(cfg, batch, seq_len, seed=0):
    """One feed dict: documents of seq_len + 1 ids uniform over the
    vocabulary from ``seed``, one a sequence; labels are the next ids."""
    doc = np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (batch, seq_len + 1), dtype=np.int64)
    return {"ids": doc[:, :-1].copy(), "labels": doc[:, 1:, None].copy()}
