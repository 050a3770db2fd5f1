"""Layer functions of hybrid decoder LMs: linear attention, sparse
experts, state-space scans, differential attention, latent attention's
heads (ops/decoder_ops.py holds the kernels and the equations)."""
from __future__ import annotations

from ..initializer import Constant
from ..layer_helper import LayerHelper

__all__ = ["rms_norm", "rotary_embedding", "causal_conv1d",
           "gated_delta_rule", "kda", "selective_scan",
           "differential_combine",
           "latent_qkv", "moe_router", "moe_expert_ffn"]


def _like(helper, x, shape=None, dtype=None):
    out = helper.create_variable_for_type_inference(dtype or x.dtype)
    out.shape = tuple(x.shape if shape is None else shape)
    return out


def rms_norm(input, group_size=None, gate=None, zero_centered=False,
             epsilon=1e-6, param_attr=None, name=None):
    """RMSNorm over groups of ``group_size`` of the last dim (all of it by
    default; a head's dims for a per-head norm), the weight [group_size]
    shared by the groups. ``zero_centered``: y * (1 + w), w from 0; else
    y * w, w from 1. ``gate``: y * SiLU(gate), gate shaped like input."""
    helper = LayerHelper("rms_norm", **locals())
    scale = helper.create_parameter(
        attr=helper.param_attr, shape=[group_size or input.shape[-1]],
        dtype=input.dtype,
        default_initializer=Constant(0.0 if zero_centered else 1.0))
    inputs = {"X": [input], "Scale": [scale]}
    if gate is not None:
        inputs["Gate"] = [gate]
    out = _like(helper, input)
    helper.append_op(type="rms_norm", inputs=inputs, outputs={"Out": [out]},
                     attrs={"epsilon": epsilon,
                            "zero_centered": zero_centered})
    return out


def rotary_embedding(x, num_heads, rotary_dim, theta, yarn=None,
                     cos_sin_scale=1.0, name=None):
    """Rotate-half rotary embedding on the first ``rotary_dim`` dims of
    each head of x [B, S, num_heads * D]; position = index in S.
    ``yarn``: {"factor", "original_max_position", "beta_fast",
    "beta_slow"} scales the dims' frequencies by YaRN
    (``decoder_ops.yarn_inv_freq``); ``cos_sin_scale`` multiplies cos
    and sin (YaRN's attention factor)."""
    helper = LayerHelper("rotary_embedding", **locals())
    out = _like(helper, x)
    attrs = {"num_heads": num_heads, "rotary_dim": rotary_dim,
             "theta": float(theta), "cos_sin_scale": float(cos_sin_scale)}
    if yarn:
        attrs.update(yarn_factor=float(yarn["factor"]),
                     original_max_position=int(
                         yarn["original_max_position"]),
                     beta_fast=float(yarn["beta_fast"]),
                     beta_slow=float(yarn["beta_slow"]))
    helper.append_op(type="rotary_embedding", inputs={"X": [x]},
                     outputs={"Out": [out]}, attrs=attrs)
    return out


def causal_conv1d(x, kernel_size, param_attr=None, name=None):
    """Depthwise causal convolution along S of x [B, S, C]; no bias."""
    helper = LayerHelper("causal_conv1d", **locals())
    w = helper.create_parameter(attr=helper.param_attr,
                                shape=[x.shape[-1], kernel_size],
                                dtype=x.dtype)
    out = _like(helper, x)
    helper.append_op(type="causal_conv1d", inputs={"X": [x], "Filter": [w]},
                     outputs={"Out": [out]})
    return out


def gated_delta_rule(q, k, v, a, b, num_key_heads, num_value_heads,
                     chunk_size=64, a_log_attr=None, dt_bias_attr=None,
                     name=None):
    """The gated delta rule over q, k [B, S, Hk*dk], v [B, S, Hv*dv] with
    the gates' pre-activations a, b [B, S, Hv]; creates the per-head
    parameters A_log and dt_bias. -> [B, S, Hv*dv]."""
    helper = LayerHelper("gated_delta_rule", **locals())
    a_log = helper.create_parameter(attr=a_log_attr, shape=[num_value_heads],
                                    dtype=v.dtype)
    dt_bias = helper.create_parameter(attr=dt_bias_attr,
                                      shape=[num_value_heads], dtype=v.dtype,
                                      default_initializer=Constant(1.0))
    out = _like(helper, v)
    helper.append_op(
        type="gated_delta_rule",
        inputs={"Q": [q], "K": [k], "V": [v], "A": [a], "B": [b],
                "ALog": [a_log], "DtBias": [dt_bias]},
        outputs={"Out": [out]},
        attrs={"num_key_heads": num_key_heads,
               "num_value_heads": num_value_heads, "chunk_size": chunk_size,
               "site": helper.name})
    return out


def kda(q, k, v, a, b, lower_bound, chunk_size=64, a_log_attr=None,
        dt_bias_attr=None, name=None):
    """Kimi delta attention over q, k, v [B, S, H*d] with the decay's
    pre-activation a [B, S, H*dk] (a key channel) and the write
    strength's b [B, S, H], the log decay bounded below by
    ``lower_bound``; creates A_log [H] and dt_bias [H*dk] (from 0).
    -> [B, S, H*dv]."""
    helper = LayerHelper("kda", **locals())
    heads = b.shape[-1]
    a_log = helper.create_parameter(attr=a_log_attr, shape=[heads],
                                    dtype=v.dtype)
    dt_bias = helper.create_parameter(attr=dt_bias_attr,
                                      shape=[a.shape[-1]], dtype=v.dtype,
                                      default_initializer=Constant(0.0))
    out = _like(helper, v)
    helper.append_op(
        type="kda",
        inputs={"Q": [q], "K": [k], "V": [v], "A": [a], "B": [b],
                "ALog": [a_log], "DtBias": [dt_bias]},
        outputs={"Out": [out]},
        attrs={"lower_bound": float(lower_bound), "chunk_size": chunk_size,
               "site": helper.name})
    return out


def selective_scan(x, dt, b, c, chunk_size=64, a_log_attr=None, d_attr=None,
                   dt_bias_attr=None, name=None):
    """Mamba's selective scan over x, dt [B, S, C] (dt the step's
    pre-activation) with the maps b, c [B, S, N]; creates A_log [C, N],
    D [C] (from 1) and dt_bias [C]. -> [B, S, C]."""
    helper = LayerHelper("selective_scan", **locals())
    channels, d_state = x.shape[-1], b.shape[-1]
    a_log = helper.create_parameter(attr=a_log_attr,
                                    shape=[channels, d_state], dtype=x.dtype)
    d = helper.create_parameter(attr=d_attr, shape=[channels], dtype=x.dtype,
                                default_initializer=Constant(1.0))
    dt_bias = helper.create_parameter(attr=dt_bias_attr, shape=[channels],
                                      dtype=x.dtype,
                                      default_initializer=Constant(0.0))
    out = _like(helper, x)
    helper.append_op(
        type="selective_scan",
        inputs={"X": [x], "Dt": [dt], "B": [b], "C": [c], "ALog": [a_log],
                "D": [d], "DtBias": [dt_bias]},
        outputs={"Out": [out]},
        attrs={"chunk_size": chunk_size, "site": helper.name})
    return out


def differential_combine(x, num_groups, head_dim, lambda_init,
                         lambda_attrs=None, name=None):
    """Differential attention's A_1 V - lambda A_2 V from the attention
    op's output x [B, S, G * 2 * J * Dv] (heads laid out [group, map,
    differential head]); creates the four lambda vectors [head_dim]
    (``lambda_attrs``: q1, k1, q2, k2). -> [B, S, G * J * Dv]."""
    helper = LayerHelper("differential_combine", **locals())
    vectors = [helper.create_parameter(attr=attr, shape=[head_dim],
                                       dtype=x.dtype)
               for attr in (lambda_attrs or [None] * 4)]
    out = _like(helper, x, tuple(x.shape[:-1]) + (x.shape[-1] // 2,))
    helper.append_op(
        type="differential_combine",
        inputs={"X": [x], "LambdaQ1": [vectors[0]], "LambdaK1": [vectors[1]],
                "LambdaQ2": [vectors[2]], "LambdaK2": [vectors[3]]},
        outputs={"Out": [out]},
        attrs={"num_groups": num_groups, "lambda_init": float(lambda_init)})
    return out


def latent_qkv(c_q, c_kv, k_rope, num_heads, nope_dim, rope_dim, v_dim,
               theta, q_attr=None, kv_attr=None, rope_interleave=True,
               name=None):
    """Latent attention's Q, K, V from the normed query latent c_q
    [B, S, Lq] (or the normed layer input, where the query has no
    latent), the normed key-value latent c_kv [B, S, Lkv] and the one
    rotary key k_rope [B, S, rope_dim] all heads share; creates the
    up-projections W_UQ [Lq, heads * (nope_dim + rope_dim)] and W_UKV
    [Lkv, heads * (nope_dim + v_dim)]. -> (q, k [B, S, heads * (nope_dim
    + rope_dim)], v [B, S, heads * v_dim]), the attention op's layout,
    rotary at ``theta`` on pairs of dims (2i, 2i + 1) where
    ``rope_interleave``, else on (i, i + rope_dim / 2)."""
    helper = LayerHelper("latent_qkv", **locals())
    w_uq = helper.create_parameter(
        attr=q_attr, shape=[c_q.shape[-1], num_heads * (nope_dim + rope_dim)],
        dtype=c_q.dtype)
    w_ukv = helper.create_parameter(
        attr=kv_attr, shape=[c_kv.shape[-1], num_heads * (nope_dim + v_dim)],
        dtype=c_q.dtype)
    lead = tuple(c_q.shape[:-1])
    q = _like(helper, c_q, lead + (num_heads * (nope_dim + rope_dim),))
    k = _like(helper, c_q, lead + (num_heads * (nope_dim + rope_dim),))
    v = _like(helper, c_q, lead + (num_heads * v_dim,))
    helper.append_op(
        type="latent_qkv",
        inputs={"CQ": [c_q], "CKV": [c_kv], "KRope": [k_rope],
                "WUQ": [w_uq], "WUKV": [w_ukv]},
        outputs={"Q": [q], "K": [k], "V": [v]},
        attrs={"num_heads": num_heads, "nope_dim": nope_dim,
               "rope_dim": rope_dim, "v_dim": v_dim, "theta": float(theta),
               "rope_interleave": bool(rope_interleave),
               "site": helper.name})
    return q, k, v


def moe_router(x, num_experts, top_k, scoring="softmax", scale=1.0,
               param_attr=None, bias_update_rate=None, bias_name=None,
               n_group=1, topk_group=1, name=None):
    """Scores over ``num_experts`` in float32 (``scoring``: "softmax", or
    "sigmoid" of each logit), top-k, the chosen weights renormalised to
    sum 1 and multiplied by ``scale``: (expert ids [.., k] int32, weights
    [.., k], the layer's load-balancing auxiliary loss [1]).

    ``bias_update_rate`` gamma (not None): a choice-only bias [E], a
    persistable from 0 named ``bias_name`` and no parameter: the top-k
    is taken of scores + bias, the weights stay the unbiased scores, and
    the op writes bias + gamma * sign(mean load - load_e) back into it
    once a step, outside the gradient (DeepSeek-V3 §2.1.2).

    ``n_group`` > 1: the experts are that many groups in order, and a
    token's k are chosen inside its ``topk_group`` groups whose two
    largest (biased) scores sum highest."""
    helper = LayerHelper("moe_router", **locals())
    w = helper.create_parameter(attr=helper.param_attr,
                                shape=[x.shape[-1], num_experts],
                                dtype=x.dtype)
    picked = tuple(x.shape[:-1]) + (top_k,)
    idx = _like(helper, x, picked, "int32")
    idx.stop_gradient = True
    weight = _like(helper, x, picked)
    aux = _like(helper, x, (1,))
    inputs = {"X": [x], "W": [w]}
    outputs = {"TopkIdx": [idx], "TopkWeight": [weight], "AuxLoss": [aux]}
    attrs = {"top_k": top_k, "scoring": scoring, "scale": float(scale),
             "site": helper.name}
    if n_group > 1:
        if num_experts % n_group or not 1 <= topk_group <= n_group:
            raise ValueError(f"moe_router: {num_experts} experts in "
                             f"{n_group} groups, {topk_group} kept")
        attrs.update(n_group=n_group, topk_group=topk_group)
    if bias_update_rate is not None:
        bias = helper.create_or_get_global_variable(
            bias_name or helper.name + ".bias", shape=(num_experts,),
            dtype=x.dtype)
        bias.stop_gradient = True
        helper.set_variable_initializer(bias, Constant(0.0))
        inputs["Bias"] = outputs["BiasOut"] = [bias]
        attrs["bias_update_rate"] = float(bias_update_rate)
    helper.append_op(type="moe_router", inputs=inputs, outputs=outputs,
                     attrs=attrs)
    return idx, weight, aux


def moe_expert_ffn(x, topk_idx, topk_weight, experts_held, expert_width,
                   expert_start=0, num_experts=0, gate_up_attr=None,
                   down_attr=None, activation="silu", name=None):
    """The part of a routed gated FFN that experts ``expert_start ..
    expert_start + experts_held - 1`` give, with no assignment dropped:
    each expert (act(x W_gate) * x W_up) W_down, act the ``activation``
    ("silu", or "relu" for ReGLU experts); one parameter a projection,
    [held, D, 2 * width] (gate then up) and [held, width, D].
    ``topk_idx`` and ``topk_weight`` may come from a router that read
    another tensor than ``x`` (the layer's input, say).

    ``num_experts`` is the width of the router that chose ``topk_idx``.
    Given it, the grouped products run over ``decoder_ops.row_bound``
    rows a pass: twice the share of the assignments the held experts
    expect (tokens * k * held / num_experts), far above what a balanced
    router sends, instead of the tokens * min(k, held) a no-drop layer
    could be sent. A step routed more than the bound is exact all the
    same: it costs further passes, as many as its rows need. (Where
    twice the share is half of the most or more, the bound is the most:
    one pass, whatever is routed.) The op's int32 output ``Passes`` [1]
    (``<layer's name>.passes``, fetchable) says how many ran. 0 =
    unknown: one pass over the most."""
    helper = LayerHelper("moe_expert_ffn", **locals())
    d = x.shape[-1]
    w_gate_up = helper.create_parameter(
        attr=gate_up_attr, shape=[experts_held, d, 2 * expert_width],
        dtype=x.dtype)
    w_down = helper.create_parameter(
        attr=down_attr, shape=[experts_held, expert_width, d], dtype=x.dtype)
    out = _like(helper, x)
    passes = helper.create_variable(name=helper.name + ".passes", shape=(1,),
                                    dtype="int32", stop_gradient=True)
    helper.append_op(
        type="moe_expert_ffn",
        inputs={"X": [x], "TopkIdx": [topk_idx], "TopkWeight": [topk_weight],
                "WGateUp": [w_gate_up], "WDown": [w_down]},
        outputs={"Out": [out], "Passes": [passes]},
        attrs={"expert_start": expert_start, "num_experts": num_experts,
               "activation": activation, "site": helper.name})
    return out
