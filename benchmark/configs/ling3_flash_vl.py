"""Ling-3.0-flash-VL's language model, next-token pretraining, as
`paddle_tpu/models/ling3_flash.py` builds it: what the harness needs of
the configuration `ling3_flash_vl.json`.

    build(config, traffic)             -> (main, startup, fetches)
    make_batches(config, traffic, seed, k) -> k feed dicts
    flops_per_sample(config, traffic)  -> required forward + backward FLOP
    tiny(config, traffic)              -> the CPU rehearsal's toy sizes

and what the per-layer roofline shares divide by, a step of the cell:

    kda_required(config, traffic)      -> {"flop": .., "bytes": ..}
    attn_required(config, traffic)     -> {"flop": .., "bytes": ..}
    moe_required(config, traffic)      -> {"flop": .., "bytes": ..}
    mla_required(config, traffic)      -> {"flop": .., "bytes": ..}

Every count is of work the layer equations REQUIRE
(`ling3_flash_vl_reference.py`), whatever implements it: the delta
rule's chunk products at the configuration's chunk, causal attention
over the kept keys and no other, the EXPECTED routed rows, nothing
recomputed, no padded row or block, no cast.
"""
import numpy as np


def layer_kinds(config):
    """The mlp kind of each layer that is here: the first
    `first_k_dense_replace` dense, the rest sparse."""
    dense = config["first_k_dense_replace"]
    return ["dense" if i < dense else "sparse"
            for i in range(config["num_hidden_layers"])]


def mixer_kinds(config):
    """The mixer of each layer that is here: latent attention where (i +
    1) % `layer_group_size` is 0, Kimi delta attention otherwise."""
    group = config["layer_group_size"]
    return ["mla" if (i + 1) % group == 0 else "kda"
            for i in range(config["num_hidden_layers"])]


def _what_is_built(config):
    """The configuration's keys whose other values this program does not
    build: each as the release has it, or the build stops here."""
    layers = config["num_hidden_layers"]
    want = {"q_lora_rank": None, "use_kda_lora": False, "no_kda_lora": True,
            "kda_safe_gate": True, "num_kv_heads_for_linear_attn": 0,
            "group_norm_size": 1, "linear_silu": True, "use_nGPT": False,
            "score_function": "sigmoid", "norm_topk_prob": True,
            "moe_router_enable_expert_bias": True, "use_mla_nope": False,
            "scale_router_input": False, "value_norm": False,
            "up_proj_norm": False,
            "gated_attention_proj_granularity_type": "head_wise",
            "rotary_dim": config["qk_rope_head_dim"],
            "v_head_dim": config["head_dim"]}
    off = {k: config[k] for k, v in want.items() if config[k] != v}
    clamps = config["expert_swiglu_limit_list"][:layers] \
        + config["share_expert_swiglu_limit_list"][:layers]
    if off or any(clamps):
        raise ValueError(f"ling3_flash_vl: not built: {off}, SwiGLU "
                         f"limits {clamps}")


def model_cfg(config):
    """The program's names for the configuration's sizes."""
    from paddle_tpu.models import ling3_flash
    _what_is_built(config)
    return dict(
        vocab_size=config["vocab_size"], hidden=config["hidden_size"],
        heads=config["num_attention_heads"],
        kv_latent=config["kv_lora_rank"],
        nope_dim=config["qk_nope_head_dim"],
        rope_dim=config["qk_rope_head_dim"], v_dim=config["v_head_dim"],
        rope_theta=float(config["rope_theta"]),
        layer_types=ling3_flash.layer_types(config["num_hidden_layers"],
                                            config["layer_group_size"]),
        mlp_types=layer_kinds(config),
        mlp_width=config["intermediate_size"],
        kda_head_dim=config["head_dim"],
        conv_kernel=config["short_conv_kernel_size"],
        kda_lower_bound=float(config["kda_lower_bound"]),
        num_experts=config["router_width"],
        experts_per_tok=config["num_experts_per_tok"],
        n_group=config["n_group"], topk_group=config["topk_group"],
        expert_width=config["moe_intermediate_size"],
        shared_width=config["moe_shared_expert_intermediate_size"],
        routed_scale=config["routed_scaling_factor"],
        eps=config["rms_norm_eps"], experts_held=config["num_experts"],
        expert_start=config["expert_start"], init_std=0.02,
        bias_update_rate=config["router_bias_update_rate"],
        kda_chunk=config["kda_chunk_size"])


def build(config, traffic):
    from paddle_tpu.models import ling3_flash
    main, startup, _, fetches = \
        ling3_flash.build_ling3_flash_pretrain_program(
            model_cfg(config), seq_len=traffic["seq_len"],
            lr=config["optimizer"]["lr"])
    return main, startup, fetches


def make_batches(config, traffic, seed, k):
    """`k` host batches from the seed: one whole document of seq_len + 1
    ids a sequence (no packing, no padding), uniform over the vocabulary
    slice; the labels are the ids shifted by one."""
    rng = np.random.default_rng(seed)
    b, s = traffic["batch"], traffic["seq_len"]
    out = []
    for _ in range(k):
        doc = rng.integers(0, config["vocab_size"], (b, s + 1),
                           dtype=np.int64)
        out.append({"ids": doc[:, :-1].copy(),
                    "labels": doc[:, 1:, None].copy()})
    return out


def _kda_width(config):
    return config["num_attention_heads"] * config["head_dim"]


def _latent_up(config):
    """Weights of the latent op's two up-projections, a layer: the query's
    from the hidden state (no query latent), the keys' and values' from
    the key-value latent."""
    h = config["num_attention_heads"]
    return (config["hidden_size"] * h * (config["qk_nope_head_dim"]
                                         + config["qk_rope_head_dim"])
            + config["kv_lora_rank"] * h * (config["qk_nope_head_dim"]
                                            + config["v_head_dim"]))


def matmul_weights_per_token(config):
    """Weights a token is multiplied by, by part: KDA's projections (q, k,
    v and the decay's W_qkva, W_b, the head gate, W_o), latent
    attention's (W_q, W_kv_a, W_kv_b, the head gate, W_o), the dense
    FFNs, the routers, the shared experts, the EXPECTED routed experts
    (top-k x held / router width of them), the head. Norms, the
    depthwise filter and the embedding lookup are not matmuls and are
    not counted."""
    hid, h = config["hidden_size"], config["num_attention_heads"]
    width = _kda_width(config)
    mixers, kinds = mixer_kinds(config), layer_kinds(config)
    expert = 3 * hid * config["moe_intermediate_size"]
    sparse = kinds.count("sparse")
    return {
        "kda": mixers.count("kda") * (hid * 4 * width + 2 * hid * h
                                      + width * hid),
        "mla": mixers.count("mla") * (
            hid * (config["kv_lora_rank"] + config["qk_rope_head_dim"])
            + _latent_up(config) + hid * h
            + h * config["v_head_dim"] * hid),
        "dense_ffn": kinds.count("dense") * 3 * hid
        * config["intermediate_size"],
        "router_and_shared": sparse * (
            hid * config["router_width"]
            + 3 * hid * config["moe_shared_expert_intermediate_size"]),
        "routed_expected": sparse * expert * config["num_experts_per_tok"]
        * config["num_experts"] / config["router_width"],
        "head": hid * config["vocab_size"],
    }


def kda_flop_per_token_head(config):
    """Forward FLOP a position of one head in the chunked (WY) delta rule
    at `kda_chunk_size` positions a chunk, a multiply-add two; by
    product: K K^T and Q K^T within the chunk, each decay-weighted a key
    channel (2 x 2 C dk: the blocks' factors make them no larger), the
    triangular solve of a C x C system against the identity (2/3 C^2),
    its products with beta K e^G and beta V (2 C dk + 2 C dv), the
    in-chunk output (Q K^T) V' (2 C dv), and the three products with
    the dk x dv state: W S, Q S, K^T V' (3 x 2 dk dv)."""
    c, d = config["kda_chunk_size"], config["head_dim"]
    return (2 * 2 * c * d + 2 * c * c / 3 + 2 * c * d + 2 * c * d
            + 2 * c * d + 3 * 2 * d * d)


def attention_flop_per_sample(config, traffic):
    """Forward FLOP of the attention maps of one sequence, all latent
    attention layers: a head's scores over the causal half of the keys
    (2 x 192 a key) and its weighted sum of values (2 x 128)."""
    s = traffic["seq_len"]
    width = config["qk_nope_head_dim"] + config["qk_rope_head_dim"] \
        + config["v_head_dim"]
    return mixer_kinds(config).count("mla") * s * (s + 1) // 2 \
        * config["num_attention_heads"] * 2 * width


def flops_per_sample(config, traffic):
    """Forward + backward FLOP one sequence requires (a multiply-add is
    two; backward twice the forward): 6 x matmul weights x tokens, the
    causal attention maps, the delta rule's chunk products. Norms,
    gates, rotary, softmax, the depthwise convolution, the embedding
    lookup, the bias update and Adam are not counted, and nothing
    recomputed is, nor a block a kernel visits and then masks."""
    s = traffic["seq_len"]
    weights = sum(matmul_weights_per_token(config).values())
    delta = mixer_kinds(config).count("kda") * 3 * s \
        * config["num_attention_heads"] * kda_flop_per_token_head(config)
    return float(6 * weights * s + 3 * attention_flop_per_sample(
        config, traffic) + delta)


def kda_required(config, traffic):
    """A step's `kda` ops, forward + backward, all KDA layers. flop: the
    chunk products, backward twice the forward. bytes, f32: the forward
    reads q, k, v, the decay's pre-activation (a channel each) and the
    write strength's (a head) and writes o; the backward reads them and
    o's gradient again and writes the five gradients."""
    tokens = traffic["batch"] * traffic["seq_len"]
    h, width = config["num_attention_heads"], _kda_width(config)
    layers = mixer_kinds(config).count("kda")
    read = 4 * width + h
    return {"flop": layers * 3 * tokens * h
            * kda_flop_per_token_head(config),
            "bytes": layers * tokens * 4 * (3 * read + 2 * width)}


def attn_required(config, traffic):
    """A step's flash kernels, forward + backward, all attention layers.
    flop: the maps over the kept keys, backward twice the forward.
    bytes, in the operands' bf16: the forward reads Q, K, V and writes
    O; the backward reads Q, K, V, O and O's gradient and writes the
    three gradients. Q and K are 192 wide a head, V and O 128."""
    tokens = traffic["batch"] * traffic["seq_len"]
    h = config["num_attention_heads"]
    qk = h * (config["qk_nope_head_dim"] + config["qk_rope_head_dim"])
    vo = h * config["v_head_dim"]
    widths = (2 * qk + 2 * vo) + (2 * qk + 3 * vo) + (2 * qk + vo)
    return {"flop": 3 * traffic["batch"] * attention_flop_per_sample(
                config, traffic),
            "bytes": mixer_kinds(config).count("mla") * tokens * 2 * widths}


def moe_required(config, traffic):
    """A step's `moe_router` + `moe_expert_ffn` ops, forward + backward,
    all sparse layers. flop: the router's logits and the EXPECTED routed
    rows (tokens x top-k x held / router width) through an expert's
    three projections, backward twice the forward. bytes: every held
    expert's f32 master weights read once forward and once backward and
    their f32 gradient written once; the router's weights likewise; x
    read and y written forward, x and dy read and dx written
    backward."""
    tokens = traffic["batch"] * traffic["seq_len"]
    hid, layers = config["hidden_size"], layer_kinds(config).count("sparse")
    expert = 3 * hid * config["moe_intermediate_size"]
    rows = tokens * config["num_experts_per_tok"] * config["num_experts"] \
        / config["router_width"]
    router = hid * config["router_width"]
    return {"flop": layers * 6 * (tokens * router + rows * expert),
            "bytes": layers * 4 * (3 * (config["num_experts"] * expert
                                        + router) + 5 * tokens * hid)}


def mla_required(config, traffic):
    """A step's `latent_qkv` ops, forward + backward, all latent attention
    layers. flop: the two up-projections (x W_q, c_KV W_UKV), backward
    twice the forward; rotary's element-wise work is not counted. bytes:
    the f32 up-projection weights read once forward and once backward
    and their gradient written once; forward reads x, c_KV, k_R and
    writes Q, K, V (f32); backward reads the three inputs and the three
    outputs' gradients and writes the inputs' gradients."""
    tokens = traffic["batch"] * traffic["seq_len"]
    h = config["num_attention_heads"]
    ins = config["hidden_size"] + config["kv_lora_rank"] \
        + config["qk_rope_head_dim"]
    outs = h * (2 * (config["qk_nope_head_dim"] + config["qk_rope_head_dim"])
                + config["v_head_dim"])
    layers = mixer_kinds(config).count("mla")
    return {"flop": layers * 6 * tokens * _latent_up(config),
            "bytes": layers * 4 * (3 * _latent_up(config)
                                   + tokens * (3 * ins + 2 * outs))}


def tiny(config, traffic):
    config = dict(config, vocab_size=96, classes=96, hidden_size=32,
                  num_attention_heads=4, head_dim=8, kv_lora_rank=16,
                  qk_nope_head_dim=8, qk_rope_head_dim=4, rotary_dim=4,
                  v_head_dim=8, intermediate_size=48, router_width=16,
                  num_experts=4, n_group=4, topk_group=2,
                  num_experts_per_tok=4, moe_intermediate_size=12,
                  moe_shared_expert_intermediate_size=12)
    return config, dict(traffic, batch=2, seq_len=80, pool=2)
