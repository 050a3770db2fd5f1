"""Qwen3-Next-80B-A3B next-token pretraining, as
`paddle_tpu/models/qwen3_next.py` builds it: what the harness needs of
the configuration `qwen3_next_80b_a3b.json`.

    build(config, traffic)             -> (main, startup, fetches)
    make_batches(config, traffic, seed, k) -> k feed dicts
    flops_per_sample(config, traffic)  -> required forward + backward FLOP
    tiny(config, traffic)              -> the CPU rehearsal's toy sizes

and what the per-layer roofline shares divide by, a step of the cell:

    gdn_required(config, traffic)      -> {"flop": .., "bytes": ..}
    moe_required(config, traffic)      -> {"flop": .., "bytes": ..}

Every count is of work the layer equations REQUIRE
(`qwen3_next_80b_a3b_reference.py`), whatever implements it: nothing
recomputed, no padded row, no cast.
"""
import numpy as np

CHUNK = 64  # positions a chunk of the delta rule (the release's)


def model_cfg(config):
    """The program's names for the configuration's sizes."""
    return dict(
        vocab_size=config["vocab_size"], hidden=config["hidden_size"],
        layers=config["num_hidden_layers"],
        full_attention_interval=config["full_attention_interval"],
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        partial_rotary_factor=config["partial_rotary_factor"],
        rope_theta=float(config["rope_theta"]),
        linear_key_heads=config["linear_num_key_heads"],
        linear_value_heads=config["linear_num_value_heads"],
        linear_key_dim=config["linear_key_head_dim"],
        linear_value_dim=config["linear_value_head_dim"],
        conv_kernel=config["linear_conv_kernel_dim"],
        num_experts=config["router_width"],
        experts_per_tok=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        shared_width=config["shared_expert_intermediate_size"],
        eps=config["rms_norm_eps"], experts_held=config["num_experts"],
        expert_start=config["expert_start"], aux_coef=0.001, init_std=0.02)


def build(config, traffic):
    from paddle_tpu.models import qwen3_next
    main, startup, _, fetches = qwen3_next.build_qwen3_next_pretrain_program(
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


def _layer_kinds(config):
    full = sum((i + 1) % config["full_attention_interval"] == 0
               for i in range(config["num_hidden_layers"]))
    return config["num_hidden_layers"] - full, full


def matmul_weights_per_token(config):
    """Weights a token is multiplied by, by part: every projection, the
    router, the shared expert and its gate, the EXPECTED routed experts
    (top-k x held / router width of them), the head. Norms, the
    depthwise filter, the gates' vectors and the embedding lookup are
    not matmuls and are not counted."""
    h = config["hidden_size"]
    key = config["linear_num_key_heads"] * config["linear_key_head_dim"]
    value = config["linear_num_value_heads"] * config["linear_value_head_dim"]
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    expert = 3 * h * config["moe_intermediate_size"]
    gdn_layers, full_layers = _layer_kinds(config)
    return {
        "gdn": gdn_layers * (h * (2 * key + 2 * value)          # W_qkvz
                             + h * 2 * config["linear_num_value_heads"]
                             + value * h),                      # W_ba, W_o
        "attention": full_layers * (h * 2 * q + 2 * h * kv + q * h),
        "router_and_shared": config["num_hidden_layers"] * (
            h * config["router_width"]
            + 3 * h * config["shared_expert_intermediate_size"] + h),
        "routed_expected": config["num_hidden_layers"] * expert
        * config["num_experts_per_tok"] * config["num_experts"]
        / config["router_width"],
        "head": h * config["vocab_size"],
    }


def delta_rule_flop_per_token_head(config):
    """Forward FLOP a position of one value head in the chunked (WY)
    delta rule at CHUNK positions a chunk, a multiply-add two; by
    product: K K^T and Q K^T within the chunk (2 x 2 C dk), the
    triangular solve of a C x C system against the identity (2/3 C^2),
    its products with beta K e^G and beta V (2 C dk + 2 C dv), the
    in-chunk output (Q K^T) V' (2 C dv), and the three products with
    the dk x dv state: W S, Q S, K^T V' (3 x 2 dk dv)."""
    c, dk = CHUNK, config["linear_key_head_dim"]
    dv = config["linear_value_head_dim"]
    return (2 * 2 * c * dk + 2 * c * c / 3 + 2 * c * dk + 2 * c * dv
            + 2 * c * dv + 3 * 2 * dk * dv)


def flops_per_sample(config, traffic):
    """Forward + backward FLOP one sequence requires (a multiply-add is
    two; backward twice the forward): 6 x matmul weights x tokens, the
    causal half of attention's scores and weighted sum, the delta
    rule's chunk products. Norms, gates, softmax, rotary, the depthwise
    convolution, the embedding lookup and Adam are not counted, and
    nothing recomputed is."""
    s = traffic["seq_len"]
    gdn_layers, full_layers = _layer_kinds(config)
    weights = sum(matmul_weights_per_token(config).values())
    attention = full_layers * 6 * s * s * (
        config["num_attention_heads"] * config["head_dim"])
    delta = gdn_layers * 3 * s * config["linear_num_value_heads"] \
        * delta_rule_flop_per_token_head(config)
    return float(6 * weights * s + attention + delta)


def gdn_required(config, traffic):
    """A step's `gated_delta_rule` ops, forward + backward, all layers.
    flop: the chunk products, backward twice the forward. bytes, f32:
    the forward reads q, k, v and the two gates' pre-activations and
    writes o; the backward reads them and o's gradient again and writes
    the five gradients."""
    tokens = traffic["batch"] * traffic["seq_len"]
    gdn_layers, _ = _layer_kinds(config)
    hv = config["linear_num_value_heads"]
    key = config["linear_num_key_heads"] * config["linear_key_head_dim"]
    value = hv * config["linear_value_head_dim"]
    read = 2 * key + value + 2 * hv
    return {"flop": gdn_layers * 3 * tokens * hv
            * delta_rule_flop_per_token_head(config),
            "bytes": gdn_layers * tokens * 4 * (3 * read + 2 * value)}


def moe_required(config, traffic):
    """A step's `moe_router` + `moe_expert_ffn` ops, forward + backward,
    all layers. flop: the router's logits and the EXPECTED routed rows
    (tokens x top-k x held / router width) through an expert's three
    projections, backward twice the forward. bytes: every held expert's
    f32 master weights read once forward and once backward and their
    f32 gradient written once; the router's weights likewise; x read
    and y written forward, x and dy read and dx written backward."""
    tokens = traffic["batch"] * traffic["seq_len"]
    h, layers = config["hidden_size"], config["num_hidden_layers"]
    expert = 3 * h * config["moe_intermediate_size"]
    rows = tokens * config["num_experts_per_tok"] * config["num_experts"] \
        / config["router_width"]
    router = h * config["router_width"]
    return {"flop": layers * 6 * (tokens * router + rows * expert),
            "bytes": layers * 4 * (3 * (config["num_experts"] * expert
                                        + router) + 5 * tokens * h)}


def tiny(config, traffic):
    config = dict(config, vocab_size=96, classes=96, hidden_size=32,
                  num_hidden_layers=4, num_attention_heads=4,
                  num_key_value_heads=2, head_dim=16,
                  linear_num_key_heads=2, linear_num_value_heads=4,
                  linear_key_head_dim=8, linear_value_head_dim=8,
                  router_width=16, num_experts=4, num_experts_per_tok=3,
                  moe_intermediate_size=12,
                  shared_expert_intermediate_size=12)
    return config, dict(traffic, batch=2, seq_len=80, pool=2)


def attn_required(config, traffic):
    """A step's flash kernels, forward + backward, the gated-attention
    layers, what `attn_roofline_pct` divides by. flop: a query head's
    scores and weighted sum (2 d + 2 d a key) over the causal half, the
    diagonal with it, backward twice the forward. bytes, in the
    operands' bf16: the forward reads Q, K, V (each key and value head
    once, not once a query head of its group) and writes O; the backward
    reads Q, K, V, O and O's gradient and writes the three gradients.
    The output gate is applied outside the kernels and is not counted."""
    s, tokens = traffic["seq_len"], traffic["batch"] * traffic["seq_len"]
    _, full_layers = _layer_kinds(config)
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    return {"flop": 3.0 * traffic["batch"] * full_layers
            * (s * (s + 1) // 2) * 4 * q,
            "bytes": full_layers * tokens * 2 * (
                (q + 2 * kv + q) + (q + 2 * kv + 2 * q) + (q + 2 * kv))}
