"""Laguna-XS.2 next-token pretraining, as `paddle_tpu/models/laguna.py`
builds it: what the harness needs of the configuration
`laguna_xs2.json`.

    build(config, traffic)             -> (main, startup, fetches)
    make_batches(config, traffic, seed, k) -> k feed dicts
    flops_per_sample(config, traffic)  -> required forward + backward FLOP
    tiny(config, traffic)              -> the CPU rehearsal's toy sizes

and what the per-layer roofline shares divide by, a step of the cell:

    attn_required(config, traffic)     -> {"flop": .., "bytes": ..}
    moe_required(config, traffic)      -> {"flop": .., "bytes": ..}

Every count is of work the layer equations REQUIRE
(`laguna_xs2_reference.py`), whatever implements it: attention over the
key positions its mask keeps and no other, the EXPECTED routed rows,
nothing recomputed, no padded row or block, no cast, no repeated K / V.
"""
import numpy as np

KINDS = {"full_attention": "full", "sliding_attention": "sliding"}


def layer_lists(config):
    """(kinds, query heads, mlp kinds) of the layers that are here: the
    first `num_hidden_layers` entries of the published per-layer lists,
    which the file keeps whole."""
    n = config["num_hidden_layers"]
    return ([KINDS[k] for k in config["layer_types"][:n]],
            list(config["num_attention_heads_per_layer"][:n]),
            list(config["mlp_layer_types"][:n]))


def _rope(config, kind):
    p = config["rope_parameters"][kind]
    rope = dict(theta=float(p["rope_theta"]),
                rotary_dim=int(config["head_dim"]
                               * p["partial_rotary_factor"]))
    if p["rope_type"] == "yarn":
        rope["yarn"] = dict(
            factor=float(p["factor"]),
            original_max_position=p["original_max_position_embeddings"],
            beta_fast=float(p["beta_fast"]), beta_slow=float(p["beta_slow"]))
        rope["cos_sin_scale"] = p["attention_factor"]
    return rope


def model_cfg(config):
    """The program's names for the configuration's sizes."""
    kinds, heads, mlps = layer_lists(config)
    return dict(
        vocab_size=config["vocab_size"], hidden=config["hidden_size"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        layer_types=kinds, heads_per_layer=heads, mlp_types=mlps,
        window=config["sliding_window"],
        mlp_width=config["intermediate_size"],
        num_experts=config["router_width"],
        experts_per_tok=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        shared_width=config["shared_expert_intermediate_size"],
        routed_scale=config["moe_routed_scaling_factor"],
        eps=config["rms_norm_eps"],
        rope={"full": _rope(config, "full_attention"),
              "sliding": _rope(config, "sliding_attention")},
        experts_held=config["num_experts"],
        expert_start=config["expert_start"], init_std=0.02)


def build(config, traffic):
    from paddle_tpu.models import laguna
    main, startup, _, fetches = laguna.build_laguna_pretrain_program(
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


def _sparse_layers(config):
    return sum(m == "sparse" for m in layer_lists(config)[2])


def matmul_weights_per_token(config):
    """Weights a token is multiplied by, by part: every projection and
    the gate's, the dense FFN, the router, the shared expert, the
    EXPECTED routed experts (top-k x held / router width of them), the
    head. Norms and the embedding lookup are not matmuls and are not
    counted."""
    h, d = config["hidden_size"], config["head_dim"]
    kv = config["num_key_value_heads"] * d
    expert = 3 * h * config["moe_intermediate_size"]
    _, heads, mlps = layer_lists(config)
    sparse = _sparse_layers(config)
    return {
        "attention": sum(2 * h * n * d + 2 * h * kv + h * n for n in heads),
        "dense_ffn": (len(mlps) - sparse) * 3 * h
        * config["intermediate_size"],
        "router_and_shared": sparse * (
            h * config["router_width"]
            + 3 * h * config["shared_expert_intermediate_size"]),
        "routed_expected": sparse * expert * config["num_experts_per_tok"]
        * config["num_experts"] / config["router_width"],
        "head": h * config["vocab_size"],
    }


def kept_keys(seq_len, window):
    """Key positions all the queries of one causal sequence see: the
    causal half, or under a window min(t + 1, w) for query t."""
    if not window or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def attention_flop_per_sample(config, traffic):
    """Forward FLOP of the attention maps of one sequence, all layers: a
    query head's scores over the keys its mask keeps (2 d a key) and its
    weighted sum of values (2 d), at the layer's own head count."""
    s, d = traffic["seq_len"], config["head_dim"]
    kinds, heads, _ = layer_lists(config)
    return sum(
        kept_keys(s, config["sliding_window"] if kind == "sliding" else 0)
        * n * 4 * d for kind, n in zip(kinds, heads))


def flops_per_sample(config, traffic):
    """Forward + backward FLOP one sequence requires (a multiply-add is
    two; backward twice the forward): 6 x matmul weights x tokens, and
    the attention maps over the keys each mask keeps (512 a query under
    the window, the causal half in the full layers). Norms, gates,
    softmax, rotary, the embedding lookup and Adam are not counted, and
    nothing recomputed is, nor a block a kernel visits and then masks."""
    weights = sum(matmul_weights_per_token(config).values())
    return float(6 * weights * traffic["seq_len"]
                 + 3 * attention_flop_per_sample(config, traffic))


def attn_required(config, traffic):
    """A step's flash kernels, forward + backward, all layers. flop: the
    maps over the kept keys, backward twice the forward. bytes, in the
    operands' bf16: the forward reads Q, K, V (each key and value head
    once, not once a query head of its group) and writes O; the backward
    reads Q, K, V, O and O's gradient and writes the three gradients."""
    tokens = traffic["batch"] * traffic["seq_len"]
    d = config["head_dim"]
    kv = config["num_key_value_heads"] * d
    widths = sum((n * d + 2 * kv + n * d) + (n * d + 2 * kv + 2 * n * d)
                 + (n * d + 2 * kv) for n in layer_lists(config)[1])
    return {"flop": 3 * traffic["batch"] * attention_flop_per_sample(
                config, traffic),
            "bytes": tokens * 2 * widths}


def moe_required(config, traffic):
    """A step's `moe_router` + `moe_expert_ffn` ops, forward + backward,
    all sparse layers. flop: the router's logits and the EXPECTED routed
    rows (tokens x top-k x held / router width) through an expert's
    three projections, backward twice the forward. bytes: every held
    expert's f32 master weights read once forward and once backward and
    their f32 gradient written once; the router's weights likewise; x
    read and y written forward, x and dy read and dx written backward."""
    tokens = traffic["batch"] * traffic["seq_len"]
    h, layers = config["hidden_size"], _sparse_layers(config)
    expert = 3 * h * config["moe_intermediate_size"]
    rows = tokens * config["num_experts_per_tok"] * config["num_experts"] \
        / config["router_width"]
    router = h * config["router_width"]
    return {"flop": layers * 6 * (tokens * router + rows * expert),
            "bytes": layers * 4 * (3 * (config["num_experts"] * expert
                                        + router) + 5 * tokens * h)}


def tiny(config, traffic):
    config = dict(config, vocab_size=96, classes=96, hidden_size=32,
                  num_key_value_heads=2, head_dim=8,
                  num_attention_heads_per_layer=[4, 8, 8, 8, 4],
                  intermediate_size=48, sliding_window=24, router_width=16,
                  num_experts=4, num_experts_per_tok=3,
                  moe_intermediate_size=12,
                  shared_expert_intermediate_size=12)
    return config, dict(traffic, batch=2, seq_len=80, pool=2)
