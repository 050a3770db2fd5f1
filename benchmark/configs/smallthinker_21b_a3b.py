"""SmallThinker-21BA3B next-token pretraining, as
`paddle_tpu/models/smallthinker.py` builds it: what the harness needs of
the configuration `smallthinker_21b_a3b.json`.

    build(config, traffic)             -> (main, startup, fetches)
    make_batches(config, traffic, seed, k) -> k feed dicts
    flops_per_sample(config, traffic)  -> required forward + backward FLOP
    tiny(config, traffic)              -> the CPU rehearsal's toy sizes

and what the per-layer roofline shares divide by, a step of the cell:

    attn_required(config, traffic)     -> {"flop": .., "bytes": ..}
    moe_required(config, traffic)      -> {"flop": .., "bytes": ..}

Every count is of work the layer equations REQUIRE
(`smallthinker_21b_a3b_reference.py`), whatever implements it: attention
over the key positions its mask keeps and no other, the EXPECTED routed
rows, nothing recomputed, no padded row or block, no cast, no repeated
K / V.
"""
import numpy as np


def layer_lists(config):
    """(rotary?, windowed?) of the layers that are here: the first
    `num_hidden_layers` entries of the published `rope_layout` and
    `sliding_window_layout`, which the file keeps whole."""
    n = config["num_hidden_layers"]
    return (list(config["rope_layout"][:n]),
            list(config["sliding_window_layout"][:n]))


def model_cfg(config):
    """The program's names for the configuration's sizes."""
    rotary, windowed = layer_lists(config)
    return dict(
        vocab_size=config["vocab_size"], hidden=config["hidden_size"],
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        rope_layout=rotary, window_layout=windowed,
        window=config["sliding_window_size"],
        rope_theta=float(config["rope_theta"]),
        num_experts=config["router_width"],
        experts_per_tok=config["moe_num_active_primary_experts"],
        expert_width=config["moe_ffn_hidden_size"],
        eps=config["rms_norm_eps"],
        experts_held=config["moe_num_primary_experts"],
        expert_start=config["expert_start"], init_std=0.02,
        embed_init_std=config["embedding_init_std"])


def build(config, traffic):
    from paddle_tpu.models import smallthinker
    main, startup, _, fetches = \
        smallthinker.build_smallthinker_pretrain_program(
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


def matmul_weights_per_token(config):
    """Weights a token is multiplied by, by part: the four projections,
    the router, the EXPECTED routed experts (top-k x held / router width
    of them), the head. Norms and the embedding lookup are not matmuls
    and are not counted; there is no shared expert and no dense layer."""
    h, d = config["hidden_size"], config["head_dim"]
    q = config["num_attention_heads"] * d
    kv = config["num_key_value_heads"] * d
    layers = config["num_hidden_layers"]
    return {
        "attention": layers * (2 * h * q + 2 * h * kv),
        "router": layers * h * config["router_width"],
        "routed_expected": layers * 3 * h * config["moe_ffn_hidden_size"]
        * config["moe_num_active_primary_experts"]
        * config["moe_num_primary_experts"] / config["router_width"],
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
    weighted sum of values (2 d)."""
    s, d = traffic["seq_len"], config["head_dim"]
    return sum(
        kept_keys(s, config["sliding_window_size"] if windowed else 0)
        * config["num_attention_heads"] * 4 * d
        for windowed in layer_lists(config)[1])


def flops_per_sample(config, traffic):
    """Forward + backward FLOP one sequence requires (a multiply-add is
    two; backward twice the forward): 6 x matmul weights x tokens, and
    the attention maps over the keys each mask keeps (4096 a query under
    the window, the causal half in the full layer). Norms, softmax,
    rotary, the router's top-k, the embedding lookup and Adam are not
    counted, and nothing recomputed is, nor a block a kernel visits and
    then masks."""
    weights = sum(matmul_weights_per_token(config).values())
    return float(6 * weights * traffic["seq_len"]
                 + 3 * attention_flop_per_sample(config, traffic))


def attn_required(config, traffic):
    """A step's flash kernels, forward + backward, all layers. flop: the
    maps over the kept keys, backward twice the forward. bytes, in the
    operands' bf16: the forward reads Q, K, V (each key and value head
    once, not once a query head of its group of seven) and writes O; the
    backward reads Q, K, V, O and O's gradient and writes the three
    gradients."""
    tokens = traffic["batch"] * traffic["seq_len"]
    d = config["head_dim"]
    q = config["num_attention_heads"] * d
    kv = config["num_key_value_heads"] * d
    widths = config["num_hidden_layers"] * (
        (q + 2 * kv + q) + (q + 2 * kv + 2 * q) + (q + 2 * kv))
    return {"flop": 3 * traffic["batch"] * attention_flop_per_sample(
                config, traffic),
            "bytes": tokens * 2 * widths}


def moe_required(config, traffic):
    """A step's `moe_router` + `moe_expert_ffn` ops, forward + backward,
    all layers. flop: the router's logits over its 64 outputs and the
    EXPECTED routed rows (tokens x top-k x held / router width) through
    an expert's three projections, backward twice the forward. bytes:
    every held expert's f32 master weights read once forward and once
    backward and their f32 gradient written once; the router's weights
    likewise; forward the router's input r and the experts' input g read
    and y written, backward r, g and dy read and dr, dg written (r is
    the layer's input, g the post-attention normed state: two tensors
    here, where a router on the experts' own input has one)."""
    tokens = traffic["batch"] * traffic["seq_len"]
    h, layers = config["hidden_size"], config["num_hidden_layers"]
    expert = 3 * h * config["moe_ffn_hidden_size"]
    rows = tokens * config["moe_num_active_primary_experts"] \
        * config["moe_num_primary_experts"] / config["router_width"]
    router = h * config["router_width"]
    return {"flop": layers * 6 * (tokens * router + rows * expert),
            "bytes": layers * 4 * (
                3 * (config["moe_num_primary_experts"] * expert + router)
                + 8 * tokens * h)}


def tiny(config, traffic):
    config = dict(config, vocab_size=96, classes=96, hidden_size=32,
                  num_attention_heads=14, num_key_value_heads=2, head_dim=8,
                  sliding_window_size=24, router_width=16,
                  moe_num_primary_experts=4,
                  moe_num_active_primary_experts=3, moe_ffn_hidden_size=12)
    return config, dict(traffic, batch=2, seq_len=80, pool=2)
