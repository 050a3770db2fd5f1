"""Phi-4-mini-flash-reasoning next-token pretraining, as
`paddle_tpu/models/phi4_flash.py` builds it: what the harness needs of
the configuration `phi4_mini_flash.json`.

    build(config, traffic)             -> (main, startup, fetches)
    make_batches(config, traffic, seed, k) -> k feed dicts
    flops_per_sample(config, traffic)  -> required forward + backward FLOP
    tiny(config, traffic)              -> the CPU rehearsal's toy sizes

and what the per-layer roofline shares divide by, a step of the cell:

    ssm_required(config, traffic)      -> {"flop": .., "bytes": ..}
    attn_required(config, traffic)     -> {"flop": .., "bytes": ..}

Every count is of work the layer equations REQUIRE
(`phi4_mini_flash_reference.py`), whatever implements it: attention over
the key positions its mask keeps and no other, the scan by its
recurrence, nothing recomputed, no padded block, no cast.
"""
import numpy as np

SCAN_FLOP = 7  # forward, a (position, channel, state): see ssm_required


def model_cfg(config):
    """The program's names for the configuration's sizes."""
    hidden = config["hidden_size"]
    return dict(
        vocab_size=config["vocab_size"], hidden=hidden,
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"],
        head_dim=hidden // config["num_attention_heads"],
        mlp_width=config["intermediate_size"],
        window=config["sliding_window"], eps=config["layer_norm_eps"],
        d_inner=config["mamba_expand"] * hidden,
        d_state=config["mamba_d_state"], d_conv=config["mamba_d_conv"],
        dt_rank=config["mamba_dt_rank"],
        layer_kinds=list(config["layer_kinds"]),
        published_index=list(config["published_index"]), init_std=0.02)


def build(config, traffic):
    from paddle_tpu.models import phi4_flash
    main, startup, _, fetches = phi4_flash.build_phi4_flash_pretrain_program(
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


def _count(config, *kinds):
    return sum(k in kinds for k in config["layer_kinds"])


def matmul_weights_per_token(config):
    """Weights a token is multiplied by, by part: every projection and
    the head (the tied embedding, as the matrix of the logits). Norms,
    biases, the depthwise filter, the scan's A, D and step bias, the
    lambda vectors and the embedding lookup are not matmuls and are not
    counted."""
    c = model_cfg(config)
    h, inner = c["hidden"], c["d_inner"]
    q, kv = c["heads"] * c["head_dim"], c["kv_heads"] * c["head_dim"]
    return {
        "mamba": _count(config, "mamba", "mamba_memory") * (
            h * 2 * inner                                   # w_in
            + inner * (c["dt_rank"] + 2 * c["d_state"])     # w_x
            + c["dt_rank"] * inner + inner * h),            # w_dt, w_out
        "self_attention": _count(config, "sliding", "full") * (
            h * (q + 2 * kv) + q * h),                      # w_qkv, w_o
        "gmu": _count(config, "gmu") * 2 * h * inner,
        "cross_attention": _count(config, "cross") * 2 * h * q,
        "mlp": len(config["layer_kinds"]) * 3 * h * c["mlp_width"],
        "head": h * c["vocab_size"],
    }


def kept_keys(seq_len, window):
    """Key positions all the queries of one causal sequence see: the
    causal half, or under a window min(t + 1, w) for query t."""
    if not window or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def attention_flop_per_sample(config, traffic):
    """Forward FLOP of the attention maps of one sequence, all attention
    layers: a query head's scores over the keys its mask keeps (2 d a
    key) and its weighted sum of values twice as wide (2 x 2 d)."""
    c = model_cfg(config)
    s = traffic["seq_len"]
    keys = _count(config, "sliding") * kept_keys(s, c["window"]) \
        + _count(config, "full", "cross") * kept_keys(s, 0)
    return keys * c["heads"] * 6 * c["head_dim"]


def scan_flop_per_sample(config, traffic):
    """Forward FLOP of the selective scans of one sequence: SCAN_FLOP a
    (position, channel, state)."""
    c = model_cfg(config)
    return _count(config, "mamba", "mamba_memory") * traffic["seq_len"] \
        * c["d_inner"] * c["d_state"] * SCAN_FLOP


def flops_per_sample(config, traffic):
    """Forward + backward FLOP one sequence requires (a multiply-add is
    two; backward twice the forward): 6 x matmul weights x tokens, the
    attention maps over the keys each mask keeps (512 a query under the
    window, the causal half in the full and cross layers), the scans'
    recurrence. Norms, gates, softmax, the depthwise convolution, the
    embedding lookup and Adam are not counted, and nothing recomputed
    is, nor a block a kernel visits and then masks."""
    s = traffic["seq_len"]
    weights = sum(matmul_weights_per_token(config).values())
    return float(6 * weights * s
                 + 3 * attention_flop_per_sample(config, traffic)
                 + 3 * scan_flop_per_sample(config, traffic))


def ssm_required(config, traffic):
    """A step's `selective_scan` ops, forward + backward, all layers.
    flop, a (position, channel, state) forward: Delta A (1), its exp
    (1), (Delta u) B (1), the state's multiply-add (2), the readout's
    (2) = SCAN_FLOP; backward twice the forward. bytes, f32: the forward
    reads u and the step's pre-activation (C channels each), B and C (N
    each) and writes y; the backward reads them and y's gradient again
    and writes the four gradients; A_log is read twice and its gradient
    written once."""
    c = model_cfg(config)
    tokens = traffic["batch"] * traffic["seq_len"]
    layers = _count(config, "mamba", "mamba_memory")
    ch, n = c["d_inner"], c["d_state"]
    read = 2 * ch + 2 * n
    return {"flop": 3 * traffic["batch"] * scan_flop_per_sample(
                config, traffic),
            "bytes": layers * 4 * (tokens * (3 * read + 2 * ch)
                                   + 3 * ch * n)}


def attn_required(config, traffic):
    """A step's flash kernels, forward + backward, all attention layers.
    flop: the maps over the kept keys, backward twice the forward.
    bytes, in the operands' bf16: the forward reads Q, K, V (each key
    and value head once, not once a query head) and writes O; the
    backward reads Q, K, V, O and O's gradient and writes the three
    gradients."""
    c = model_cfg(config)
    tokens = traffic["batch"] * traffic["seq_len"]
    layers = _count(config, "sliding", "full", "cross")
    q = c["heads"] * c["head_dim"]
    kv = c["kv_heads"] * c["head_dim"]  # K's width, and V's
    o = c["heads"] * 2 * c["head_dim"]
    return {"flop": 3 * traffic["batch"] * attention_flop_per_sample(
                config, traffic),
            "bytes": layers * tokens * 2 * (
                (q + 2 * kv + o) + (q + 2 * kv + 2 * o) + (q + 2 * kv))}


def tiny(config, traffic):
    config = dict(config, vocab_size=96, classes=96, hidden_size=32,
                  num_attention_heads=8, num_key_value_heads=4,
                  intermediate_size=48, sliding_window=24,
                  mamba_d_state=4, mamba_dt_rank=2)
    return config, dict(traffic, batch=2, seq_len=80, pool=2)
