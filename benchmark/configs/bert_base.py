"""BERT-base masked-LM pretraining, as `paddle_tpu/models/bert.py` builds
it: what the harness needs of the configuration `bert_base.json`.

    build(config, traffic)             -> (main, startup, fetches)
    make_batches(config, traffic, seed, k) -> k feed dicts
    flops_per_sample(config, traffic)  -> required forward + backward FLOP
    tiny(config, traffic)              -> the CPU rehearsal's toy sizes
"""
import numpy as np


def _model_cfg(config):
    """The program's names for the published sizes."""
    return dict(vocab_size=config["vocab_size"],
                hidden=config["hidden_size"],
                layers=config["num_hidden_layers"],
                heads=config["num_attention_heads"],
                ffn=config["intermediate_size"],
                max_len=config["max_position_embeddings"],
                type_vocab=config["type_vocab_size"])


def build(config, traffic):
    from paddle_tpu.models import bert
    main, startup, _, fetches = bert.build_bert_pretrain_program(
        _model_cfg(config), seq_len=traffic["seq_len"],
        dropout=traffic["dropout"], lr=config["optimizer"]["lr"],
        use_input_mask=traffic.get("input_mask", False))
    return main, startup, fetches


def make_batches(config, traffic, seed, k):
    """`k` host batches from the seed: token ids, masked positions and
    labels uniform random; a fixed number of predictions a sequence, at
    positions inside that sequence, so that the mask feeds split over a
    data-parallel mesh wherever the batch does."""
    rng = np.random.default_rng(seed)
    b, s, m = traffic["batch"], traffic["seq_len"], traffic["predictions"]
    rows = np.repeat(np.arange(b, dtype=np.int64), m) * s
    out = []
    for _ in range(k):
        feed = {
            "src_ids": rng.integers(0, config["vocab_size"], (b, s),
                                    dtype=np.int64),
            "pos_ids": np.tile(np.arange(s, dtype=np.int64), (b, 1)),
            "sent_ids": np.zeros((b, s), np.int64),
            "mask_pos": (rows + rng.integers(0, s, b * m, dtype=np.int64)
                         ).reshape(-1, 1),
            "mask_label": rng.integers(0, config["vocab_size"], (b * m, 1),
                                       dtype=np.int64),
        }
        if traffic.get("input_mask", False):
            lo, hi = traffic["lengths"]
            lengths = rng.integers(lo, hi + 1, (b, 1))
            feed["input_mask"] = (np.arange(s)[None, :] < lengths
                                  ).astype(np.float32)
        out.append(feed)
    return out


def flops_per_sample(config, traffic):
    """Forward + backward FLOP one sequence requires (a multiply-add is
    two): 6 x weights x tokens for the encoder's matmuls, the attention
    scores and their weighted sum, and the masked-LM head over the
    predicted positions. Embedding lookups, layer norm, GELU and softmax
    are not counted, and nothing recomputed is."""
    h, layers = config["hidden_size"], config["num_hidden_layers"]
    f, s = config["intermediate_size"], traffic["seq_len"]
    encoder_weights = layers * (4 * h * h + 2 * h * f)
    attention = 12 * layers * s * s * h
    head = 6 * traffic["predictions"] * h * config["vocab_size"]
    return float(6 * encoder_weights * s + attention + head)


def tiny(config, traffic):
    config = dict(config, vocab_size=128, hidden_size=32,
                  num_hidden_layers=2, num_attention_heads=4,
                  intermediate_size=64, max_position_embeddings=32,
                  type_vocab_size=2, classes=128)
    traffic = dict(traffic, batch=8, seq_len=16, predictions=2, pool=2)
    if traffic.get("input_mask", False):
        traffic["lengths"] = [8, 16]
    return config, traffic


def attn_required(config, traffic):
    """A step's attention maps as the flash kernels run them, forward +
    backward, all layers: what `attn_roofline_pct` divides by. (That
    reader says nothing where no kernel ran, as at s128, which takes
    XLA's dense path: the count needs no threshold of its own.) Every
    query row of a padded sequence is computed (a prediction may lie on
    any of them); the input mask keeps a sequence's first L keys, L
    uniform in `lengths`, so the counts are the EXPECTATION over the
    lengths (the whole sequence without an input mask). flop: a head's
    scores and weighted sum over the kept keys (2 d + 2 d a key),
    backward twice the forward. bytes, in the operands' bf16, each
    `hidden` wide a token: the forward reads Q and the kept rows of K, V
    and writes O; the backward reads Q, O, O's gradient and the kept K,
    V and writes Q's gradient and the kept rows of K's and V's. The
    dropout mask is made in the kernel and costs no bytes."""
    s = traffic["seq_len"]
    kept = sum(traffic["lengths"]) / 2 if traffic.get("input_mask") else s
    h, layers = config["hidden_size"], config["num_hidden_layers"]
    return {"flop": 3.0 * traffic["batch"] * layers * s * kept * 4 * h,
            "bytes": traffic["batch"] * layers * 2 * h * (
                6 * s + 6 * kept)}
