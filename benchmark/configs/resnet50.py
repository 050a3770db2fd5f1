"""ResNet-50 ImageNet training, as `paddle_tpu/models/resnet.py` builds
it: what the harness needs of the configuration `resnet50.json`.

    build(config, traffic)             -> (main, startup, fetches)
    make_batches(config, traffic, seed, k) -> k feed dicts
    flops_per_sample(config, traffic)  -> required forward + backward FLOP
    tiny(config, traffic)              -> the CPU rehearsal's toy sizes
"""
import numpy as np


def build(config, traffic):
    from paddle_tpu.models.resnet import build_resnet_train_program
    opt = config["optimizer"]
    main, startup, _, fetches = build_resnet_train_program(
        depth=config["depth"], class_dim=config["classes"],
        image_size=traffic["image_size"], lr=opt["lr"],
        momentum=opt["momentum"])
    return main, startup, fetches[:1]  # the loss; accuracy is not fetched


def make_batches(config, traffic, seed, k):
    """`k` host batches from the seed: f32 NCHW images uniform in [0, 1),
    int64 labels uniform over the classes."""
    rng = np.random.default_rng(seed)
    b, size = traffic["batch"], traffic["image_size"]
    return [{
        "image": rng.random((b, config["image_channels"], size, size),
                            dtype=np.float32),
        "label": rng.integers(0, config["classes"], (b, 1), dtype=np.int64),
    } for _ in range(k)]


def forward_multiply_adds(config, image_size):
    """Multiply-adds of one image's forward pass, counted from the shapes
    of the convolutions and the classifier as `models/resnet.py` lays
    them out (bottlenecks; the stride in the 3x3). Batch norm, ReLU,
    pooling and the residual additions are not counted."""
    def conv(side_out, c_in, c_out, kernel):
        return side_out * side_out * c_in * c_out * kernel * kernel

    def half(side):  # a stride-2 layer with "same" padding
        return (side + 1) // 2

    side = half(image_size)                      # 7x7 stem, stride 2
    total = conv(side, config["image_channels"], config["stem_filters"],
                 config["stem_kernel"])
    side = half(side)                            # 3x3 max pool, stride 2
    c_in, expansion = config["stem_filters"], config["bottleneck_expansion"]
    for stage, (blocks, f) in enumerate(zip(config["stage_blocks"],
                                            config["stage_filters"])):
        for block in range(blocks):
            stride = 2 if block == 0 and stage > 0 else 1
            out_side = half(side) if stride == 2 else side
            total += conv(side, c_in, f, 1)              # 1x1 reduce
            total += conv(out_side, f, f, 3)             # 3x3, strided
            total += conv(out_side, f, f * expansion, 1)  # 1x1 expand
            if c_in != f * expansion or stride != 1:
                total += conv(out_side, c_in, f * expansion, 1)  # shortcut
            side, c_in = out_side, f * expansion
    return total + c_in * config["classes"]      # classifier


def flops_per_sample(config, traffic):
    """Forward + backward FLOP one image requires: a multiply-add is two
    operations, and the backward pass costs twice the forward (a gradient
    for the input and one for the weights of every layer)."""
    return float(3 * 2 * forward_multiply_adds(config, traffic["image_size"]))


def tiny(config, traffic):
    config = dict(config, depth=18, classes=10)
    return config, dict(traffic, batch=4, image_size=32, pool=2)
