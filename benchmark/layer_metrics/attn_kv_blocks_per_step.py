"""Op kernels: (Q block, K block) pairs whose scores the forward flash
kernel computes a step, all attention layers, heads and sequences: the
gauge `attn_kv_blocks_per_step` the `fused_attention_qkv` op sets a
layer where it is traced onto the kernels (`fluid/telemetry.py`'s
registry), summed over the layers. A window layer adds the pairs that
hold a key of some row's window and no other."""
import sys


def compute(run):
    telemetry = sys.modules.get("paddle_tpu.fluid.telemetry")
    if telemetry is None or run.trace is None:
        return None  # no program, or no chip's trace: a rehearsal
    family = telemetry.REGISTRY.get("attn_kv_blocks_per_step")
    if family is None:
        return None  # a program without the counter
    return sum(child.value() for child in family.children()) or None
