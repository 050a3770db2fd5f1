"""Op kernels: steps of the forward flash kernel's whole grid a step,
all attention layers, heads and sequences, the steps a causal mask skips
included: the gauge `attn_grid_steps_per_step` the
`fused_attention_qkv` op sets a layer where it is traced onto the
kernels (`fluid/telemetry.py`'s registry), at the blocks chosen for the
call, summed over the layers. Beside `attn_kv_blocks_per_step`, the
pairs whose scores are computed: a step costs its fixed time whether it
computes or not."""
import sys


def compute(run):
    telemetry = sys.modules.get("paddle_tpu.fluid.telemetry")
    if telemetry is None or run.trace is None:
        return None  # no program, or no chip's trace: a rehearsal
    family = telemetry.REGISTRY.get("attn_grid_steps_per_step")
    if family is None:
        return None  # a program without the counter
    return sum(child.value() for child in family.children()) or None
