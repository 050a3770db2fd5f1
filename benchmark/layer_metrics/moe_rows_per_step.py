"""Op kernels: rows the grouped expert products run over a step, all
layers: the gauge `moe_rows_per_step` the `moe_expert_ffn` op sets a
layer where it is traced (`fluid/telemetry.py`'s registry), summed over
the layers. Against tokens x top-k x held / router width it is the
padding a static shape costs."""
import sys


def compute(run):
    telemetry = sys.modules.get("paddle_tpu.fluid.telemetry")
    if telemetry is None or run.trace is None:
        return None  # no program, or no chip's trace: a rehearsal
    family = telemetry.REGISTRY.get("moe_rows_per_step")
    if family is None:
        return None  # a program without the counter
    return sum(child.value() for child in family.children()) or None
