"""Model FLOP/s utilization: samples a second times the forward + backward
FLOP the configuration requires for a sample, over the chips' published
bf16 peak. Nothing recomputed is counted."""


def compute(run):
    peak = run.chips * run.peak(run.device_kind, "bf16_flops_per_s")
    return 100.0 * run.rate * run.flops_per_sample / peak
