"""Op kernels: the whole step's share of the chips' bf16 peak WHILE THE
DEVICE IS BUSY: the forward + backward FLOP the configuration requires
for a step (`flops_per_sample` x the global batch, nothing recomputed)
over the device time a traced step keeps a chip busy x the chips x the
published peak. It stands beside the kernels' roofline shares: a kernel
taken off the path leaves its own share silent, and this one still
bounds the claim (the benchmark's contract asks for it there: a
per-layer share of the peak with `mfu` in its name, moving the metric
the kernels' shares move). `mfu_pct`, end to end, is the same FLOP over
the WINDOW's wall time; this one is taken from the ten TRACED steps,
which follow the window. So it is `mfu_pct` / (1 - idle) only where
those steps cost what the window's did, and it can read BELOW
`mfu_pct`: on the Laguna cell, whose router is trained as the window
goes, the traced steps are busy 487.8 ms where the window's mean step
took 472.9, and it read 20.50 under `mfu_pct` 21.15 (PR 38)."""


def compute(run):
    trace = run.trace
    if not trace or not trace["steps"] or not trace["busy_s"]:
        return None
    busy_s = trace["busy_s"] / trace["steps"]  # a step, mean of the chips
    peak = run.chips * run.peak(run.device_kind, "bf16_flops_per_s")
    return 100.0 * run.flops_per_sample * run.samples_per_step \
        / (busy_s * peak)
