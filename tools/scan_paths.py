"""The state-space scan op alone, both of its lowerings, on the chip: the
table `ops/pallas/selective_scan._block_sizes` rests on.

`selective_scan` at the Phi cell's call (1 x 4096 positions x 5120
channels x 16 states, chunks of 64, float32), forward alone and forward +
backward the way a compiled step holds them (the op, then `jax.vjp` over
the same kernel, in ONE jitted program): once through `lax.scan` and once
through the Pallas kernels at each (channel block, positions a grid step)
pair asked for. Per row: device ms a call from a profiler trace
(`benchmark/trace_reduce.py`: union of the device's operation
intervals), the compiler's temporary bytes, and the worst difference from
the `lax.scan` lowering's output and seven gradients, each as a share of
its own scale.

    chiprun -- python tools/scan_paths.py --blocks 512x64 256x64 128x64
    python tools/scan_paths.py --tiny                 # CPU rehearsal

Without a TPU only `--tiny` runs (kernels through the Pallas
interpreter): it rehearses the control flow and prints no device number.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

NAMES = ("X", "Dt", "B", "C", "ALog", "D", "DtBias")


def _programs(chunk):
    import jax
    from paddle_tpu.ops.registry import OPS
    kernel = OPS.get("selective_scan").kernel

    def op(*args):
        return kernel({k: [v] for k, v in zip(NAMES, args)},
                      {"chunk_size": chunk, "site": "scan_paths"})["Out"][0]

    def step(g, *args):
        out, vjp = jax.vjp(op, *args)
        return (out,) + tuple(vjp(g))
    return op, step


def device_ms(compiled, args, iters, top=4, keep=None):
    """(device ms a call, the ``top`` device operations by name, ms a
    call) over ``iters`` traced calls; ``keep``: a directory that keeps
    the trace."""
    import jax
    import trace_reduce
    trace_dir = keep or tempfile.mkdtemp(prefix="scan_paths_")
    try:
        jax.profiler.start_trace(trace_dir)
        for _ in range(iters):
            jax.block_until_ready(compiled(*args))
        jax.profiler.stop_trace()
        red = trace_reduce.reduce(*trace_reduce.read(trace_dir, ()), iters,
                                  top=top)
    finally:
        if not keep:
            shutil.rmtree(trace_dir, ignore_errors=True)
    return red["busy_s"] / iters * 1e3, \
        [[name, s / iters * 1e3] for name, s in red["device_ops"]]


def measure(blocks, *, batch, seq, channels, states, chunk, iters, on_chip):
    """One row: ``blocks`` is None (`lax.scan`) or a (cb, T) pair."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.ops.pallas import selective_scan as ss

    rng = np.random.RandomState(0)
    def normal(*shape):
        return jnp.asarray(rng.randn(*shape), jnp.float32)
    args = (normal(batch, seq, channels), normal(batch, seq, channels),
            normal(batch, seq, states), normal(batch, seq, states),
            jnp.log(jnp.tile(jnp.arange(1.0, states + 1), (channels, 1))),
            jnp.ones((channels,), jnp.float32), normal(channels) - 2.0)
    g = normal(batch, seq, channels)
    op, step = _programs(chunk)
    with contextlib.ExitStack() as stack:
        if blocks is None:  # the lowering of a backend without kernels
            was, ss.use_kernels = ss.use_kernels, lambda: False
            stack.callback(setattr, ss, "use_kernels", was)
        else:
            stack.enter_context(ss.block_override(*blocks))
            if not on_chip:
                stack.enter_context(fa.interpret_guard())
        fwd = jax.jit(op).lower(*args).compile()
        both = jax.jit(step).lower(g, *args).compile()
    row = {"path": "lax.scan" if blocks is None else "kernels",
           "blocks": blocks, "shape": [batch, seq, channels, states, chunk],
           "kernel_calls": both.as_text().count("tpu_custom_call"),
           "temp_bytes": both.memory_analysis().temp_size_in_bytes}
    outs = jax.block_until_ready(both(g, *args))
    jax.block_until_ready(fwd(*args))
    if on_chip:
        row["fwd_device_ms"], _ = device_ms(fwd, args, iters)
        row["fwd_bwd_device_ms"], row["device_ops_ms"] = device_ms(
            both, (g,) + args, iters)
    return row, [np.asarray(o, np.float32) for o in outs]


# the Phi cell's scan (benchmark/configs/phi4_mini_flash.json at s4096)
FULL = dict(batch=1, seq=4096, channels=5120, states=16, chunk=64, iters=5)
TINY = dict(batch=2, seq=40, channels=200, states=4, chunk=8, iters=1)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--blocks", nargs="+", default=[],
                    help="CBxT pairs to pin; none: the pair the op chooses")
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal: a small call, kernels through the "
                         "interpreter, no device number")
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    from paddle_tpu.ops.pallas import selective_scan as ss
    from tools.device_peaks import device_stamp, require_tpu
    if not args.tiny:
        require_tpu("tools/scan_paths.py")
    size = TINY if args.tiny else FULL
    on_chip = jax.devices()[0].platform == "tpu"
    pairs = [tuple(int(v) for v in p.split("x")) for p in args.blocks] \
        or [ss._block_sizes(size["channels"], size["states"], size["seq"],
                            size["chunk"])]
    base, want = measure(None, on_chip=on_chip, **size)
    base["device"] = device_stamp()
    print(json.dumps(base), flush=True)
    for pair in pairs:
        row, got = measure(pair, on_chip=on_chip, **size)
        row["max_diff_out_and_grads"] = [
            float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))
            for a, b in zip(got, want)]
        row["device"] = device_stamp()
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
