"""The attention op alone, both of its paths, on the chip: the table that
`ops/attention_ops.DENSE_MAX_SEQ` rests on.

For each sequence length, `fused_attention_qkv` forward + backward the
way a compiled step holds them — the forward op, then the grad op's
`jax.vjp` over the same kernel (`registry.run_generic_grad`), in ONE
jitted program so XLA may merge what it can — once with the Pallas flash
kernels forced and once with XLA's dense attention forced (the module
constant patched to 0 / to the sequence length, the way the tests do).
The batch shrinks as the sequence grows so every row moves the same
number of tokens. Per row: device ms a call from a profiler trace
(`benchmark/trace_reduce.py`: union of the device's operation intervals),
the compiler's temporary bytes, the largest device operations, and the
worst difference between the two paths' outputs and gradients.

    chiprun -- python tools/attention_paths.py            # BERT-base's shapes
    python tools/attention_paths.py --tiny                # CPU rehearsal

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


def _fwd_bwd(heads):
    import jax
    from paddle_tpu.ops.registry import OPS
    kernel = OPS.get("fused_attention_qkv").kernel
    attrs = {"num_heads": heads, "dropout_rate": 0.0, "causal": False}

    def op(q, k, v):
        return kernel({"Q": [q], "K": [k], "V": [v], "Bias": [None]},
                      dict(attrs))["Out"][0]

    def step(q, k, v, g):
        out = op(q, k, v)                    # fwd/fused_attention_qkv
        _, vjp = jax.vjp(op, q, k, v)        # bwd/fused_attention_qkv_grad
        return (out,) + tuple(vjp(g))
    return step


def measure(path, seq, *, tokens, heads, head_dim, iters, on_chip):
    """One row: ``path`` is "flash" or "dense"."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import trace_reduce
    from paddle_tpu.ops import attention_ops
    from paddle_tpu.ops.pallas import flash_attention as fa

    batch = tokens // seq
    rng = np.random.RandomState(seq)
    q, k, v, g = (jnp.asarray(rng.randn(batch, seq, heads * head_dim) * 0.5,
                              jnp.float32) for _ in range(4))
    bound = attention_ops.DENSE_MAX_SEQ
    attention_ops.DENSE_MAX_SEQ = 0 if path == "flash" else seq
    guard = contextlib.nullcontext() if on_chip else fa.interpret_guard()
    try:
        with guard:
            compiled = jax.jit(_fwd_bwd(heads)).lower(q, k, v, g).compile()
    finally:
        attention_ops.DENSE_MAX_SEQ = bound
    text = compiled.as_text()
    row = {"path": path, "seq": seq, "batch": batch, "heads": heads,
           "head_dim": head_dim,
           "kernel_calls": text.count("tpu_custom_call"),
           "temp_bytes": compiled.memory_analysis().temp_size_in_bytes}
    outs = jax.block_until_ready(compiled(q, k, v, g))      # warm
    if on_chip:
        trace_dir = tempfile.mkdtemp(prefix="attention_paths_")
        try:
            jax.profiler.start_trace(trace_dir)
            for _ in range(iters):
                jax.block_until_ready(compiled(q, k, v, g))
            jax.profiler.stop_trace()
            red = trace_reduce.reduce(*trace_reduce.read(trace_dir, ()),
                                      iters, top=6)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        row["device_ms"] = red["busy_s"] / iters * 1e3
        row["device_ops_ms"] = [[name, s / iters * 1e3]
                                for name, s in red["device_ops"]]
    return row, [np.asarray(o, np.float32) for o in outs]


# the BERT cell's attention (benchmark/workloads/bert_base.b128_s128.json):
# 16,384 tokens a step, 12 heads x 64; traced calls a row
FULL = dict(tokens=16384, heads=12, head_dim=64, iters=10)
TINY = dict(tokens=256, heads=2, head_dim=8, iters=1)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seqs", type=int, nargs="+", default=[128, 256, 512])
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal: 256 tokens, 2 heads x 8, kernels "
                         "through the interpreter, no device number")
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    from paddle_tpu.fluid import core
    from tools.device_peaks import device_stamp, require_tpu
    if not args.tiny:
        require_tpu("tools/attention_paths.py")
    size = TINY if args.tiny else FULL
    on_chip = jax.devices()[0].platform == "tpu"
    # the cell's precision: f32 activations in, bf16 matmul operands
    was = core.globals_["FLAGS_use_bf16_matmul"]
    core.set_flag("FLAGS_use_bf16_matmul", True)
    try:
        for seq in args.seqs:
            (flash, o_flash), (dense, o_dense) = (
                measure(path, seq, on_chip=on_chip, **size)
                for path in ("flash", "dense"))
            # each output's worst difference, as a share of its own scale
            diff = [float(np.abs(a - b).max() / max(1.0, np.abs(b).max()))
                    for a, b in zip(o_flash, o_dense)]
            for row in (flash, dense):
                row["max_diff_out_dq_dk_dv"] = diff
                row["device"] = device_stamp()
                print(json.dumps(row), flush=True)
    finally:
        core.set_flag("FLAGS_use_bf16_matmul", was)


if __name__ == "__main__":
    main()
