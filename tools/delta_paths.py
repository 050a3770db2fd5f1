"""The delta rule's two ops alone, both lowerings of their chunk
recurrence, on the chip: the table `ops/pallas/delta_rule.block_sizes`
rests on.

`gated_delta_rule` at the Qwen3-Next cell's call (1 x 4096 positions, 16
key heads repeated to 32 value heads of 128, chunks of 64) and `kda` at
the Ling cell's (1 x 4096, 32 heads of 128, chunks of 64, the decay a key
channel bounded at -5), bf16 matmul operands as the cells run them
(`--f32`: the float32 parity programs' operands at the "highest"
precision), forward alone and forward + backward the way a compiled step
holds them (the op, then `jax.vjp` over it, in ONE jitted program): once
through `solve_triangular` + `lax.scan` and once through the Pallas
kernels at each (heads, chunks) a grid step asked for. Per row: device
ms a call from a profiler trace (`benchmark/trace_reduce.py`: union of
the device's operation intervals) with the busiest device operations,
the compiler's temporary bytes, and the worst difference from the
`lax.scan` lowering's output and seven gradients, each as a share of its
own scale.

    python tools/delta_paths.py --blocks 4x8 8x4 4x4   # on a TPU host
    python tools/delta_paths.py --tiny                 # CPU rehearsal

Without a TPU only `--tiny` runs (kernels through the Pallas
interpreter): it rehearses the control flow and prints no device number.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from tools.scan_paths import device_ms  # noqa: E402

NAMES = ("Q", "K", "V", "A", "B", "ALog", "DtBias")


def _programs(op_type, attrs):
    import jax
    from paddle_tpu.ops.registry import OPS
    kernel = OPS.get(op_type).kernel

    def op(*args):
        return kernel({k: [v] for k, v in zip(NAMES, args)},
                      dict(attrs, site="delta_paths"))["Out"][0]

    def step(g, *args):
        out, vjp = jax.vjp(op, *args)
        return (out,) + tuple(vjp(g))
    return op, step


def _inputs(op_type, seq, key_heads, heads, dk, dv):
    """The op's seven inputs at one sequence of ``seq`` positions."""
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.RandomState(0)

    def normal(*shape, scale=1.0):
        return jnp.asarray(rng.randn(*shape) * scale, jnp.float32)
    channel = op_type == "kda"
    return (normal(1, seq, key_heads * dk), normal(1, seq, key_heads * dk),
            normal(1, seq, heads * dv),
            normal(1, seq, heads * dk if channel else heads, scale=2.0),
            normal(1, seq, heads),
            jnp.log(jnp.linspace(1.0, 16.0, heads)),
            normal(heads * dk if channel else heads, scale=0.5))


def measure(blocks, *, op_type, attrs, seq, key_heads, heads, dk, dv, iters,
            on_chip):
    """One row: ``blocks`` is None (`lax.scan`) or an (hb, nc) pair."""
    import jax
    import numpy as np
    from paddle_tpu.ops.pallas import delta_rule as dr
    from paddle_tpu.ops.pallas import flash_attention as fa

    args = _inputs(op_type, seq, key_heads, heads, dk, dv)
    g = jax.numpy.asarray(np.random.RandomState(1).randn(1, seq, heads * dv),
                          jax.numpy.float32)
    op, step = _programs(op_type, attrs)
    with contextlib.ExitStack() as stack:
        if blocks is None:  # the lowering of a backend without kernels
            was, dr.use_kernels = dr.use_kernels, lambda: False
            stack.callback(setattr, dr, "use_kernels", was)
        else:
            stack.enter_context(dr.block_override(*blocks))
            if not on_chip:
                stack.enter_context(fa.interpret_guard())
        fwd = jax.jit(op).lower(*args).compile()
        both = jax.jit(step).lower(g, *args).compile()
    row = {"op": op_type, "path": "lax.scan" if blocks is None else "kernels",
           "blocks": blocks,
           "shape": [seq, key_heads, heads, dk, dv],
           "kernel_calls": both.as_text().count("tpu_custom_call"),
           "temp_bytes": both.memory_analysis().temp_size_in_bytes}
    outs = jax.block_until_ready(both(g, *args))
    jax.block_until_ready(fwd(*args))
    if on_chip:
        row["fwd_device_ms"], _ = device_ms(fwd, args, iters)
        row["fwd_bwd_device_ms"], row["device_ops_ms"] = device_ms(
            both, (g,) + args, iters, top=6)
    return row, [np.asarray(o, np.float32) for o in outs]


# the two cells' calls (benchmark/configs/qwen3_next_80b_a3b.json,
# ling3_flash_vl.json at s4096)
CELLS = {"gdn": dict(op_type="gated_delta_rule",
                     attrs={"num_key_heads": 16, "num_value_heads": 32,
                            "chunk_size": 64},
                     seq=4096, key_heads=16, heads=32, dk=128, dv=128),
         "kda": dict(op_type="kda", attrs={"lower_bound": -5.0,
                                           "chunk_size": 64},
                     seq=4096, key_heads=32, heads=32, dk=128, dv=128)}
TINY = dict(seq=150, dk=8, dv=16)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--ops", nargs="+", default=sorted(CELLS),
                    choices=sorted(CELLS))
    ap.add_argument("--blocks", nargs="+", default=[],
                    help="HBxNC pairs to pin; none: the pair the op chooses")
    ap.add_argument("--f32", action="store_true",
                    help="float32 operands at the highest precision, as "
                         "the parity programs run")
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal: a small call, kernels through the "
                         "interpreter, no device number")
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    from paddle_tpu.fluid import core
    from paddle_tpu.ops.pallas import delta_rule as dr
    from tools.device_peaks import device_stamp, require_tpu
    if not args.tiny:
        require_tpu("tools/delta_paths.py")
    on_chip = jax.devices()[0].platform == "tpu"
    core.set_flag("FLAGS_use_bf16_matmul", not args.f32)
    precision = jax.default_matmul_precision(
        "highest" if args.f32 else "default")
    with precision:
        for name in args.ops:
            size = dict(CELLS[name], iters=args.iters)
            if args.tiny:
                size = dict(size, **TINY, iters=1, heads=4,
                            key_heads=2 if name == "gdn" else 4)
                if name == "gdn":
                    size["attrs"] = dict(size["attrs"], num_key_heads=2,
                                         num_value_heads=4)
            pairs = [tuple(int(v) for v in p.split("x"))
                     for p in args.blocks] or [dr.block_sizes(
                         size["heads"], -(-size["seq"] // 64), 64, size["dk"],
                         size["dv"], 4 if args.f32 or not on_chip else 2)]
            scan, want = measure(None, on_chip=on_chip, **size)
            scan["device"] = device_stamp()
            print(json.dumps(scan), flush=True)
            for pair in pairs:
                row, got = measure(pair, on_chip=on_chip, **size)
                row["max_diff_out_and_grads"] = [
                    float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))
                    for a, b in zip(got, want)]
                row["device"] = device_stamp()
                print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
