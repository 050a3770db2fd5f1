"""The expert op alone, both of its lowerings, on the chip: what a pass of
`moe_expert_ffn` costs at the three expert cells' calls.

`moe_expert_ffn` at a cell's call (tokens x top-k, experts held of the
router's width, D x F, the activation; bf16 matmul operands), forward
alone and forward + backward the way a compiled step holds them (the op,
then `jax.vjp` over the same kernel, in ONE jitted program): once through
`lax.ragged_dot` and once through the Pallas kernels of
`ops/pallas/grouped_matmul.py`, at each row tile asked for. `--share` is
the part of all assignments the router sends the held experts: the held
share of its width at a uniform router (the default; a pass is then half
full), more where a trained router leans on them (SmallThinker's late
steps: 0.77, two passes a layer). Per row: device ms a call from a
profiler trace (`benchmark/trace_reduce.py`: union of the device's
operation intervals), the largest device operations by name, the
compiler's temporary bytes, and the worst difference from the
`lax.ragged_dot` lowering's output and four gradients, each as a share of
its own scale.

    chiprun -- python tools/moe_paths.py --cells smallthinker --share 0.25 0.77
    python tools/moe_paths.py --tiny                  # CPU rehearsal

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

NAMES = ("X", "TopkIdx", "TopkWeight", "WGateUp", "WDown")
# tokens, top-k, experts held, the router's width, D, F, the gate's
# activation (benchmark/configs/*.json at the cells' sequence lengths)
CELLS = {
    "qwen3_next": dict(tokens=4096, k=10, held=32, width=512, d=2048,
                       f=512, activation="silu"),
    "laguna": dict(tokens=8192, k=8, held=32, width=256, d=2048, f=512,
                   activation="silu"),
    "smallthinker": dict(tokens=16384, k=6, held=16, width=64, d=2560,
                         f=768, activation="relu"),
}
TINY = dict(tokens=96, k=3, held=4, width=16, d=128, f=128,
            activation="relu")


def _programs(width, activation):
    import jax
    from paddle_tpu.ops.registry import OPS
    kernel = OPS.get("moe_expert_ffn").kernel

    def op(x, idx, *rest):
        return kernel({k: [v] for k, v in zip(NAMES, (x, idx) + rest)},
                      {"expert_start": 0, "num_experts": width,
                       "site": "moe_paths", "activation": activation})

    def step(g, x, idx, *rest):
        out, vjp = jax.vjp(lambda x, *rest: op(x, idx, *rest)["Out"][0],
                           x, *rest)
        return (out,) + tuple(vjp(g))
    return lambda *args: op(*args)["Out"][0], step


def _inputs(share, *, tokens, k, held, width, d, f, **_):
    """The op's five inputs: a router that sends ``share`` of its
    assignments to the held experts 0 .. held - 1, evenly among them."""
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.RandomState(0)
    to_held = rng.rand(tokens, k) < share
    idx = np.where(to_held, rng.randint(0, held, (tokens, k)),
                   rng.randint(held, max(width, held + 1), (tokens, k)))
    weight = rng.rand(tokens, k).astype(np.float32)
    weight /= weight.sum(-1, keepdims=True)

    def normal(*shape, scale=1.0):
        return jnp.asarray(rng.randn(*shape) * scale, jnp.float32)
    return (normal(1, tokens, d), jnp.asarray(idx[None], jnp.int32),
            jnp.asarray(weight[None]), normal(held, d, 2 * f, scale=0.02),
            normal(held, f, d, scale=0.02)), normal(1, tokens, d)


def measure(tile, share, size, *, iters, on_chip, top, keep=None):
    """One row: ``tile`` is None (`lax.ragged_dot`) or a row tile, 0 for
    the one `_block_sizes` chooses. ``keep``: a directory that keeps the
    forward + backward program's text and its trace."""
    import jax
    import numpy as np
    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.ops.pallas import grouped_matmul as gm
    from tools.scan_paths import device_ms

    args, g = _inputs(share, **size)
    op, step = _programs(size["width"], size["activation"])
    with contextlib.ExitStack() as stack:
        if tile is None:  # the lowering of a backend without kernels
            was, gm.use_kernels = gm.use_kernels, lambda: False
            stack.callback(setattr, gm, "use_kernels", was)
        else:
            if tile:
                stack.enter_context(gm.block_override(tile))
            if not on_chip:
                stack.enter_context(fa.interpret_guard())
        fwd = jax.jit(op).lower(*args).compile()
        both = jax.jit(step).lower(g, *args).compile()
    row = {"path": "ragged_dot" if tile is None else "kernels",
           "row_tile": tile, "share": share,
           "kernel_calls": both.as_text().count("tpu_custom_call"),
           "temp_bytes": both.memory_analysis().temp_size_in_bytes}
    outs = jax.block_until_ready(both(g, *args))
    jax.block_until_ready(fwd(*args))
    if on_chip:
        row["fwd_device_ms"], _ = device_ms(fwd, args, iters, top)
        if keep:
            keep = os.path.join(keep, f"{row['path']}_{tile}_{share}")
            os.makedirs(keep, exist_ok=True)
            with open(os.path.join(keep, "step.hlo"), "w") as f:
                f.write(both.as_text())
        row["fwd_bwd_device_ms"], row["device_ops_ms"] = device_ms(
            both, (g,) + args, iters, top, keep)
    return row, [np.asarray(o, np.float32) for o in outs]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", nargs="+", default=list(CELLS),
                    choices=list(CELLS))
    ap.add_argument("--share", nargs="+", type=float, default=[],
                    help="part of the assignments sent to the held experts; "
                         "none: held / width, a uniform router's")
    ap.add_argument("--tiles", nargs="+", type=int, default=[0],
                    help="row tiles to pin; 0: the one the op chooses")
    ap.add_argument("--top", type=int, default=12)
    ap.add_argument("--keep", help="a directory (under chiprun_out/) that "
                    "keeps each row's compiled text and its trace")
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal: a small call, kernels through the "
                         "interpreter, no device number")
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    from paddle_tpu.fluid import core
    from tools.device_peaks import device_stamp, require_tpu
    if not args.tiny:
        require_tpu("tools/moe_paths.py")
    on_chip = jax.devices()[0].platform == "tpu"
    was = core.globals_["FLAGS_use_bf16_matmul"]
    core.set_flag("FLAGS_use_bf16_matmul", True)  # the cells' precision
    try:
        for name, size in ({"tiny": TINY} if args.tiny else
                           {c: CELLS[c] for c in args.cells}).items():
            for share in args.share or [size["held"] / size["width"]]:
                kw = dict(iters=1 if args.tiny else 5, on_chip=on_chip,
                          top=args.top,
                          keep=args.keep and os.path.join(args.keep, name))
                base, want = measure(None, share, size, **kw)
                print(json.dumps(dict(base, cell=name,
                                      device=device_stamp())), flush=True)
                for tile in args.tiles:
                    row, got = measure(tile, share, size, **kw)
                    row["max_diff_out_and_grads"] = [
                        float(np.abs(a - b).max()
                              / max(np.abs(b).max(), 1e-30))
                        for a, b in zip(got, want)]
                    print(json.dumps(dict(row, cell=name,
                                          device=device_stamp())), flush=True)
    finally:
        core.set_flag("FLAGS_use_bf16_matmul", was)


if __name__ == "__main__":
    main()
