"""MFU report from XLA's OWN cost analysis of the compiled train step
(reference counterpart: operators/benchmark/op_tester.cc's
measure-don't-assert discipline, plus the BASELINE.md "≥45% MFU" bar
this framework is judged against). Needs a TPU: without one it exits
non-zero and prints nothing.

Instead of the hand 6·N·D FLOP formula, this lowers the FULL fluid
program (fwd+bwd+optimizer, the same _CompiledBlock step the executor
runs) and asks the compiler: `compiled.cost_analysis()["flops"]`. MFU is
then measured-time against peak. Optionally captures a profiler trace
directory for TensorBoard/XProf offline reading.

Usage:
    python -m tools.mfu_report [bert|mnist] [--trace-dir DIR]
Emits one JSON line:
    {"model": ..., "xla_flops_per_step": ..., "step_ms": ...,
     "achieved_tflops": ..., "mfu_vs_bf16_peak": ..., "device": {...}}
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np

from tools.device_peaks import (bf16_peak_flops, device_stamp,
                                require_tpu)


def compiled_step_of(exe):
    """The executor's jitted step for the LAST program it ran (its
    _CompiledBlock), for lowering/cost analysis."""
    if not exe._compiled_cache:
        raise RuntimeError("run the program once before asking for its "
                           "compiled step")
    return list(exe._compiled_cache.values())[-1]


def analyze(cb, scope, feed_arrays, rng):
    """Lower the step and return XLA's cost analysis dict. Reuses the
    executor's OWN jitted step (cb._jitted), so the already-compiled
    train step is not re-compiled — on TPU that second compile would
    roughly double the tool's wall time."""
    mut = {n: scope.find_var(n).get_tensor().array for n in cb.mut_state}
    ro = {n: scope.find_var(n).get_tensor().array for n in cb.ro_state}
    lowered = cb._jitted.lower(mut, ro, feed_arrays, rng)
    return lowered.compile().cost_analysis() or {}


def _build(model):
    """(main, startup, feed, fetch_list, batch) of the measured step."""
    import paddle_tpu.fluid as fluid
    if model == "bert":
        from paddle_tpu.models import bert
        cfg = bert.bert_base_config()
        batch, seq_len = 128, 128  # the bert_base.b128_s128 cell's size
        main, startup, feeds, fetches = bert.build_bert_pretrain_program(
            cfg, seq_len=seq_len, dropout=0.0, lr=1e-4)
        return (main, startup,
                bert.synthetic_pretrain_batch(cfg, batch, seq_len),
                fetches, batch)
    batch = 64
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.data("img", shape=[784], dtype="float32")
        label = fluid.data("label", shape=[1], dtype="int64")
        h = fluid.layers.fc(img, 256, act="relu")
        pred = fluid.layers.fc(h, 10, act="softmax")
        loss = fluid.layers.mean(
            fluid.layers.cross_entropy(pred, label))
        fluid.optimizer.SGD(0.01).minimize(loss)
    rng_np = np.random.RandomState(0)
    feed = {"img": rng_np.rand(batch, 784).astype("float32"),
            "label": rng_np.randint(0, 10, (batch, 1)).astype("int64")}
    return main, startup, feed, [loss], batch


def report(model="bert", steps=10, trace_dir=None, timed=True):
    """``timed=False`` stops after the compiler's counts (flops, bytes):
    those are facts about the program and hold on any backend. A step
    time and an MFU are facts about a chip: ``timed`` needs a TPU."""
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import core

    chip = require_tpu("tools.mfu_report") if timed else None
    prev_bf16 = core.globals_["FLAGS_use_bf16_matmul"]
    core.set_flag("FLAGS_use_bf16_matmul", model == "bert")
    try:
        main, startup, feed, fetch_list, batch = _build(model)
        exe = fluid.Executor()
        scope = core.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            exe.run(main, feed=feed, fetch_list=fetch_list,
                    return_numpy=False)  # compile + cache
            cb = compiled_step_of(exe)
            feed_arrays = {k: core._to_device_array(v)
                           for k, v in feed.items()}
            cost = analyze(cb, scope, feed_arrays, jax.random.key(0))

            def timed_window():
                # one dispatched scan per window (exe.run n_steps): the
                # first call compiles and warms — and must be SYNCED
                # before the clock starts, or the timed dispatch queues
                # behind the still-executing warm window
                w = exe.run(main, feed=feed, fetch_list=fetch_list,
                            return_numpy=False, n_steps=steps)
                _ = np.asarray(w[0].array).ravel()[:1]
                t0 = time.perf_counter()
                o = exe.run(main, feed=feed, fetch_list=fetch_list,
                            return_numpy=False, n_steps=steps)
                _ = np.asarray(o[0].array).ravel()[:1]
                return (time.perf_counter() - t0) / steps

            dt = None
            if timed and trace_dir:
                import jax.profiler
                with jax.profiler.trace(trace_dir):
                    dt = timed_window()
            elif timed:
                dt = timed_window()
    finally:
        core.set_flag("FLAGS_use_bf16_matmul", prev_bf16)

    flops = float(cost.get("flops", 0.0))
    out = {"model": model, "xla_flops_per_step": flops, "batch": batch,
           "device": device_stamp()}
    if cost.get("bytes accessed") is not None:
        ba = float(cost["bytes accessed"])
        out["xla_bytes_accessed"] = ba
        # arithmetic intensity — below ~240 flops/byte the step is
        # HBM-bound on v5e (197e12 / 819e9)
        out["flops_per_byte"] = round(flops / ba, 2) if ba else 0.0
    if dt is not None:
        out["step_ms"] = round(dt * 1e3, 3)
        out["achieved_tflops"] = round(flops / dt / 1e12, 3)
        out["mfu_vs_bf16_peak"] = round(
            flops / dt / bf16_peak_flops(chip), 4)
    if trace_dir:
        out["trace_dir"] = trace_dir
    return out


def main():
    model = "bert"
    trace_dir = None
    args = sys.argv[1:]
    if args and not args[0].startswith("-"):
        model = args[0]
        args = args[1:]
    if "--trace-dir" in args:
        i = args.index("--trace-dir")
        if i + 1 >= len(args) or args[i + 1].startswith("-"):
            raise SystemExit("--trace-dir requires a directory argument")
        trace_dir = args[i + 1]
    print(json.dumps(report(model, trace_dir=trace_dir)))


if __name__ == "__main__":
    main()
