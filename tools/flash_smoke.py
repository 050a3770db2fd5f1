"""Flash-attention hardware sweep: compile the Pallas kernels via Mosaic
(NO interpret mode), check on-chip parity vs the einsum path, and sweep
block sizes — one JSON row per configuration.

Everything that can fail on Mosaic contact — scratch shapes, SMEM scalar
handling, dimension_semantics, VMEM budgets — is exercised here in one
command (the compile alone is also asked of the chip's compiler, without
a chip, in tests/test_chip_compile.py). Reference counterpart:
operators/fused/multihead_matmul_op.cu is the reference's fused fast
path; operators/benchmark/op_tester.cc is its measure-don't-assert
harness.

Usage (needs a TPU; exits non-zero without one):
    python -m tools.flash_smoke            # full sweep

Per-config JSON row fields: seq_len, blk_q, blk_k, dtype, causal,
dropout, fwd_ms, fwdbwd_ms, tflops_fwd, vmem_kb_est, max_err_fwd,
max_err_dq/dk/dv, dropout_deterministic, status ('ok' | 'parity_fail' |
'compile_error'), error.
"""
from __future__ import annotations

import contextlib
import json
import time
import traceback

import numpy as np


def _vmem_kb_estimate(blk_q, blk_k, D, bwd=False):
    """Analytic resident-VMEM estimate per grid step (f32 working set):
    fwd: q, k, v tiles + acc[blk_q,D] + m/l[blk_q,128] + o tile.
    bwd adds do/lse/delta tiles and the dk/dv (or dq) accumulators."""
    f = 4  # f32 working set (inputs are upcast in-kernel)
    fwd = (blk_q * D + 2 * blk_k * D) * f            # q,k,v tiles
    fwd += blk_q * D * f                             # acc scratch
    fwd += 2 * blk_q * 128 * f                       # m, l scratch
    fwd += blk_q * D * f                             # o tile
    if not bwd:
        return fwd / 1024.0
    b = blk_q * D * f                                # do tile
    b += 2 * blk_q * 128 * f                         # lse/delta (LANES)
    b += 2 * blk_k * D * f                           # dk/dv accumulators
    return (fwd + b) / 1024.0


def _timed_scan(fn, q, k, v, iters):
    """Time ``iters`` executions inside ONE dispatched lax.scan, so the
    per-dispatch host cost stays out of a kernel-sized number. The scan
    carry threads a tiny data
    dependency through q so XLA cannot hoist the loop-invariant body out
    of the loop. Returns ms per iteration."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def body(c, _):
        out = fn(q + c, k, v)
        leaf = out[0] if isinstance(out, (tuple, list)) else out
        return (leaf.ravel()[0] * 1e-20).astype(q.dtype), None

    @jax.jit
    def many():
        c, _ = lax.scan(body, jnp.zeros((), q.dtype), None, length=iters)
        return c

    many().block_until_ready()  # compile + warm
    t0 = time.perf_counter()
    many().block_until_ready()
    return (time.perf_counter() - t0) / iters * 1e3


def run_config(S, blk_q, blk_k, *, B=4, H=8, D=64, dtype="bfloat16",
               causal=False, dropout=0.0, steps=None, interpret=False):
    """Compile + parity-check + time one (S, blk_q, blk_k) config.
    ``steps`` overrides the scan-timing iteration count. Returns the
    JSON row dict (fwd_ms/fwdbwd_ms from the device-side scan,
    dispatch_ms = single-dispatch wall time); never raises."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.attention_ops import _dense_attention
    from paddle_tpu.ops.pallas import flash_attention as fa

    row = {"seq_len": S, "blk_q": blk_q, "blk_k": blk_k, "dtype": dtype,
           "batch": B, "heads": H, "head_dim": D, "causal": causal,
           "dropout": dropout,
           "vmem_kb_est": round(_vmem_kb_estimate(blk_q, blk_k, D, True), 1)}
    if S % blk_q or S % blk_k:
        row["ragged"] = True  # boundary blocks masked in-kernel
    # the custom-vjp backward kernels are traced when the grad is built,
    # AFTER the wrapped forward returns — so the interpret/block
    # overrides must span the whole computation, not just the fwd call
    ictx = fa.interpret_guard() if interpret else contextlib.nullcontext()
    try:
        with ictx, fa.block_override(blk_q, blk_k):
            rng = np.random.RandomState(0)
            jdt = jnp.dtype(dtype)
            q, k, v = (jnp.asarray(rng.randn(B, H, S, D) * 0.3, jdt)
                       for _ in range(3))
            scale = 1.0 / np.sqrt(D)
            seed = jnp.asarray([1234], jnp.int32)

            def flash(q, k, v):
                return fa.flash_attention(q, k, v, scale, causal,
                                          dropout_rate=dropout,
                                          dropout_seed=seed)

            def loss(q, k, v):
                return jnp.sum(flash(q, k, v).astype(jnp.float32) ** 2)

            fwd = jax.jit(flash)
            grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

            # --- compile + numerics ---------------------------------
            o = np.asarray(fwd(q, k, v), np.float32)
            dq, dk, dv = (np.asarray(t, np.float32)
                          for t in grad(q, k, v))

            if dropout == 0.0:
                o_ref = np.asarray(
                    _dense_attention(q, k, v, scale, causal), np.float32)

                def loss_ref(q, k, v):
                    return jnp.sum(_dense_attention(
                        q, k, v, scale, causal) ** 2)

                rq, rk, rv = (np.asarray(t, np.float32) for t in
                              jax.jit(jax.grad(loss_ref,
                                               argnums=(0, 1, 2)))(q, k, v))
                scale_o = max(1.0, float(np.abs(o_ref).max()))
                row["max_err_fwd"] = float(np.abs(o - o_ref).max()
                                           / scale_o)
                for nm, a, b in (("dq", dq, rq), ("dk", dk, rk),
                                 ("dv", dv, rv)):
                    s = max(1.0, float(np.abs(b).max()))
                    row[f"max_err_{nm}"] = float(np.abs(a - b).max() / s)
                # bf16 inputs, f32 accumulation: 2e-2 relative headroom
                tol = 2e-2 if jdt == jnp.bfloat16 else 2e-3
                ok = all(row[f"max_err_{n}"] < tol
                         for n in ("fwd", "dq", "dk", "dv"))
            else:
                # dropout parity has no closed-form twin on-chip; the
                # checks are determinism (same seed → identical bits)
                # and finite grads
                o2 = np.asarray(fwd(q, k, v), np.float32)
                row["dropout_deterministic"] = bool((o == o2).all())
                ok = (row["dropout_deterministic"]
                      and all(np.isfinite(t).all()
                              for t in (o, dq, dk, dv)))

            # --- timing (device-side scan: one dispatch, many iters) --
            iters = steps or (2 if interpret else 20)
            row["fwd_ms"] = round(_timed_scan(flash, q, k, v, iters), 3)
            row["fwdbwd_ms"] = round(_timed_scan(
                jax.grad(loss, argnums=(0, 1, 2)), q, k, v, iters), 3)
            # single-dispatch wall time, host overhead included
            t0 = time.perf_counter()
            fwd(q, k, v).block_until_ready()
            row["dispatch_ms"] = round((time.perf_counter() - t0) * 1e3, 3)
            # 4·B·H·S²·D MACs fwd (QKᵀ + PV) → 2 flops/MAC
            flops = 4 * B * H * S * S * D * 2 * (0.5 if causal else 1.0)
            row["tflops_fwd"] = round(flops / (row["fwd_ms"] * 1e-3) / 1e12,
                                      2)
            row["status"] = "ok" if ok else "parity_fail"
    except Exception as e:  # compile errors are DATA here, not crashes
        row["status"] = "compile_error"
        row["error"] = repr(e)[:400]
        row["traceback_tail"] = traceback.format_exc()[-600:]
    return row


def sweep_plan():
    """The full config list, as (S, bq, bk, causal, dropout) tuples."""
    plan = []
    # 128/256 first: the headline bench shape (bert seq_len=128, D=64)
    for S in (128, 256, 512, 1024, 2048):
        for bq in (128, 256, 512):
            for bk in (128, 256, 512):
                if bq > S or bk > S:
                    continue
                plan.append((S, bq, bk, False, 0.0))
    # causal + dropout + ragged legs on the default block config
    S, bq, bk = 512, 128, 128
    plan.append((S, bq, bk, True, 0.0))
    plan.append((S, bq, bk, False, 0.1))
    # ragged boundary block (S not a multiple of the block)
    plan.append((S - S // 4 - 3, bq, bk, False, 0.0))
    return plan


def sweep(emit=print):
    """The full sweep on the chip, one emitted JSON row per config."""
    rows = []
    for (S, bq, bk, causal, dropout) in sweep_plan():
        r = run_config(S, bq, bk, causal=causal, dropout=dropout)
        rows.append(r)
        emit(json.dumps(r))
    return rows


def best_blocks(rows):
    """The fastest (blk_q, blk_k) per "seq_len:head_dim" among ``rows``
    (training criterion: fwd+bwd ms; clean non-causal/no-dropout/
    non-ragged rows only). Reported by `summarize`; the kernel's block
    choice does not read it — whoever measures (ROADMAP S6) commits the
    table they trust into the package."""
    best = {}
    for r in rows:
        if r.get("status") != "ok" or r.get("causal") \
                or r.get("dropout") or r.get("ragged"):
            continue
        if "fwdbwd_ms" not in r:
            continue
        key = (int(r["seq_len"]), int(r.get("head_dim", 64)))
        cur = best.get(key)
        if cur is None or r["fwdbwd_ms"] < cur["fwdbwd_ms"]:
            best[key] = r
    return {f"{s}:{d}": [int(r["blk_q"]), int(r["blk_k"])]
            for (s, d), r in sorted(best.items())}


def summarize(rows, backend):
    ok = [r for r in rows if r.get("status") == "ok"]
    fails = [r for r in rows if r.get("status") in ("parity_fail",
                                                    "compile_error")]
    best = max(ok, key=lambda r: r.get("tflops_fwd", 0.0), default=None)
    out = {"metric": "flash_attention_best_tflops_fwd",
           "value": best["tflops_fwd"] if best else 0.0, "unit": "TFLOP/s",
           "vs_baseline": 1.0, "configs_ok": len(ok),
           "configs_failed": len(fails), "backend": backend}
    if best:
        out["best_config"] = {k: best[k] for k in
                              ("seq_len", "blk_q", "blk_k", "fwd_ms",
                               "fwdbwd_ms")}
        out["best_blocks"] = best_blocks(ok)
    if fails:
        out["first_failure"] = {k: fails[0].get(k) for k in
                                ("seq_len", "blk_q", "blk_k", "status",
                                 "error")}
    return out


def main():
    from tools.device_peaks import require_tpu
    chip = require_tpu("tools.flash_smoke")
    print(json.dumps(summarize(sweep(), chip.platform)))


if __name__ == "__main__":
    main()
