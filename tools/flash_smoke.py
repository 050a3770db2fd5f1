"""Flash-attention hardware sweep: compile the Pallas kernels via Mosaic
(NO interpret mode), check on-chip parity against dense attention, and
sweep block sizes over the shapes the benchmark's cells run — one JSON
row per configuration.

Everything that can fail on Mosaic contact — scratch shapes, SMEM scalar
handling, dimension_semantics, VMEM budgets — is exercised here in one
command (the compile alone is also asked of the chip's compiler, without
a chip, in tests/test_chip_compile.py). Reference counterpart:
operators/fused/multihead_matmul_op.cu is the reference's fused fast
path; operators/benchmark/op_tester.cc is its measure-don't-assert
harness.

Usage (needs a TPU; exits non-zero without one):
    python -m tools.flash_smoke [--shape NAME ...] [--out FILE]

Per-config JSON row fields: shape (`SHAPES`' name, if any), batch, heads,
seq_len, head_dim, v_dim, dtype, causal, window, bias, dropout, blk_q,
blk_k, fwd_ms, dkv_ms, dq_ms (each kernel alone), fwdbwd_ms (the grad:
all three), tflops_fwd, vmem_kb_est (the kernels' own estimate,
`flash_attention._working_set`, the largest of the three), grid_steps
and kv_blocks (the forward kernel's, a head), max_err_fwd,
max_err_dq/dk/dv (against the dense computation with the SAME keep mask
under dropout), status ('ok' | 'parity_fail' | 'compile_error'), error.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import json
import time
import traceback

import numpy as np

# The attention calls of the benchmark's cells (PERF.md §4): the padded
# BERT phase-2 cell, Phi's causal and window layers, Qwen's wide head.
SHAPES = {
    "bert_s512_pad": dict(B=32, H=12, S=512, D=64, bias=True, dropout=0.1),
    "phi_causal": dict(B=1, H=40, S=4096, D=64, Dv=128, causal=True),
    "phi_w512": dict(B=1, H=40, S=4096, D=64, Dv=128, window=512),
    "qwen_d256": dict(B=1, H=16, S=4096, D=256, causal=True),
}


def _timed_scan(fn, q, k, v, iters):
    """Time ``iters`` executions inside ONE dispatched lax.scan, so the
    per-dispatch host cost stays out of a kernel-sized number. The scan
    carry threads a tiny data dependency through q so XLA cannot hoist
    the loop-invariant body out of the loop. Returns ms per iteration."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def body(c, _):
        # EVERY output feeds the carry: a kernel none of whose outputs is
        # read is dead code to XLA (the sweep of PR 33 timed the grad
        # without its dK/dV kernel this way: dQ alone was read)
        first = sum(leaf.ravel()[0].astype(jnp.float32)
                    for leaf in jax.tree_util.tree_leaves(fn(q + c, k, v)))
        return (first * 1e-20).astype(q.dtype), None

    @jax.jit
    def many():
        c, _ = lax.scan(body, jnp.zeros((), q.dtype), None, length=iters)
        return c

    many().block_until_ready()  # compile + warm
    t0 = time.perf_counter()
    many().block_until_ready()
    return (time.perf_counter() - t0) / iters * 1e3


@functools.lru_cache(maxsize=1)
def _problem(B, H, S, D, Dv, dtype, causal, window, bias, dropout):
    """Inputs of one shape and what dense attention makes of them
    (output and the three gradients of sum(o^2), f32 numpy): computed
    once a shape, a head at a time under `jax.checkpoint`, so one head's
    S x S scores are all that is live at s4096. Under dropout the dense
    side multiplies by the kernels' own keep mask (`_keep_mask` is plain
    integer arithmetic on absolute positions)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import flash_attention as fa
    rng = np.random.RandomState(0)
    jdt = jnp.dtype(dtype)
    q, k = (jnp.asarray(rng.randn(B, H, S, D) * 0.3, jdt) for _ in range(2))
    v = jnp.asarray(rng.randn(B, H, S, Dv) * 0.3, jdt)
    pad = None
    if bias:  # key-padding form: lengths uniform in S/2..S, -10000 past
        lengths = rng.randint(S // 2, S + 1, size=(B,))
        pad = jnp.asarray(np.where(np.arange(S)[None, :] < lengths[:, None],
                                   0.0, -10000.0), jnp.float32)
    seed = jnp.asarray([1234], jnp.int32)
    scale = 1.0 / np.sqrt(D)

    @jax.checkpoint
    def head(bh, qh, kh, vh):
        s = jnp.einsum("qd,kd->qk", qh, kh,
                       preferred_element_type=jnp.float32) * scale
        if bias:
            s = s + pad[bh // H][None, :]
        if causal or window:
            rows, cols = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
            seen = rows >= cols
            if window:
                seen = seen & (cols > rows - window)
            s = jnp.where(seen, s, fa.NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        if dropout:
            keep = fa._keep_mask(seed[0], bh, 0, 0, S, S, dropout)
            p = p * keep.astype(p.dtype) / (1.0 - dropout)
        return jnp.dot(p.astype(vh.dtype), vh,
                       preferred_element_type=jnp.float32)

    def loss(q, k, v):
        o = jax.lax.map(lambda a: head(*a), (
            jnp.arange(B * H, dtype=jnp.int32), q.reshape(B * H, S, D),
            k.reshape(B * H, S, D), v.reshape(B * H, S, Dv)))
        return jnp.sum(o ** 2), o.reshape(B, H, S, Dv)

    (_, o), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    want = tuple(np.asarray(t, np.float32) for t in (o, *grads))
    return q, k, v, pad, seed, want


def run_config(S, blk_q, blk_k, *, B=4, H=8, D=64, Dv=None,
               dtype="bfloat16", causal=False, window=0, bias=False,
               dropout=0.0, steps=None, interpret=False, shape=None):
    """Compile + parity-check + time one (shape, blk_q, blk_k) config:
    all three kernels at that pair. ``steps`` overrides the scan-timing
    iteration count. Returns the JSON row dict; never raises."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import flash_attention as fa

    Dv = Dv or D
    mask = fa.Mask(causal, window)
    blocks = (min(S, blk_q), min(S, blk_k))
    row = {"shape": shape, "batch": B, "heads": H, "seq_len": S,
           "head_dim": D, "v_dim": Dv, "dtype": dtype, "causal": causal,
           "window": window, "bias": bias, "dropout": dropout,
           "blk_q": blk_q, "blk_k": blk_k,
           "vmem_kb_est": round(max(
               fa._working_set(kern, *blocks, D, Dv,
                               jnp.dtype(dtype).itemsize, bias)
               for kern in fa.KERNELS) / 1024.0, 1),
           "grid_steps": fa.grid_steps(S, S, *blocks, mask),
           "kv_blocks": fa.visited_blocks(S, S, *blocks, mask)}
    if S % blk_q or S % blk_k:
        row["ragged"] = True  # boundary blocks masked in-kernel
    # the custom-vjp backward kernels are traced when the grad is built,
    # AFTER the wrapped forward returns — so the interpret/block
    # overrides must span the whole computation, not just the fwd call
    ictx = fa.interpret_guard() if interpret else contextlib.nullcontext()
    try:
        with ictx, fa.block_override(blk_q, blk_k):
            q, k, v, pad, seed, want = _problem(
                B, H, S, D, Dv, dtype, causal, window, bias, dropout)
            scale = 1.0 / np.sqrt(D)
            biased = mask._replace(bias=pad)

            def flash(q, k, v):
                return fa.flash_attention(q, k, v, scale, biased,
                                          dropout_rate=dropout,
                                          dropout_seed=seed)

            def loss(q, k, v):
                o = flash(q, k, v).astype(jnp.float32)
                return jnp.sum(o ** 2), o

            # --- compile + numerics ---------------------------------
            (_, o), grads = jax.jit(jax.value_and_grad(
                loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
            for nm, got, ref in zip(("fwd", "dq", "dk", "dv"),
                                    (o, *grads), want):
                unit = max(1.0, float(np.abs(ref).max()))
                row[f"max_err_{nm}"] = float(
                    np.abs(np.asarray(got, np.float32) - ref).max() / unit)
            # bf16 inputs, f32 accumulation: 2e-2 relative headroom
            tol = 2e-2 if dtype == "bfloat16" else 2e-3
            ok = all(row[f"max_err_{n}"] < tol
                     for n in ("fwd", "dq", "dk", "dv"))

            # --- timing (device-side scan: one dispatch, many iters) --
            iters = steps or (2 if interpret else 20)
            _, lse = fa._pallas_fwd(q, k, v, seed, scale, biased, *blocks,
                                    dropout)
            g = (2.0 * o).astype(q.dtype)

            def backward(kernel):
                def run(q, k, v):
                    return kernel(
                        fa._bwd_operands(q, k, v, o.astype(q.dtype),
                                         lse[:, :, 0], g), seed,
                        H, scale, biased, *blocks, dropout)
                return run

            for name, fn in (
                    ("fwd_ms", flash),
                    ("dkv_ms", backward(fa._pallas_bwd_dkv)),
                    ("dq_ms", backward(fa._pallas_bwd_dq)),
                    ("fwdbwd_ms", jax.grad(lambda *a: loss(*a)[0],
                                           argnums=(0, 1, 2)))):
                row[name] = round(_timed_scan(fn, q, k, v, iters), 3)
            # 2·B·H·S²·(D + Dv) MACs fwd (QKᵀ + PV) over the keys the
            # mask keeps → 2 flops/MAC
            kept = S * S
            if window:
                kept = sum(min(t + 1, window) for t in range(S))
            elif causal:
                kept = S * (S + 1) // 2
            flops = 2 * B * H * kept * (D + Dv)
            row["tflops_fwd"] = round(flops / (row["fwd_ms"] * 1e-3) / 1e12,
                                      2)
            row["status"] = "ok" if ok else "parity_fail"
    except Exception as e:  # compile errors are DATA here, not crashes
        row["status"] = "compile_error"
        row["error"] = repr(e)[:400]
        row["traceback_tail"] = traceback.format_exc()[-600:]
    return row


def sweep_plan(shapes=None):
    """The full config list, as `run_config` keyword dicts: every
    candidate block pair of the kernels' own lists on each of `SHAPES`
    (a pair longer than the sequence is the same kernel as the sequence
    itself: once), then a ragged boundary leg at the smallest pair."""
    from paddle_tpu.ops.pallas import flash_attention as fa
    plan = []
    for name in shapes or SHAPES:
        cfg = SHAPES[name]
        pairs = sorted({(min(bq, cfg["S"]), min(bk, cfg["S"]))
                        for bq in fa.BLOCK_Q_CANDIDATES
                        for bk in fa.BLOCK_K_CANDIDATES})
        plan += [dict(cfg, blk_q=bq, blk_k=bk, shape=name)
                 for bq, bk in pairs]
    if not shapes:
        plan.append(dict(B=4, H=8, S=381, D=64, blk_q=128, blk_k=128))
    return plan


def sweep(shapes=None, emit=print):
    """The sweep on the chip, one emitted JSON row per config."""
    rows = []
    for cfg in sweep_plan(shapes):
        r = run_config(cfg.pop("S"), cfg.pop("blk_q"), cfg.pop("blk_k"),
                       steps=10, **cfg)
        rows.append(r)
        emit(json.dumps(r))
    return rows


SHAPE_KEYS = ("batch", "heads", "seq_len", "head_dim", "v_dim", "causal",
              "window", "bias", "dropout")


def best_blocks(rows):
    """The fastest (blk_q, blk_k) a shape among ``rows`` (training
    criterion: fwd+bwd ms), keyed by the WHOLE shape: batch, heads,
    length, head widths, mask and dropout, as "b:h:s:d:dv:causal:window:
    bias:dropout". Reported by `summarize`; the kernels' block choice
    (`flash_attention._block_sizes`) does not read it: it is a rule of
    the shape, fitted to such a sweep (PERF.md §6, PR 33)."""
    best = {}
    for r in rows:
        if r.get("status") != "ok" or "fwdbwd_ms" not in r:
            continue
        key = ":".join(str(int(r.get(k, 0)) if k != "dropout"
                           else r.get(k, 0.0)) for k in SHAPE_KEYS)
        cur = best.get(key)
        if cur is None or r["fwdbwd_ms"] < cur["fwdbwd_ms"]:
            best[key] = r
    return {key: [int(r["blk_q"]), int(r["blk_k"])]
            for key, r in sorted(best.items())}


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


def main(argv=None):
    from tools.device_peaks import require_tpu
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", action="append", choices=sorted(SHAPES),
                    help="sweep these shapes only (default: all + ragged)")
    ap.add_argument("--out", help="also append each row to this file")
    args = ap.parse_args(argv)
    chip = require_tpu("tools.flash_smoke")

    def emit(line):
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")

    emit(json.dumps(summarize(sweep(args.shape, emit), chip.platform)))


if __name__ == "__main__":
    main()
