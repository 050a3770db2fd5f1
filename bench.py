#!/usr/bin/env python
"""Benchmark entry point — prints ONE JSON line:
{"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Headline config (BASELINE.md, the default): BERT-base MLM train step,
samples/sec/chip, through the full fluid front end (Program → jitted XLA
step with donation, Pallas flash attention). MFU is reported against v5e
bf16 peak. Other modes:

    python bench.py mnist       MLP smoke bench
    python bench.py resnet      ResNet-50 train step (BASELINE row 1)
    python bench.py allreduce   Fleet DP step time, transformer-big WMT
"""
import json
import os
import subprocess
import sys
import time


def _pin_host_threads(n=8):
    """Fix BLAS/OMP pools so CPU trend rows are comparable across
    sessions (round-3 drift 5.19 -> 4.61 samples/s had no in-repo
    explanation; ambient thread-pool sizing was the suspect). MUST run
    before numpy loads OpenBLAS/MKL — the pools size themselves at
    library load. Explicit env set by the caller wins."""
    for var in ("OMP_NUM_THREADS", "OPENBLAS_NUM_THREADS",
                "MKL_NUM_THREADS", "NUMEXPR_NUM_THREADS"):
        os.environ.setdefault(var, str(n))


_pin_host_threads()

import numpy as np  # noqa: E402  (after the thread pinning, by design)

from tools.device_peaks import (bf16_peak_flops, device_stamp,  # noqa: E402
                                require_tpu)


LAST_COMPILE_S = None  # wall time of the last harness compile+warm call
# (a SECOND invocation in the same checkout loads the persisted
# executable and shows compile_s collapsing)


def _timed_steps(exe, main, feed, fetch_list, steps, warmup, mesh=None):
    """Shared timing harness: `steps` optimizer steps execute as ONE
    dispatched lax.scan (exe.run n_steps) — per-dispatch host overhead
    amortizes to a single dispatch per window. The
    warmup call uses the same n_steps so the scanned executable is
    compiled exactly once. Feeds are immutable here, so the device-side
    feed cache skips the per-step device_put."""
    global LAST_COMPILE_S
    from paddle_tpu.fluid import core as _core
    _core.set_flag("FLAGS_feed_device_cache", True)
    if os.environ.get("PADDLE_TPU_BENCH_LOOP"):
        # per-dispatch comparison mode (measures host+wire overhead too)
        return _timed_steps_loop(exe, main, feed, fetch_list, steps,
                                 warmup, mesh=mesh)
    del warmup  # the compile run below IS the warmup
    tc = time.perf_counter()
    exe.run(main, feed=feed, fetch_list=fetch_list, mesh=mesh,
            return_numpy=False, n_steps=steps)  # compile + warm
    LAST_COMPILE_S = round(time.perf_counter() - tc, 2)
    t0 = time.perf_counter()
    out = exe.run(main, feed=feed, fetch_list=fetch_list, mesh=mesh,
                  return_numpy=False, n_steps=steps)
    _ = float(np.asarray(out[0].array).ravel()[-1])  # sync
    return time.perf_counter() - t0


LAST_FETCHES = None  # final-step fetch values of the last timed loop


def _timed_steps_loop(exe, main, feed, fetch_list, steps, warmup,
                      mesh=None):
    """Per-step dispatch variant for MULTI-PROCESS benches whose sync
    plane barriers every step (the PS plane lock-steps subprocess
    trainers by run count — a scanned window would change trainer 0's
    barrier count and deadlock the plane)."""
    global LAST_COMPILE_S, LAST_FETCHES
    from paddle_tpu.fluid import core as _core
    _core.set_flag("FLAGS_feed_device_cache", True)
    for i in range(warmup):
        tc = time.perf_counter()
        exe.run(main, feed=feed, fetch_list=fetch_list, mesh=mesh,
                return_numpy=False)
        if i == 0:  # first warmup call is the compile
            LAST_COMPILE_S = round(time.perf_counter() - tc, 2)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = exe.run(main, feed=feed, fetch_list=fetch_list, mesh=mesh,
                      return_numpy=False)
    _ = float(np.asarray(out[0].array).ravel()[0])  # sync
    LAST_FETCHES = out
    return time.perf_counter() - t0


def bench_mnist_mlp(batch=256, steps=60, warmup=10):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import core

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.data("img", shape=[784], dtype="float32")
        label = fluid.data("label", shape=[1], dtype="int64")
        h = fluid.layers.fc(img, 1024, act="relu")
        h = fluid.layers.fc(h, 1024, act="relu")
        pred = fluid.layers.fc(h, 10, act="softmax")
        loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, label))
        fluid.optimizer.Momentum(0.01, momentum=0.9).minimize(loss)
    exe = fluid.Executor()
    scope = core.Scope()
    rng = np.random.RandomState(0)
    X = rng.rand(batch, 784).astype("float32")
    Y = rng.randint(0, 10, (batch, 1)).astype("int64")
    with fluid.scope_guard(scope):
        exe.run(startup)
        dt = _timed_steps(exe, main, {"img": X, "label": Y}, [loss],
                          steps, warmup)
    return {"metric": "mnist_mlp_samples_per_sec",
            "value": round(batch * steps / dt, 1), "unit": "samples/s",
            "vs_baseline": 1.0}


def _realdata_pair(build_fn, batches, k, warmup=2):
    """Real-data step windows (ISSUE 2): time one full pass over
    ``batches`` (all DISTINCT) two ways —

      loop           one exe.run dispatch per batch (per-step host,
                     dispatch and upload costs paid N times)
      scan_realdata  DataLoader.window(k) stacks K batches + device-
                     prefetches the next window while this one computes;
                     exe.run(n_steps=k) scans the K slices in ONE
                     dispatch per window

    Both lanes pull from the same loader protocol and run the same
    batch sequence from a fresh program/scope. Returns a dict with both
    throughput numbers plus a window-of-K vs K-sequential-steps loss
    parity check (fresh programs, same seed — the contract the fast
    tier enforces in tests/test_window_executor.py)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import core as _core
    from paddle_tpu.fluid.reader import DataLoader

    n = len(batches)

    def loader_of():
        dl = DataLoader.from_generator(capacity=4)
        dl.set_batch_generator(lambda: iter(batches))
        return dl

    # ---- loop lane: one dispatch per distinct batch
    main, startup, fetch_list = build_fn()
    exe = fluid.Executor()
    scope = _core.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        # warm through the SAME production path the timed loop uses:
        # the first call compiles against uncommitted startup state, the
        # second against the committed step outputs — both signatures
        # must be warm or a recompile lands inside the clock
        for b, _ in zip(loader_of(), range(max(1, warmup))):
            exe.run(main, feed=b, fetch_list=fetch_list,
                    return_numpy=False)
        t0 = time.perf_counter()
        for b in loader_of():
            out = exe.run(main, feed=b, fetch_list=fetch_list,
                          return_numpy=False)
        _ = float(np.asarray(out[0].array).ravel()[-1])  # sync
        loop_dt = time.perf_counter() - t0
    loop_mode = exe._last_run_mode

    # ---- scan lane: one dispatch per K-batch window
    main, startup, fetch_list = build_fn()
    exe = fluid.Executor()
    scope = _core.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        for w, _ in zip(loader_of().window(k, drop_last=True),
                        range(max(1, warmup))):
            exe.run(main, feed=w, fetch_list=fetch_list,
                    return_numpy=False, n_steps=k)
        t0 = time.perf_counter()
        for w in loader_of().window(k, drop_last=True):
            out = exe.run(main, feed=w, fetch_list=fetch_list,
                          return_numpy=False, n_steps=k)
        _ = float(np.asarray(out[0].array).ravel()[-1])  # sync
        scan_dt = time.perf_counter() - t0
    scan_mode = exe._last_run_mode
    wfeed = {name: np.stack([np.asarray(b[name]) for b in batches[:k]])
             for name in batches[0]}

    # ---- parity: window-of-K losses == K sequential steps
    def first_losses(windowed):
        main, startup, fetch_list = build_fn()
        exe = fluid.Executor()
        scope = _core.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            if windowed:
                (l,) = exe.run(main, feed=wfeed,
                               fetch_list=fetch_list[:1], n_steps=k)
                return np.asarray(l).ravel()
            return np.asarray([
                float(np.asarray(exe.run(main, feed=b,
                                         fetch_list=fetch_list[:1])[0]
                                 ).ravel()[0])
                for b in batches[:k]])

    diff = float(np.max(np.abs(first_losses(True) - first_losses(False))))
    return {"loop_dt": loop_dt, "scan_dt": scan_dt,
            "loop_steps": n, "scan_steps": (n // k) * k,
            "loop_mode": loop_mode, "scan_mode": scan_mode,
            "parity_max_diff": diff, "parity_ok": diff < 1e-4}


def bench_mnist_realdata(batch=64, hidden=256, n_batches=64, k=8):
    """MNIST-shaped MLP trained on DISTINCT batches: the honest
    training-loop number (the headline mnist lane reuses ONE batch, so
    its scan window measures dispatch amortization with an asterisk).
    Model is sized so per-step compute doesn't drown the per-dispatch
    overhead this lane exists to measure."""
    import paddle_tpu.fluid as fluid

    def build():
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 5
        with fluid.program_guard(main, startup):
            img = fluid.data("img", shape=[784], dtype="float32")
            label = fluid.data("label", shape=[1], dtype="int64")
            h = fluid.layers.fc(img, hidden, act="relu")
            h = fluid.layers.fc(h, hidden, act="relu")
            pred = fluid.layers.fc(h, 10, act="softmax")
            loss = fluid.layers.mean(
                fluid.layers.cross_entropy(pred, label))
            fluid.optimizer.Momentum(0.01, momentum=0.9).minimize(loss)
        return main, startup, [loss]

    rng = np.random.RandomState(0)
    batches = [{"img": rng.rand(batch, 784).astype("float32"),
                "label": rng.randint(0, 10, (batch, 1)).astype("int64")}
               for _ in range(n_batches)]
    r = _realdata_pair(build, batches, k)
    return {"metric": "mnist_mlp_realdata_samples_per_sec",
            "value": round(batch * r["scan_steps"] / r["scan_dt"], 1),
            "unit": "samples/s", "vs_baseline": 1.0,
            "mode": "scan_realdata", "window": k, "batch": batch,
            "hidden": hidden, "distinct_batches": n_batches,
            "loop_samples_per_sec":
                round(batch * r["loop_steps"] / r["loop_dt"], 1),
            "speedup_vs_loop":
                round((batch * r["scan_steps"] / r["scan_dt"])
                      / (batch * r["loop_steps"] / r["loop_dt"]), 3),
            "executor_mode": r["scan_mode"],
            "parity_ok": r["parity_ok"],
            "parity_max_diff": r["parity_max_diff"]}


def bench_mnist_realdata_guard(batch=64, hidden=256, n_batches=64, k=8,
                               repeats=3):
    """Paired guard-off vs guard-on lanes for the windowed
    mnist_realdata shape (ISSUE 5 acceptance: fused-guard overhead ≤ 2%
    with action=skip). Both lanes run the IDENTICAL scan window path
    (DataLoader.window(k) → one dispatch per window); the guard-on lane
    sets FLAGS_check_nan_inf=1, FLAGS_nan_inf_action=skip — the per-step
    health reduction + bad-step select fused into the scan. Best-of-
    ``repeats`` per lane (this 1-core box jitters ±10-15%); a first-
    window loss parity check confirms the guard changes nothing on
    clean data."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import core as _core
    from paddle_tpu.fluid.reader import DataLoader

    if n_batches < k:
        raise ValueError(
            f"mnist_guard needs n_batches >= window k "
            f"({n_batches} < {k}): drop_last windows would yield "
            f"nothing to time")

    def build():
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 5
        with fluid.program_guard(main, startup):
            img = fluid.data("img", shape=[784], dtype="float32")
            label = fluid.data("label", shape=[1], dtype="int64")
            h = fluid.layers.fc(img, hidden, act="relu")
            h = fluid.layers.fc(h, hidden, act="relu")
            pred = fluid.layers.fc(h, 10, act="softmax")
            loss = fluid.layers.mean(
                fluid.layers.cross_entropy(pred, label))
            fluid.optimizer.Momentum(0.01, momentum=0.9).minimize(loss)
        return main, startup, [loss]

    rng = np.random.RandomState(0)
    batches = [{"img": rng.rand(batch, 784).astype("float32"),
                "label": rng.randint(0, 10, (batch, 1)).astype("int64")}
               for _ in range(n_batches)]

    def loader_of():
        dl = DataLoader.from_generator(capacity=4)
        dl.set_batch_generator(lambda: iter(batches))
        return dl

    def scan_pass():
        """One timed full pass over the windowed loader (fresh program/
        scope; both warmup signatures warmed). Returns (dt, first-window
        losses)."""
        main, startup, fetch_list = build()
        exe = fluid.Executor()
        scope = _core.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            for w, _ in zip(loader_of().window(k, drop_last=True),
                            range(2)):
                first = exe.run(main, feed=w, fetch_list=fetch_list,
                                return_numpy=False, n_steps=k)
            first_losses = np.asarray(first[0].array).ravel().copy()
            t0 = time.perf_counter()
            for w in loader_of().window(k, drop_last=True):
                out = exe.run(main, feed=w, fetch_list=fetch_list,
                              return_numpy=False, n_steps=k)
            _ = float(np.asarray(out[0].array).ravel()[-1])  # sync
            return time.perf_counter() - t0, first_losses

    def lane():
        best_dt, losses = min((scan_pass() for _ in range(repeats)),
                              key=lambda r: r[0])
        return best_dt, losses

    saved = (_core.globals_["FLAGS_check_nan_inf"],
             _core.globals_["FLAGS_nan_inf_action"])
    try:
        _core.set_flag("FLAGS_check_nan_inf", False)
        off_dt, off_losses = lane()
        _core.set_flag("FLAGS_check_nan_inf", True)
        _core.set_flag("FLAGS_nan_inf_action", "skip")
        on_dt, on_losses = lane()
    finally:
        _core.set_flag("FLAGS_check_nan_inf", saved[0])
        _core.set_flag("FLAGS_nan_inf_action", saved[1])
    steps = (n_batches // k) * k
    off_sps = batch * steps / off_dt
    on_sps = batch * steps / on_dt
    return {"metric": "mnist_realdata_guard_samples_per_sec",
            "value": round(on_sps, 1), "unit": "samples/s",
            "vs_baseline": 1.0, "mode": "scan_realdata", "window": k,
            "batch": batch, "hidden": hidden,
            "guard": "skip", "guard_off_samples_per_sec": round(off_sps, 1),
            "guard_overhead_pct": round((off_sps / on_sps - 1.0) * 100, 2),
            "best_of": repeats,
            "parity_ok": bool(np.array_equal(off_losses, on_losses))}


def bench_wide_deep_realdata(batch=256, n_batches=32, k=8):
    """Wide&Deep CTR on distinct batches. ``with_auc=False`` keeps the
    block fully compiled so the window collapses to one dispatch (the
    with-AUC block is segmented — its islands force the documented
    per-step fallback, which the headline wide_deep lane already
    times)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import wide_deep

    def build():
        main, startup, feeds, loss, _ = wide_deep.build_wide_deep_program(
            num_dense=13, num_slots=26, sparse_dim=int(1e5),
            embedding_dim=16, hidden=(64, 64), lr=1e-3, with_auc=False)
        main.random_seed = startup.random_seed = 5
        return main, startup, [loss]

    nb = wide_deep.ctr_reader(batch, num_dense=13, num_slots=26,
                              sparse_dim=int(1e5), seed=0)
    batches = [nb() for _ in range(n_batches)]
    r = _realdata_pair(build, batches, k)
    return {"metric": "wide_deep_realdata_samples_per_sec",
            "value": round(batch * r["scan_steps"] / r["scan_dt"], 1),
            "unit": "samples/s", "vs_baseline": 1.0,
            "mode": "scan_realdata", "window": k, "batch": batch,
            "distinct_batches": n_batches, "with_auc": False,
            "loop_samples_per_sec":
                round(batch * r["loop_steps"] / r["loop_dt"], 1),
            "speedup_vs_loop":
                round((batch * r["scan_steps"] / r["scan_dt"])
                      / (batch * r["loop_steps"] / r["loop_dt"]), 3),
            "executor_mode": r["scan_mode"],
            "parity_ok": r["parity_ok"],
            "parity_max_diff": r["parity_max_diff"]}


def bench_bert_base(batch=128, seq_len=128, steps=20, warmup=5):
    """BERT-base MLM train step. b128 x s128 is the size PR 21's chip run
    showed to fit one v5e chip (the compiler counts 1.6 GB of arguments
    and 8.5 GB of temporaries). A size that does not fit is an error to
    read, not a batch to halve."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import core
    from paddle_tpu.models import bert

    chip = require_tpu("bench.py bert")
    core.set_flag("FLAGS_use_bf16_matmul", True)  # MXU-native math
    cfg = bert.bert_base_config()
    batch = int(os.environ.get("PADDLE_TPU_BENCH_BATCH", batch))
    seq_len = int(os.environ.get("PADDLE_TPU_BENCH_SEQ", seq_len))
    # PADDLE_TPU_BENCH_RECOMPUTE=1: per-layer activation remat — buys a
    # bigger batch for ~1/3 extra FLOPs
    recompute = os.environ.get("PADDLE_TPU_BENCH_RECOMPUTE") == "1"
    main, startup, feeds, fetches = bert.build_bert_pretrain_program(
        cfg, seq_len=seq_len, dropout=0.0, lr=1e-4, recompute=recompute)
    exe = fluid.Executor()
    scope = core.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        dt = _timed_steps(
            exe, main, bert.synthetic_pretrain_batch(cfg, batch, seq_len),
            fetches, steps, warmup)
    sps = batch * steps / dt
    # 6·N·tokens FLOPs estimate (fwd+bwd), N = transformer params (no embed)
    h, L, f = cfg["hidden"], cfg["layers"], cfg["ffn"]
    n_params = L * (4 * h * h + 2 * h * f)
    flops_per_sample = 6 * n_params * seq_len \
        + 12 * L * seq_len * seq_len * h  # attention scores fwd+bwd
    mfu = sps * flops_per_sample / bf16_peak_flops(chip)
    return {"metric": "bert_base_samples_per_sec_per_chip",
            "value": round(sps, 2), "unit": "samples/s",
            "vs_baseline": 1.0, "mfu_vs_bf16_peak": round(mfu, 4),
            "batch": batch, "seq_len": seq_len}


def bench_resnet50(batch=64, image_size=224, steps=10, warmup=3):
    """ResNet-50 ImageNet train step (BASELINE.md row 1)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import core
    from paddle_tpu.models.resnet import build_resnet_train_program

    chip = require_tpu("bench.py resnet")
    core.set_flag("FLAGS_use_bf16_matmul", True)  # MXU-native convs
    main, startup, feeds, fetches = build_resnet_train_program(
        depth=50, class_dim=1000, image_size=image_size)
    loss = fetches[0]
    rng = np.random.RandomState(0)
    exe = fluid.Executor()
    scope = core.Scope()
    img = rng.rand(batch, 3, image_size, image_size).astype("float32")
    lbl = rng.randint(0, 1000, (batch, 1)).astype("int64")
    with fluid.scope_guard(scope):
        exe.run(startup)
        dt = _timed_steps(exe, main, {"image": img, "label": lbl},
                          [loss], steps, warmup)
    sps = batch * steps / dt
    # ~3.8 GFLOPs fwd per 224x224 sample (scales ~quadratically with
    # resolution); x3 for fwd+bwd
    flops_fwd = 3.8e9 * (image_size / 224.0) ** 2
    mfu = sps * flops_fwd * 3 / bf16_peak_flops(chip)
    return {"metric": "resnet50_samples_per_sec_per_chip",
            "value": round(sps, 2), "unit": "samples/s",
            "vs_baseline": 1.0, "mfu_vs_bf16_peak": round(mfu, 4),
            "batch": batch}


def bench_allreduce_dp(steps=10, warmup=3):
    """Fleet-collective data-parallel step time over the available mesh
    (BASELINE.md: allreduce step-time, Transformer-big WMT config scaled
    to fit). XLA inserts the grad all-reduce over ICI inside the one
    jitted step; this measures the whole DP step including it."""
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import core
    from paddle_tpu.parallel.mesh import build_mesh
    from paddle_tpu.models.transformer import (build_wmt_train_program,
                                               transformer_big_config)

    require_tpu("bench.py allreduce")
    n_dev = len(jax.devices())
    cfg = transformer_big_config()
    cfg.update(src_vocab=4096, trg_vocab=4096, enc_layers=2, dec_layers=2,
               dropout=0.0)
    B, S = 8 * n_dev, 64
    main, startup, feeds, loss = build_wmt_train_program(
        cfg, src_len=S, trg_len=S, lr=1e-4)
    mesh = build_mesh(n_dev) if n_dev > 1 else None
    exe = fluid.Executor()
    scope = core.Scope()
    rng = np.random.RandomState(0)
    sv, tv = cfg["src_vocab"], cfg["trg_vocab"]
    feed = {
        "src_ids": rng.randint(0, sv, (B, S)).astype("int64"),
        "src_mask": np.ones((B, S), "float32"),
        "trg_ids": rng.randint(0, tv, (B, S)).astype("int64"),
        "trg_mask": np.ones((B, S), "float32"),
        "labels": rng.randint(0, tv, (B, S, 1)).astype("int64"),
    }
    with fluid.scope_guard(scope):
        exe.run(startup)
        dt = _timed_steps(exe, main, feed, [loss], steps, warmup,
                          mesh=mesh)
    return {"metric": "fleet_dp_step_ms_transformer_big",
            "value": round(dt / steps * 1e3, 2), "unit": "ms/step",
            "vs_baseline": 1.0, "devices": n_dev, "batch": B}


def bench_wide_deep(batch=4096, steps=20, warmup=5):
    """Wide&Deep CTR train step, samples/sec (BASELINE.md sparse-scale row
    scaled to one chip: dense embeddings + MLP compile into the jitted
    step; the beyond-HBM table path is exercised by the PS tests).

    The AUC metric op stays IN the train program: the segmented executor
    compiles fwd+bwd+update as jitted segments around the stateful auc
    island, instead of de-compiling the whole block (the pre-r6
    interpreter cliff). The row carries compiled_metric: true when that
    path actually served the run."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import core
    from paddle_tpu.models import wide_deep

    require_tpu("bench.py wide_deep")
    main, startup, feeds, loss, auc = wide_deep.build_wide_deep_program(
        num_dense=13, num_slots=26, sparse_dim=int(1e6), embedding_dim=16,
        hidden=(400, 400, 400), lr=1e-3)
    exe = fluid.Executor()
    scope = core.Scope()
    nb = wide_deep.ctr_reader(batch, num_dense=13, num_slots=26,
                              sparse_dim=int(1e6), seed=0)
    feed = nb()
    with fluid.scope_guard(scope):
        exe.run(startup)
        # per-step dispatch: the segmented step runs its islands host-side
        # each step, so the scanned window doesn't apply
        dt = _timed_steps_loop(exe, main, feed, [loss, auc], steps, warmup)
        # streaming AUC after the timed window's final step (no extra
        # training step just to read the metric)
        auc_val = float(np.asarray(LAST_FETCHES[1].array).ravel()[0])
    return {"metric": "wide_deep_ctr_samples_per_sec_per_chip",
            "value": round(batch * steps / dt, 1), "unit": "samples/s",
            "vs_baseline": 1.0, "batch": batch,
            "embedding_params": int(26 * 1e6 * 16 + 26 * 1e6),
            "compiled_metric": exe._last_run_mode == "segmented",
            "executor_mode": exe._last_run_mode,
            "auc": round(auc_val, 4)}


def bench_wide_deep_1b(batch=512, steps=10, warmup=2, n_pservers=2,
                       sparse_dim=int(2.5e6), n_trainers=2,
                       async_staleness=0, window_k=1, metric=None):
    """Wide&Deep CTR with ≥1e9 embedding parameters over the distributed
    PS plane (BASELINE.md sparse-scale row): 26 deep [2.5M, 16] + 26 wide
    [2.5M, 1] per-slot tables, row-sharded across pserver subprocesses as
    init-on-touch lazy tables (fleet_wrapper.h DownpourSparseTable role).
    ``n_trainers`` data-parallel trainers train in lock step through the
    sync plane (trainer 0 in-process, the rest as subprocesses); the row
    reports the SUMMED samples/sec. Includes the RPC pulls.

    Paired data-plane lanes (docs/PS_DATA_PLANE.md): the default lane
    rides the overhauled plane (binary framing, channel pool, parallel
    shard fan-out, lookup dedup); PADDLE_TPU_PS_PICKLE_WIRE=1 restores
    the full LEGACY plane for every client (subprocess trainers inherit
    the env). Same model, same feeds, and every legacy-gated difference
    is numerics-exact, so the two rows' final losses must agree
    bit-for-bit (the recorded parity flag).

    Async-overlap lanes (docs/PS_DATA_PLANE.md "Async overlap"):
    ``async_staleness=k`` pipelines every trainer's comm tail behind
    its next step (FLAGS_async_staleness rides into the subprocess
    trainers via env) and ``window_k`` feeds [K, ...] stacks so the
    window fallback stages sparse prefetch for slice i+1 while slice i
    computes. The async row additionally records overlap EVIDENCE from
    a short profiled epilogue — cat="comm" span seconds concurrent
    with cat="segment" step spans — plus the trainer-side prefetch hit
    rate and the pservers' prefetch-tagged pull counters, because on
    this 1-core box the summed samples/s is scheduler-bound, not
    wire-bound (the PR 4 lesson)."""
    import socket
    import numpy as np

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    os.environ["FLAGS_lazy_sparse_table_threshold"] = "1000000"
    os.environ["FLAGS_async_staleness"] = str(int(async_staleness))
    wire = ("pickle" if os.environ.get("PADDLE_TPU_PS_PICKLE_WIRE") == "1"
            else "binary")
    from tools import wide_deep_ps_worker as W

    def free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        p = s.getsockname()[1]
        s.close()
        return p

    eps = ",".join(f"127.0.0.1:{free_port()}" for _ in range(n_pservers))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.dirname(os.path.abspath(__file__)))
    # the children stay on the CPU: a chip belongs to one process, and
    # that is this one (trainer 0)
    workers = []
    trainer_procs = []
    try:
        import tempfile
        logfiles = []
        for i in range(n_pservers):
            # log to a FILE, not a pipe: an undrained pipe would block a
            # chatty pserver once the 64KB buffer fills mid-bench
            lf = tempfile.NamedTemporaryFile("wb+", prefix=f"ps{i}_",
                                             suffix=".log", delete=False)
            logfiles.append(lf)
            workers.append(subprocess.Popen(
                [sys.executable, "-m", "tools.wide_deep_ps_worker",
                 "pserver", eps, str(i), str(sparse_dim),
                 str(n_trainers)],
                env=env, stdout=lf, stderr=subprocess.STDOUT))
        deadline = time.time() + 180
        for w, lf in zip(workers, logfiles):
            while True:
                lf.flush()
                if b"PSERVER_READY" in open(lf.name, "rb").read():
                    break
                if w.poll() is not None:
                    raise RuntimeError(
                        f"pserver exited rc={w.returncode}: "
                        + open(lf.name, "rb").read()[-1500:].decode(
                            errors="replace"))
                if time.time() > deadline:
                    raise TimeoutError("pserver never became ready: "
                                       + lf.name)
                time.sleep(0.3)

        # trainers 1..N-1 as subprocesses, lock-stepped with trainer 0
        # through the sync barriers (same warmup+steps count)
        trainer_outs, trainer_logs = [], []
        for tid in range(1, n_trainers):
            tf = tempfile.NamedTemporaryFile("r", prefix=f"tr{tid}_",
                                             suffix=".json", delete=False)
            trainer_outs.append(tf.name)
            tl = tempfile.NamedTemporaryFile("wb+", prefix=f"tr{tid}_",
                                             suffix=".log", delete=False)
            trainer_logs.append(tl)
            trainer_procs.append(subprocess.Popen(
                [sys.executable, "-m", "tools.wide_deep_ps_worker",
                 "trainer", eps, str(tid), str(n_trainers),
                 str(sparse_dim), str(batch), str(steps), str(warmup),
                 tf.name, str(window_k)],
                env=env, stdout=tl, stderr=subprocess.STDOUT))
        # startup grace: a trainer that dies before its first barrier
        # would hang trainer 0 in the sync plane (the pserver-side
        # dead-trainer barrier check needs one heartbeat first)
        time.sleep(2.0)
        for p, tl in zip(trainer_procs, trainer_logs):
            if p.poll() is not None:
                raise RuntimeError(
                    f"trainer subprocess died rc={p.returncode}: "
                    + open(tl.name, "rb").read()[-1500:].decode(
                        errors="replace"))

        import paddle_tpu.fluid as fluid
        from paddle_tpu.fluid import async_overlap, core, profiler
        from paddle_tpu.fluid.communicator import drain_async_rounds
        from paddle_tpu.models import wide_deep
        core.set_flag("FLAGS_async_staleness", int(async_staleness))
        main_p, startup, feeds, loss, auc = W.build(sparse_dim)
        t = W.transpile(main_p, startup, eps, trainer_id=0,
                        trainers=n_trainers)
        prog = t.get_trainer_program()
        exe = fluid.Executor()
        scope = core.Scope()
        nb = wide_deep.ctr_reader(batch, num_dense=13, num_slots=26,
                                  sparse_dim=sparse_dim, seed=0)
        evidence = {}
        from paddle_tpu.fluid.ps_rpc import WorkerHeartBeat
        beat = WorkerHeartBeat(eps.split(","), 0, interval=1.0).start()
        try:
            with fluid.scope_guard(scope):
                exe.run(startup)
                if window_k <= 1:
                    feed = nb()
                    dt = _timed_steps_loop(exe, prog, feed, [loss],
                                           steps, warmup)
                else:
                    # [K, ...] stacks of K DISTINCT batches — the
                    # window-fallback shape that staggers sparse
                    # prefetch across the slices
                    assert steps % window_k == 0 \
                        and warmup % window_k == 0
                    batches = [nb() for _ in range(window_k)]
                    feed = {n: np.stack([b[n] for b in batches])
                            for n in batches[0]}
                    global LAST_FETCHES
                    n_warm = warmup // window_k
                    for w in range(n_warm):
                        if w == n_warm - 1:
                            # evidence window: profile the LAST WARMUP
                            # window (it runs the identical production
                            # path) so the timed loop below stays free
                            # of profiling overhead — cat="comm" spans
                            # from the round pipeline / prefetch
                            # threads concurrent with cat="segment"
                            # step spans prove the wire ran behind the
                            # step
                            profiler.start_profiler("CPU")
                        out = exe.run(prog, feed=feed,
                                      fetch_list=[loss],
                                      n_steps=window_k,
                                      return_numpy=False)
                    ev = profiler.snapshot_events()
                    profiler.stop_profiler(profile_path="")
                    t0 = time.perf_counter()
                    for _ in range(steps // window_k):
                        out = exe.run(prog, feed=feed,
                                      fetch_list=[loss],
                                      n_steps=window_k,
                                      return_numpy=False)
                    # in-flight rounds are part of the measured work
                    drain_async_rounds()
                    dt = time.perf_counter() - t0
                    comm_s = sum(e["end"] - e["start"] for e in ev
                                 if e["cat"] == "comm")
                    overlap_s = profiler.concurrent_seconds(
                        "comm", "segment", events=ev)
                    evidence = {
                        "comm_span_s": round(comm_s, 4),
                        "comm_overlap_s": round(overlap_s, 4),
                        "comm_overlap_frac": round(
                            overlap_s / comm_s, 4) if comm_s else 0.0,
                    }
                    plane = async_overlap.active_plane()
                    if plane is not None:
                        s = plane.stats()
                        evidence["prefetch_hit_rate"] = round(
                            s["hit_rate"], 4)
                        evidence["prefetch_stages"] = s["stages"]
                    LAST_FETCHES = out
        finally:
            beat.stop()
        total_sps = batch * steps / dt
        for p, out_path, tl in zip(trainer_procs, trainer_outs,
                                   trainer_logs):
            p.wait(timeout=120)
            if p.returncode != 0:
                raise RuntimeError(
                    f"trainer subprocess rc={p.returncode}: "
                    + open(tl.name, "rb").read()[-1500:].decode(
                        errors="replace"))
            total_sps += json.load(open(out_path))["samples_per_sec"]
        emb_params = 26 * sparse_dim * 16 + 26 * sparse_dim
        final_loss = float(np.asarray(LAST_FETCHES[0].array).ravel()[-1])
        if int(async_staleness) > 0:
            # server-side view of the prefetch traffic (stats RPC)
            try:
                from paddle_tpu.fluid.ps_rpc import VarClient
                pf = [VarClient.of(ep).call("stats").get("prefetch", {})
                      for ep in eps.split(",")]
                evidence["server_prefetch_calls"] = sum(
                    int(p.get("calls", 0)) for p in pf)
                evidence["server_prefetch_rows"] = sum(
                    int(p.get("rows", 0)) for p in pf)
            except Exception:
                pass
        # capacity-tier gauges (docs/PS_DATA_PLANE.md "Capacity tier"):
        # when the pservers run a spill tier, record the aggregated
        # slab stats as the lane's evidence surface before teardown
        try:
            from paddle_tpu.fluid import slab_spill
            from paddle_tpu.fluid.ps_rpc import VarClient
            slabs = [VarClient.of(ep).call("stats").get("slab") or {}
                     for ep in eps.split(",")]
            agg = slab_spill.merge_tier_stats(slabs)
            if agg:
                evidence["slab"] = {
                    k: agg.get(k, 0) for k in (
                        "resident_rows", "spilled_rows",
                        "resident_bytes", "spilled_bytes", "hit_rate",
                        "density_x", "promoted_rows",
                        "clean_evictions", "store_reads")}
        except Exception:
            pass
        return {"metric": metric or "wide_deep_1b_ps_samples_per_sec",
                "value": round(total_sps, 1), "unit": "samples/s",
                "vs_baseline": 1.0, "batch": batch,
                "embedding_params": int(emb_params),
                "pservers": n_pservers, "trainers": n_trainers,
                # wire lane + trainer-0 final loss: the paired
                # binary-vs-pickle rows must agree on this bit-for-bit
                # (framing must never change the numerics; the
                # staleness>0 lane is NOT bit-comparable — bounded-
                # staleness reads are the point)
                "wire": wire, "final_loss": final_loss,
                "async_staleness": int(async_staleness),
                "window_k": int(window_k),
                **evidence,
                # the AUC op rides in-graph: fwd+bwd+update run as
                # compiled jitted segments around the stateful islands
                # (auc + RPC ops) instead of the whole-block interpreter
                "compiled_metric": exe._last_run_mode == "segmented",
                "executor_mode": exe._last_run_mode}
    finally:
        try:
            from paddle_tpu.fluid.ps_rpc import VarClient
            for ep in eps.split(","):
                VarClient.of(ep).stop()
        except Exception:
            pass
        for w in workers + trainer_procs:
            if w.poll() is None:
                w.terminate()
            try:
                w.wait(timeout=10)
            except Exception:
                w.kill()
        # never leak the overlap plane into a later lane of the same
        # bench invocation
        os.environ.pop("FLAGS_async_staleness", None)
        try:
            from paddle_tpu.fluid import async_overlap as _ao
            from paddle_tpu.fluid import communicator as _comm
            from paddle_tpu.fluid import core as _core
            _core.set_flag("FLAGS_async_staleness", 0)
            _ao.reset_plane()
            _comm.reset_round_pipeline()
        except Exception:
            pass


def bench_wide_deep_1b_syncw(batch=512, steps=16, warmup=16,
                             n_pservers=2, sparse_dim=int(2.5e6),
                             n_trainers=2):
    """Windowed SYNC baseline of the async-overlap pair: same [K=8]
    window stacks, same cluster shape, FLAGS_async_staleness=0 (the
    plain send/barrier/recv/fetch tail). Pairs with wide_deep_1b_async
    and wide_deep_1b_ceiling (docs/PS_DATA_PLANE.md "Async overlap")."""
    return bench_wide_deep_1b(
        batch=batch, steps=steps, warmup=warmup, n_pservers=n_pservers,
        sparse_dim=sparse_dim, n_trainers=n_trainers, async_staleness=0,
        window_k=8, metric="wide_deep_1b_ps_syncw_samples_per_sec")


def bench_wide_deep_1b_async(batch=512, steps=16, warmup=16,
                             n_pservers=2, sparse_dim=int(2.5e6),
                             n_trainers=2, staleness=2):
    """Async-overlap lane: FLAGS_async_staleness=2 pipelines every
    trainer's round (push/barrier/pull) behind its next step and the
    window fallback prefetches slice i+1's embedding rows while slice
    i computes. Row carries overlap evidence (comm∩segment span
    seconds from the profiled last window, prefetch hit rate, server
    prefetch counters) because summed samples/s on the 1-core box is
    scheduler-bound (docs/PS_DATA_PLANE.md "Async overlap")."""
    return bench_wide_deep_1b(
        batch=batch, steps=steps, warmup=warmup, n_pservers=n_pservers,
        sparse_dim=sparse_dim, n_trainers=n_trainers,
        async_staleness=staleness, window_k=8,
        metric="wide_deep_1b_ps_async_samples_per_sec")


def bench_wide_deep_geo(batch=256, steps=64, warmup=8, n_pservers=2,
                        sparse_dim=20000, n_trainers=2):
    """Compressed geo WAN lane (docs/PS_DATA_PLANE.md "Compression"):
    the same wide_deep cluster as wide_deep_1b but geo-SGD transpiled
    (local optimizer + delta pushes every 8 steps), under an emulated
    WAN — 50ms injected server-side delay with 10ms jitter on every
    data RPC — with the whole compression stack on: geo deltas ride
    the async RoundPipeline (staleness 2), DGC top-k sparsifies them
    (error feedback in @GEO_OLD), and the wire runs int8 quantized
    frames. Non-lazy tables (geo keeps the optimizer local), so
    sparse_dim stays small. Pairs with wide_deep_geo_sync: plain sync
    mode under the SAME delay — the ratio is the WAN-survivability
    claim. The row carries the dgc/quant compression ratios from the
    in-process trainer."""
    from paddle_tpu.fluid import communicator as _comm
    from paddle_tpu.fluid import ps_rpc as _ps_rpc
    saved = {k: os.environ.get(k) for k in
             ("PADDLE_TPU_PS_RPC_DELAY_MS",
              "PADDLE_TPU_PS_RPC_DELAY_JITTER_MS", "PADDLE_TPU_WD_GEO",
              "FLAGS_dgc", "FLAGS_ps_wire_quant",
              "FLAGS_lazy_sparse_table_threshold")}
    os.environ.update({
        "PADDLE_TPU_PS_RPC_DELAY_MS": "50",
        "PADDLE_TPU_PS_RPC_DELAY_JITTER_MS": "10",
        "PADDLE_TPU_WD_GEO": "1",
        "FLAGS_dgc": "1", "FLAGS_ps_wire_quant": "int8",
        # geo refuses lazy tables; keep the small tables dense-hosted
        "FLAGS_lazy_sparse_table_threshold": str(1 << 26)})
    from paddle_tpu.fluid import core as _core
    _core.set_flag("FLAGS_dgc", True)
    _core.set_flag("FLAGS_ps_wire_quant", "int8")
    _core.set_flag("FLAGS_lazy_sparse_table_threshold", 1 << 26)
    _comm.reset_dgc()
    _ps_rpc.reset_quant_wire_stats()
    try:
        row = bench_wide_deep_1b(
            batch=batch, steps=steps, warmup=warmup,
            n_pservers=n_pservers, sparse_dim=sparse_dim,
            n_trainers=n_trainers, async_staleness=2, window_k=1,
            metric="wide_deep_geo_wan_samples_per_sec")
        dgc = _comm.active_dgc_stats()
        quant = _ps_rpc.quant_wire_stats()
        row.update({
            "mode": "geo+dgc+int8", "rpc_delay_ms": 50,
            "dgc_compression_ratio": dgc.get("compression_ratio"),
            "wire_bytes_raw": quant.get("bytes_raw_total"),
            "wire_bytes_sent": quant.get("bytes_sent_total"),
            "wire_ratio": round(
                quant.get("bytes_raw_total", 0)
                / max(1, quant.get("bytes_sent_total", 1)), 2)})
        return row
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        _core.set_flag("FLAGS_dgc", False)
        _core.set_flag("FLAGS_ps_wire_quant", "")
        _core.set_flag("FLAGS_lazy_sparse_table_threshold", 1 << 26)


def bench_wide_deep_geo_sync(batch=256, steps=8, warmup=2, n_pservers=2,
                             sparse_dim=20000, n_trainers=2):
    """Plain-sync counterpart of wide_deep_geo under the SAME 50ms+
    jitter WAN emulation: every step pays the full send/barrier/recv
    tail plus one delayed row pull per sparse table — which is exactly
    why the step count is small (each step costs seconds). Same model,
    same cluster shape, compression off."""
    saved = {k: os.environ.get(k) for k in
             ("PADDLE_TPU_PS_RPC_DELAY_MS",
              "PADDLE_TPU_PS_RPC_DELAY_JITTER_MS",
              "FLAGS_lazy_sparse_table_threshold")}
    os.environ.update({
        "PADDLE_TPU_PS_RPC_DELAY_MS": "50",
        "PADDLE_TPU_PS_RPC_DELAY_JITTER_MS": "10",
        "FLAGS_lazy_sparse_table_threshold": str(1 << 26)})
    from paddle_tpu.fluid import core as _core
    _core.set_flag("FLAGS_lazy_sparse_table_threshold", 1 << 26)
    try:
        row = bench_wide_deep_1b(
            batch=batch, steps=steps, warmup=warmup,
            n_pservers=n_pservers, sparse_dim=sparse_dim,
            n_trainers=n_trainers, async_staleness=0, window_k=1,
            metric="wide_deep_geo_sync_wan_samples_per_sec")
        row.update({"mode": "sync", "rpc_delay_ms": 50})
        return row
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def bench_wide_deep_spill(batch=256, steps=12, warmup=4, n_pservers=2,
                          sparse_dim=int(2.5e6), n_trainers=2,
                          resident_frac=0.10):
    """Capacity-tier paired lanes (docs/PS_DATA_PLANE.md "Capacity
    tier", ROADMAP item 2): the SAME wide_deep cluster and
    deterministic feed three ways — (a) all-in-RAM oracle, (b) spill
    tier with each table's hot set capped at ~10% of its per-step
    working set (raw rows at rest), (c) the same cap with int8 rows at
    rest. The tier flags reach the pserver subprocesses via env
    (lazy_table_init reads them at startup). Acceptance: (b) trains at
    >50% of (a)'s rate with the final loss BIT-IDENTICAL (raw
    write-back is exact — promotion/eviction churn must not change a
    single bit); (c) stays within the documented int8 at-rest error
    envelope (absmax_row/254 per element per first quantization) and
    holds >=3.5x at-rest row density at dim 16+scale — the slab gauges
    are scraped from the pservers' stats RPC before teardown.

    The repeated-batch feed makes this the LRU worst case: every step
    cycles the whole working set through a hot set 10x smaller, so the
    spill lane pays promotion+write-back for ~90% of its rows every
    step (hit_rate evidence ~= resident fraction). Real CTR traffic is
    zipfian and does strictly better; the clean-backing write elision
    (unmodified promotes evict for free) is what keeps even this
    pathological lane inside the bar."""
    import tempfile

    # per-table working set of the repeated batch ~= `batch` distinct
    # ids (uniform draw over 2.5e6); the hot cap is ~10% of that
    hot_rows = max(16, int(batch * resident_frac))
    lanes = {}
    saved = {k: os.environ.get(k) for k in
             ("FLAGS_ps_slab_spill_dir", "FLAGS_ps_slab_hot_rows",
              "FLAGS_ps_at_rest_quant", "FLAGS_ps_slab_seg_rows")}

    def _restore():
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    try:
        # the ORACLE lane must run tier-off even if the caller's env
        # has the spill flags exported — otherwise the "RAM" baseline
        # also spills and every comparison self-compares
        for k in saved:
            os.environ.pop(k, None)
        lanes["ram"] = bench_wide_deep_1b(
            batch=batch, steps=steps, warmup=warmup,
            n_pservers=n_pservers, sparse_dim=sparse_dim,
            n_trainers=n_trainers,
            metric="wide_deep_spill_ram_samples_per_sec")
        for key, quant in (("spill", ""), ("spill_int8", "int8")):
            spill_dir = tempfile.mkdtemp(prefix=f"pt-wdspill-{key}-")
            os.environ["FLAGS_ps_slab_spill_dir"] = spill_dir
            os.environ["FLAGS_ps_slab_hot_rows"] = str(hot_rows)
            os.environ["FLAGS_ps_at_rest_quant"] = quant
            os.environ["FLAGS_ps_slab_seg_rows"] = str(max(64, batch))
            try:
                lanes[key] = bench_wide_deep_1b(
                    batch=batch, steps=steps, warmup=warmup,
                    n_pservers=n_pservers, sparse_dim=sparse_dim,
                    n_trainers=n_trainers,
                    metric=f"wide_deep_{key}_samples_per_sec")
            finally:
                _restore()
                import shutil
                shutil.rmtree(spill_dir, ignore_errors=True)
    finally:
        _restore()

    ram, spill, spill8 = lanes["ram"], lanes["spill"], lanes["spill_int8"]
    ratio = spill["value"] / max(ram["value"], 1e-9)
    ratio8 = spill8["value"] / max(ram["value"], 1e-9)
    return {
        "metric": "wide_deep_spill_samples_per_sec",
        "value": spill["value"], "unit": "samples/s",
        "vs_baseline": 1.0, "batch": batch,
        "embedding_params": ram.get("embedding_params"),
        "pservers": n_pservers, "trainers": n_trainers,
        "resident_frac_target": resident_frac, "hot_rows": hot_rows,
        "ram_samples_per_sec": ram["value"],
        "rate_vs_ram": round(ratio, 3),
        "rate_bar_0p5_met": ratio > 0.5,
        # raw-at-rest loss parity is the bit-exactness contract
        "final_loss": spill["final_loss"],
        "loss_ram": ram["final_loss"],
        "loss_bit_identical": spill["final_loss"] == ram["final_loss"],
        "slab": spill.get("slab", {}),
        # int8-at-rest companion: rate + loss envelope + density gauge
        "int8_samples_per_sec": spill8["value"],
        "int8_rate_vs_ram": round(ratio8, 3),
        "loss_int8": spill8["final_loss"],
        "int8_loss_delta": round(
            abs(spill8["final_loss"] - ram["final_loss"]), 6),
        "int8_slab": spill8.get("slab", {}),
        # density is a row-WIDTH property (dim/(dim/4+4)): this model's
        # dim-16 deep tables cap at 3.2x and its dim-1 wide tables are
        # expansion-gated to raw, so the aggregate lands ~2.8x; the
        # >=3.5x acceptance gauge is evidenced at dim>=32 by
        # tests/test_ps_capacity.py and rpc_microbench --spill (3.76x
        # at dim 64)
        "int8_density_x": spill8.get("slab", {}).get("density_x", 0.0),
    }


def bench_wide_deep_1b_ceiling(batch=512, steps=16, warmup=8,
                               sparse_dim=20000, window_k=8):
    """No-PS compiled ceiling PROXY for the wide_deep_1b pair: the same
    arch/batch/window shape with LOCAL embedding tables at a reduced
    sparse_dim — the true 2.5M-row×26-slot tables are ~4.3 GB dense and
    exactly why the PS plane exists, so the ceiling is what the
    compiled step could do if the wire were free. Single process, no
    pservers; with_auc keeps the segmented execution shape of the PS
    lanes."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import core
    from paddle_tpu.models import wide_deep

    main, startup, feeds, loss, auc = wide_deep.build_wide_deep_program(
        num_dense=13, num_slots=26, sparse_dim=sparse_dim,
        embedding_dim=16, hidden=(64, 64), lr=1e-3,
        optimizer=fluid.optimizer.SGD(1e-3))
    exe = fluid.Executor()
    scope = core.Scope()
    nb = wide_deep.ctr_reader(batch, num_dense=13, num_slots=26,
                              sparse_dim=sparse_dim, seed=0)
    batches = [nb() for _ in range(window_k)]
    feed = {n: np.stack([b[n] for b in batches]) for n in batches[0]}
    with fluid.scope_guard(scope):
        exe.run(startup)
        for _ in range(max(1, warmup // window_k)):
            exe.run(main, feed=feed, fetch_list=[loss],
                    n_steps=window_k, return_numpy=False)
        t0 = time.perf_counter()
        for _ in range(max(1, steps // window_k)):
            out = exe.run(main, feed=feed, fetch_list=[loss],
                          n_steps=window_k, return_numpy=False)
        _ = float(np.asarray(out[0].array).ravel()[-1])
        dt = time.perf_counter() - t0
    return {"metric": "wide_deep_1b_nops_ceiling_samples_per_sec",
            "value": round(batch * steps / dt, 1), "unit": "samples/s",
            "vs_baseline": 1.0, "batch": batch,
            "sparse_dim_proxy": int(sparse_dim), "window_k": window_k,
            "executor_mode": exe._last_run_mode,
            "note": "no-PS ceiling proxy at reduced local table size"}


def bench_serving_mnist(clients=16, duration=2.5, warmup_s=0.5):
    """Online-serving lanes (docs/SERVING.md "Bench methodology"):
    closed-loop QPS + p50/p99 at ``clients`` concurrent single-row
    clients over the mnist MLP, three lanes on one model/scope:

      * naive   — the PRE-serving-plane path: reference PredictorPool /
                  Clone() semantics, one ``Executor.run`` dispatch per
                  request on a per-client executor. One-request-one-
                  dispatch, zero batching.
      * nobatch — the ServingEngine with max_batch=1: the batching
                  ablation (same queue/futures plumbing, batching off).
      * batched — continuous batching, max_batch=``clients``: the
                  serving plane's default row-exact scan mode.

    The acceptance bar (ISSUE 7) compares batched vs naive; the nobatch
    ablation is reported because on this 1-core box the client threads'
    GIL wakeups bound it — see the SERVING.md caveat."""
    import threading
    import paddle_tpu.fluid as fluid
    from paddle_tpu.serving import ServingEngine
    from tools import serving_loadgen as LG

    main, scope, out_name, feeds = LG.build_mlp_serving_model()
    feeds_b = [{"x": f["x"][None]} for f in feeds]  # [1, 784] for exe.run

    # --- naive lane: per-client executor, one dispatch per request ----
    exes = [fluid.Executor() for _ in range(clients)]
    for e in exes:  # warm TWICE through the production path (memory:
        for _ in range(2):  # arg-sharding recompile on call 2)
            e.run(main, feed=feeds_b[0], fetch_list=[out_name],
                  scope=scope)
    tl = threading.local()
    nxt = iter(range(clients))
    lk = threading.Lock()

    def naive_predict(feed):
        e = getattr(tl, "exe", None)
        if e is None:
            with lk:
                tl.exe = e = exes[next(nxt)]
        return e.run(main, feed=feed, fetch_list=[out_name],
                     scope=scope)

    naive = LG.run_closed_loop(naive_predict, feeds_b, clients=clients,
                               duration_s=duration, warmup_s=warmup_s)

    def engine_lane(max_batch):
        eng = ServingEngine(program=main, scope=scope, feed_names=["x"],
                            fetch_names=[out_name], max_batch=max_batch,
                            max_queue_delay_ms=2.0, num_workers=2)
        try:
            eng.warm()
            eng.reset_stats()
            res = LG.run_closed_loop(eng.predict, feeds, clients=clients,
                                     duration_s=duration,
                                     warmup_s=warmup_s)
            st = eng.stats()
        finally:
            eng.close()
        return res, st

    nobatch, _ = engine_lane(1)
    batched, bst = engine_lane(clients)
    return {"metric": "serving_mnist_qps", "value": round(batched["qps"], 1),
            "unit": "req/s", "vs_baseline": round(
                batched["qps"] / max(naive["qps"], 1e-9), 2),
            "clients": clients,
            "naive_qps": round(naive["qps"], 1),
            "engine_nobatch_qps": round(nobatch["qps"], 1),
            "speedup_vs_naive": round(
                batched["qps"] / max(naive["qps"], 1e-9), 2),
            "speedup_vs_nobatch": round(
                batched["qps"] / max(nobatch["qps"], 1e-9), 2),
            "p50_ms": round(batched["p50_ms"], 2),
            "p99_ms": round(batched["p99_ms"], 2),
            "naive_p50_ms": round(naive["p50_ms"], 2),
            "naive_p99_ms": round(naive["p99_ms"], 2),
            "batch_mode": bst["mode"],
            "avg_batch": round(bst["avg_batch"], 1),
            "buckets_compiled": bst["buckets_compiled"]}


def bench_serving_wide_deep(clients=8, duration=2.0, warmup_s=0.5,
                            sparse_dim=20000, num_slots=26):
    """Wide&Deep CTR serving lanes: the same forward program served
    (a) from local embedding tables (compiled row-exact scan mode) and
    (b) through LIVE pservers — ``rewrite_sparse_lookups`` points the 52
    per-slot tables at 2 in-process listen_and_serv shards and the
    engine's EmbeddingCache fronts the ``distributed_lookup_table``
    pulls (PR 4 binary wire underneath). Reports both lanes' QPS +
    p50/p99, the cache hit rate, and a bit-parity flag: the PS lane's
    predictions must equal the local-table oracle bit-for-bit on the
    same padded bucket (the table is unchanged during the bench)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import core
    from paddle_tpu.fluid.ps_rpc import VarClient
    from paddle_tpu.models.wide_deep import wide_deep_net, ctr_reader
    from paddle_tpu.serving import (EmbeddingCache, ServingEngine,
                                    rewrite_sparse_lookups)
    from tools import serving_loadgen as LG

    num_dense = 13
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        dense = fluid.data("dense", shape=[num_dense], dtype="float32")
        slots = [fluid.data("slot_%d" % i, shape=[1], dtype="int64")
                 for i in range(num_slots)]
        prob = wide_deep_net(dense, slots, sparse_dim=sparse_dim,
                             embedding_dim=16, hidden=(128, 64),
                             is_distributed=True)
    exe = fluid.Executor()
    scope = core.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    feed_names = ["dense"] + ["slot_%d" % i for i in range(num_slots)]
    nb = ctr_reader(64, num_dense=num_dense, num_slots=num_slots,
                    sparse_dim=sparse_dim, seed=0)
    raw = [nb() for _ in range(16)]
    feeds = []
    for b in raw:
        for i in range(8):  # single-row serving requests
            feeds.append({n: b[n][i] for n in feed_names})

    probe = {n: np.stack([feeds[k][n] for k in range(4)])
             for n in feed_names}

    def lane(program, cache=None, mode=None, loadgen=True):
        eng = ServingEngine(program=program, scope=scope,
                            feed_names=feed_names,
                            fetch_names=[prob.name], max_batch=clients,
                            max_queue_delay_ms=2.0, num_workers=2,
                            batch_mode=mode, embedding_cache=cache)
        res, st = None, None
        try:
            eng.warm((1, 2, 4, clients))
            if loadgen:
                eng.reset_stats()
                res = LG.run_closed_loop(eng.predict, feeds,
                                         clients=clients,
                                         duration_s=duration,
                                         warmup_s=warmup_s)
                st = eng.stats()
            # parity probe: one deterministic padded bucket through THIS
            # engine (oracle comparison happens outside the timed loop)
            (pred,) = eng.predict_many(probe)
        finally:
            eng.close()
        return res, st, pred

    local_res, local_st, local_pred = lane(main)

    eps = [f"127.0.0.1:{LG.free_port()}" for _ in range(2)]
    servers = [LG.start_inproc_pserver(ep) for ep in eps]
    try:
        tables = (["wide_emb_%d" % i for i in range(num_slots)]
                  + ["deep_emb_%d" % i for i in range(num_slots)])
        with fluid.scope_guard(scope):
            for t in tables:
                LG.push_table(
                    eps, t, np.asarray(scope.find_var(t).value().array))
        ps_prog, _hit = rewrite_sparse_lookups(main, eps, tables=tables)
        cache = EmbeddingCache(ttl_s=300.0, max_entries=2_000_000)
        ps_res, ps_st, ps_pred = lane(ps_prog, cache=cache, mode="fused")
        cache_stats = ps_st.get("embedding_cache") or {}
        # no-cache PS lane for the RPC-elision delta
        ps_nc_res, _st, _p = lane(ps_prog, cache=None, mode="fused")
        # local-table oracle for the SAME padded probe bucket (fused
        # mode at the same bucket size -> bit-comparable)
        _r, _s, oracle_pred = lane(main, mode="fused", loadgen=False)
        parity_ok = bool((ps_pred == oracle_pred).all())
    finally:
        for ep, (th, _scope) in zip(eps, servers):
            LG.stop_inproc_pserver(ep, th)
        VarClient.reset_pool()
    return {"metric": "serving_wide_deep_qps",
            "value": round(ps_res["qps"], 1), "unit": "req/s",
            "vs_baseline": 1.0, "clients": clients,
            "sparse_dim": sparse_dim, "num_slots": num_slots,
            "local_qps": round(local_res["qps"], 1),
            "ps_qps_cached": round(ps_res["qps"], 1),
            "ps_qps_nocache": round(ps_nc_res["qps"], 1),
            "cache_hit_rate": round(cache_stats.get("hit_rate", 0.0), 4),
            "p50_ms": round(ps_res["p50_ms"], 2),
            "p99_ms": round(ps_res["p99_ms"], 2),
            "local_p50_ms": round(local_res["p50_ms"], 2),
            "local_p99_ms": round(local_res["p99_ms"], 2),
            "parity_ok": parity_ok,
            "pservers": len(eps)}


def bench_serve_http_overload(clients=16, duration=2.5, warmup_s=0.5,
                              overload_factor=4.0):
    """HTTP ingress overload lane (docs/SERVING.md "Ingress &
    overload"): the full serving stack on the wire — ThreadingHTTP
    ingress → admission queue → continuous batcher → scan-mode engine —
    measured closed-loop at capacity (1× load), then open-loop at 1×
    and 4× the measured capacity with 16 HTTP clients. Reports the
    accepted-request p99 at 1× and 4×, the shed rate (typed 429s; any
    untyped 5xx/transport failure fails the lane), and the engine's
    shed/deadline counters. The robustness claim is the RATIO: under
    4× offered load the accepted p99 stays bounded (admission bound +
    CoDel head-drop) and every refused request is answered typed.
    1-core caveat: clients, ingress handlers, and engine workers
    time-slice one core, so absolute QPS is trend-only (PR 7 serving
    caveat) — ratio and typed-refusal figures are the robust
    numbers."""
    from tools.serving_loadgen import run_overload_scenario

    res = run_overload_scenario(clients=clients, duration_s=duration,
                                warmup_s=warmup_s,
                                overload_factor=overload_factor)
    return {
        "metric": "serve_http_overload_p99_ratio",
        "value": res["p99_ratio"],
        "unit": "x (accepted p99 at 4x / 1x)",
        "vs_baseline": res["p99_ratio"],
        "clients": clients,
        "capacity_qps_1x": res["capacity_qps_1x"],
        "accepted_p99_ms_1x": round(res["accepted_p99_ms_1x"], 2),
        "accepted_p99_ms_1x_open": round(
            res["accepted_p99_ms_1x_open"], 2),
        "accepted_p99_ms_overload": round(
            res["accepted_p99_ms_overload"], 2),
        "p99_ratio_vs_open_1x": res["p99_ratio_vs_open_1x"],
        "shed_rate_overload": res["shed_rate_overload"],
        "overload_statuses": res["open_overload"]["statuses"],
        "untyped_failures": res["untyped_failures"],
        "all_refusals_typed": res["all_refusals_typed"],
        "engine_shed": res["engine"]["shed"],
        "engine_deadline_expired": res["engine"]["deadline_expired"],
        # the bound/deadline the scenario actually resolved and ran
        # with — re-deriving its defaults here would silently drift
        "max_queue_rows": res["max_queue_rows"],
        "deadline_ms": res["deadline_ms"],
    }


def bench_serve_fleet(members=4, clients=8, duration=3.0, warmup_s=0.5,
                      n_rows=256, dim=8):
    """Serving-fleet scale lane (docs/SERVING.md "Fleet"): ``members``
    REAL engine subprocesses (tools/chaos_ps.py serving-member — each
    its own interpreter, ingress, EmbeddingCache and invalidation
    subscriber) behind a FleetDirectory, driven closed-loop through
    the FleetRouter, vs the SAME load against one member. Also probes
    the fleet contracts outside the timed loops: per-member response
    parity (every member must answer a probe id identically — they
    serve one table), and the trainer-push freshness window (publish →
    new value in a remote HTTP response, wall-clock measured).

    1-core caveat: all member processes time-slice one core, so the
    fleet/single QPS ratio is trend-only there — the acceptance
    evidence arm is parity + freshness + the per-endpoint spread
    showing genuine multi-process overlap (PR 7 serving caveat; the
    ≥3× scale claim needs ≥``members`` cores)."""
    import tempfile
    import threading

    from tools.chaos_ps import (_spawn, _wait_file, free_port)
    from tools.serving_loadgen import (HttpClient,
                                       run_http_fleet_closed_loop)
    from paddle_tpu.fluid.ps_rpc import VarClient, VarServer
    from paddle_tpu.serving import FleetDirectory, InvalidationPublisher

    rng = np.random.RandomState(7)
    table = rng.rand(n_rows, dim).astype(np.float32)
    tlock = threading.Lock()

    def serve_table(name, rows, prefetch=False, trainer_id=0):
        with tlock:
            return table[np.asarray(rows, np.int64)].copy()

    workdir = tempfile.mkdtemp(prefix="bench_fleet_")
    table_ep = f"127.0.0.1:{free_port()}"
    pub_ep = f"127.0.0.1:{free_port()}"
    dir_ep = f"127.0.0.1:{free_port()}"
    srv = VarServer(table_ep, {"prefetch_rows": serve_table}).start()
    pub = InvalidationPublisher(pub_ep).start()
    directory = FleetDirectory(dir_ep, heartbeat_timeout_s=2.0).start()
    chaos_py = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "tools", "chaos_ps.py")
    procs = []
    try:
        waits = []
        for i in range(members):
            ready = os.path.join(workdir, f"m{i}.ready")
            p, tail = _spawn(
                [chaos_py, "serving-member", f"m{i}", table_ep, pub_ep,
                 dir_ep, ready, f"--rows={n_rows}", f"--dim={dim}",
                 "--hb=2.0"],
                os.path.join(workdir, f"m{i}.log"))
            procs.append(p)
            waits.append((ready, p, tail))
        ports = []
        for ready, p, tail in waits:
            _wait_file(ready, 180, [(p, tail)], desc=ready)
            ports.append(int(open(ready).read().strip()))

        feeds = [{"ids": np.array([[i % n_rows]], np.int64)}
                 for i in range(64)]
        # per-member parity probe: one table, identical answers
        probe_id = 13
        answers = []
        for port in ports:
            cli = HttpClient("127.0.0.1", port)
            try:
                status, obj = cli.predict({"ids": [[probe_id]]},
                                          model="fleet")
            finally:
                cli.close()
            assert status == 200, (status, obj)
            answers.append(float(np.asarray(obj["outputs"][0])
                                 .reshape(-1)[0]))
        parity_ok = all(a == answers[0] for a in answers)

        single = run_http_fleet_closed_loop(
            [f"127.0.0.1:{ports[0]}"], feeds, clients=clients,
            duration_s=duration, warmup_s=warmup_s, model="fleet")
        fleet = run_http_fleet_closed_loop(
            [], feeds, clients=clients, duration_s=duration,
            warmup_s=warmup_s, model="fleet", directory_ep=dir_ep)

        # freshness: a trainer push must reach a REMOTE response fast
        with tlock:
            table[probe_id] += 1.0
            expect = float(table[probe_id].sum())
        t_push = time.time()
        pub.publish("emb_fleet", [probe_id])
        window = None
        cli = HttpClient("127.0.0.1", ports[-1])
        try:
            while time.time() - t_push < 10.0:
                status, obj = cli.predict({"ids": [[probe_id]]},
                                          model="fleet")
                if status == 200 and abs(
                        float(np.asarray(obj["outputs"][0])
                              .reshape(-1)[0]) - expect) < 1e-3:
                    window = time.time() - t_push
                    break
                time.sleep(0.01)
        finally:
            cli.close()

        ratio = (fleet["qps"] / single["qps"]) if single["qps"] else 0.0
        return {
            "metric": "serve_fleet_scale",
            "value": round(ratio, 3),
            "unit": f"x ({members}-member fleet QPS / 1-member QPS; "
                    "trend-only on 1 core)",
            "vs_baseline": round(ratio, 3),
            "members": members, "clients": clients,
            "fleet_qps": round(fleet["qps"], 1),
            "single_qps": round(single["qps"], 1),
            "fleet_p99_ms": round(fleet["p99_ms"], 2),
            "single_p99_ms": round(single["p99_ms"], 2),
            "by_endpoint_ok": {
                ep: d.get("ok", 0)
                for ep, d in fleet["by_endpoint"].items()},
            "parity_ok": bool(parity_ok),
            "freshness_window_s": (round(window, 4)
                                   if window is not None else None),
            "cores": os.cpu_count(),
        }
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=15)
            except Exception:
                p.kill()
        directory.close()
        pub.close()
        srv.shutdown()
        VarClient.reset_pool()


def bench_stream_ctr(steps=30, batch=8, step_sleep=0.12):
    """Streaming online-learning CTR lane (docs/FAULT_TOLERANCE.md
    "Streaming online learning"): runs the full chaos acceptance
    scenario — sync-oracle leg, then the fully-async train+serve
    cluster with its mid-run pserver SIGKILL — and reports async vs
    sync-oracle trainer samples/s plus the event→served freshness p99
    scraped off the serving member's /metrics histogram. Prints one
    row per leg: the sync-oracle row to stderr, the async row as the
    lane's JSON line.

    1-core evidence-arm caveat (same as serve_fleet /
    wide_deep_1b_async): every cluster process shares one core, so
    samples/s is scheduler-bound evidence — the robustness checks
    (zero typed-error leaks across the SIGKILL, loss in the oracle's
    neighborhood) are the lane's primary product. The async trainer is
    paced by ``step_sleep`` (it models event arrival; the oracle leg
    is unpaced), so the row records the pacing and a pacing-adjusted
    rate alongside the raw one. Faster pacing starves the co-located
    serving member on one core (accepted p99 blows the bar at 0.05s),
    so the default keeps the scenario's 0.12s event cadence."""
    import tempfile
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from tools.chaos_ps import run_streaming_scenario

    wd = tempfile.mkdtemp(prefix="bench_stream_ctr_")
    res = run_streaming_scenario(wd, steps=steps, batch=batch,
                                 step_sleep=step_sleep,
                                 kill_at=max(5, steps // 3))
    n_async = int(res.get("async_steps_run") or steps)
    wall_a = float(res.get("async_train_wall_s") or 0) or None
    wall_o = float(res.get("oracle_train_wall_s") or 0) or None
    sps_async = round(n_async * batch / wall_a, 2) if wall_a else None
    sps_oracle = round(steps * batch / wall_o, 2) if wall_o else None
    paced_out = n_async * step_sleep
    sps_async_adj = (round(n_async * batch / (wall_a - paced_out), 2)
                     if wall_a and wall_a > paced_out else None)
    note = ("1-core box: all cluster processes share one core — "
            "samples/s is scheduler-bound evidence; robustness checks "
            "(zero typed leaks across SIGKILL, oracle-neighborhood "
            "loss) are the lane's product")
    rows = [
        {"metric": "stream_ctr_async_samples_per_sec",
         "value": sps_async, "unit": "samples/s",
         "vs_baseline": (round(sps_async / sps_oracle, 3)
                         if sps_async and sps_oracle else None),
         "steps": n_async, "batch": batch, "step_sleep_s": step_sleep,
         "pacing_adjusted_samples_per_sec": sps_async_adj,
         "freshness_p99_s": res.get("freshness_p99_s"),
         "freshness_samples": res.get("freshness_samples"),
         "serving_p99_ms": (res.get("load") or {}).get("p99_ms"),
         "shrink_runs": res.get("shrink_runs"),
         "async_tail_mean": res.get("async_tail_mean"),
         "ok": res.get("ok"), "note": note},
        {"metric": "stream_ctr_sync_oracle_samples_per_sec",
         "value": sps_oracle, "unit": "samples/s", "vs_baseline": 1.0,
         "steps": steps, "batch": batch, "step_sleep_s": 0.0,
         "oracle_tail_mean": res.get("oracle_tail_mean"),
         "note": note},
    ]
    print(json.dumps(rows[1]), file=sys.stderr)
    return rows[0]


def bench_longctx(iters=8):
    """Long-context attention lane (SURVEY §5: long-context is
    first-class here — ring/Ulysses SP + flash kernels — where the
    reference's v1.7 answer was LoD ragged batching). Two shapes:

    TPU (one chip): causal Pallas flash attention fwd+bwd at S=8192,
    bf16 — the single-chip long-sequence path, scan-timed so dispatch
    stays out of the number.
    CPU (virtual mesh): 8-device ring attention fwd+bwd, the
    sequence-parallel path whose K/V blocks rotate over ppermute.

    Reports tokens/s and attention-only achieved TFLOPs (causal fwd
    2·B·H·S²·D multiply-adds ≈ 4·B·H·S²·D FLOPs halved for causality,
    ×3.5 for fwd+bwd)."""
    import jax
    import jax.numpy as jnp
    from tools.flash_smoke import _timed_scan

    if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        # the CPU lane measures the 8-device ring — force the virtual
        # mesh BEFORE the backend initializes (ambient XLA_FLAGS must
        # not be a prerequisite; a 1-device "ring" never exercises the
        # ppermute rotation this lane exists for)
        try:
            jax.config.update("jax_num_cpu_devices", 8)
        except Exception:
            pass  # backend already initialized (e.g. env-forced count)
    on_tpu = jax.devices()[0].platform == "tpu"
    rng = np.random.RandomState(0)
    if on_tpu:
        from paddle_tpu.ops.pallas.flash_attention import flash_attention
        B, H, S, D = 1, 12, 8192, 64
        dt_ = jnp.bfloat16
        q, k, v = (jnp.asarray(rng.randn(B, H, S, D) * 0.3, dt_)
                   for _ in range(3))
        sm = 1.0 / float(np.sqrt(D))

        def fwdbwd(q_, k_, v_):
            def loss(q2, k2, v2):
                return jnp.sum(
                    flash_attention(q2, k2, v2, sm, causal=True)
                    .astype(jnp.float32) ** 2)
            l, grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(
                q_, k_, v_)
            return l + sum(jnp.sum(g.astype(jnp.float32) ** 2)
                           for g in grads)
        ms = _timed_scan(fwdbwd, q, k, v, iters)
        mode = "flash_causal_1chip"
        n_dev = 1
    else:
        from paddle_tpu.parallel.ring_attention import (ring_attention,
                                                        sequence_mesh)
        n_dev = len(jax.devices())
        if n_dev == 1:
            # the jax_num_cpu_devices update silently no-ops once the
            # backend is initialized; a 1-device "ring" never exercises
            # the ppermute rotation this lane exists to measure
            raise SystemExit(
                "bench.py longctx: the CPU ring lane needs a multi-device "
                "virtual mesh; the backend initialized before "
                "jax_num_cpu_devices could take effect")
        mesh = sequence_mesh(n_dev)
        B, H, D = 1, 4, 64
        S = 512 * max(1, n_dev)
        q, k, v = (jnp.asarray(rng.randn(B, H, S, D) * 0.3, jnp.float32)
                   for _ in range(3))
        sm = 1.0 / float(np.sqrt(D))

        def fwdbwd(q_, k_, v_):
            def loss(q2, k2, v2):
                return jnp.sum(ring_attention(q2, k2, v2, sm, causal=True,
                                              mesh=mesh) ** 2)
            l, grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(
                q_, k_, v_)
            return l + sum(jnp.sum(g) for g in grads)
        ms = _timed_scan(fwdbwd, q, k, v, iters)
        mode = f"ring_sp{n_dev}_virtual"
    flops = 4.0 * B * H * S * S * D / 2.0 * 3.5  # causal fwd+bwd
    return {"metric": "longctx_attention_tokens_per_sec",
            "value": round(B * S / (ms / 1e3), 1), "unit": "tokens/s",
            "vs_baseline": 1.0, "seq_len": S, "heads": H, "head_dim": D,
            "mode": mode, "devices": n_dev, "step_ms": round(ms, 3),
            "attn_tflops": round(flops / (ms / 1e3) / 1e12, 3)}


def bench_lm3d(k=8, rounds=3, parity_steps=4):
    """Composed 3D-parallel LM lane (ROADMAP item 4): a GPT-style
    decoder trained at dp2×pp2×sp2 (+ a 4-expert MoE expert-parallel
    variant over "dp") on the 8-device virtual mesh —
    parallel/lm3d.py. Reports tokens/s and achieved model TFLOPs
    (6·N·tokens, the longctx-lane methodology; attention quadratic term
    alongside), per-step loss parity vs the single-device oracle,
    counted MoE token drops, zero-retrace steady-state evidence
    (jit cache size + jax backend-compile counter over the timed
    region, scraped as executor_retraces_total{kind=lm3d}), and a PR 10
    merged cluster-timeline artifact (tools/lm3d_timeline.json) whose
    cat="window" spans are the dispatch-level overlap evidence. On this
    1-core box the 8 mesh "devices" time-slice one CPU, so tokens/s is
    a composition-correctness trend number, not a speedup claim
    (docs/PERF.md caveats)."""
    import tempfile
    import jax
    import jax.numpy as jnp

    if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        try:
            jax.config.update("jax_num_cpu_devices", 8)
        except Exception:
            pass  # backend already initialized
    n_dev = len(jax.devices())
    if n_dev < 8:
        raise SystemExit(
            f"bench.py lm3d: the composed dp2×pp2×sp2 lane needs an "
            f"8-device (virtual) mesh, have {n_dev}; the backend "
            f"initialized before jax_num_cpu_devices could take effect")

    from paddle_tpu.fluid import core as _core, telemetry, profiler
    from paddle_tpu.parallel import lm3d

    telemetry.install_jax_compile_listener()
    trace_dir = tempfile.mkdtemp(prefix="lm3d_trace_")
    _core.set_flag("FLAGS_trace_dir", trace_dir)
    telemetry.set_process_role("lm3d")

    def backend_compiles():
        fam = telemetry.REGISTRY.get("jax_backend_compiles_total")
        return sum(c.value() for c in fam.children()) if fam else 0.0

    def run_variant(tag, cfg):
        global LAST_COMPILE_S
        mesh = cfg.mesh()
        params = lm3d.place_params(cfg, mesh, lm3d.init_params(cfg))
        amp = lm3d.init_amp_state(cfg, mesh)
        win = jax.jit(lm3d.make_window_step(cfg, mesh))
        key = jax.random.PRNGKey(cfg.seed)
        telemetry.count_compile(f"lm3d_{tag}")
        t0 = time.perf_counter()
        with profiler.RecordEvent(f"compile:lm3d_{tag}[{k}]",
                                  cat="compile"):
            w = lm3d.place_window(cfg, mesh,
                                  lm3d.sample_window(cfg, 0, k))
            p, a, outs = win(params, amp, w, key, jnp.int32(0))
            jax.block_until_ready(outs[0])
        compile_s = round(time.perf_counter() - t0, 2)
        LAST_COMPILE_S = compile_s
        loss0 = float(outs[0][0])
        # timed steady state: the jitted window must never retrace
        c0 = backend_compiles()
        idx = k
        t0 = time.perf_counter()
        for _ in range(rounds):
            wz = lm3d.place_window(cfg, mesh,
                                   lm3d.sample_window(cfg, idx, k))
            with profiler.RecordEvent(f"lm3d_{tag}:window[{k}]",
                                      cat="window",
                                      args={"steps": k}):
                p, a, outs = win(p, a, wz, key, jnp.int32(idx))
                jax.block_until_ready(outs[0])
            idx += k
        dt = time.perf_counter() - t0
        retraces = win._cache_size() - 1
        if retraces > 0:
            telemetry.count_compile(f"lm3d_{tag}", retrace=True)
        fl = lm3d.flops_per_step(cfg, lm3d.param_count(
            lm3d.init_params(cfg)))
        steps = rounds * k
        tokens = fl["tokens"] * steps
        # oracle parity: fresh params, same feeds/folds, one device
        ostep = jax.jit(lm3d.make_oracle_step(cfg))
        po = lm3d.init_params(cfg)
        ao = lm3d.init_amp_state(cfg)
        pc = lm3d.init_params(cfg)
        pc = lm3d.place_params(cfg, mesh, pc)
        ac = lm3d.init_amp_state(cfg, mesh)
        step = jax.jit(lm3d.make_train_step(cfg, mesh))
        wp = lm3d.sample_window(cfg, 0, parity_steps)
        rel = 0.0
        for i in range(parity_steps):
            xb = jnp.asarray(wp[i, ..., :-1])
            yb = jnp.asarray(wp[i, ..., 1:])
            kk = jax.random.fold_in(key, i)
            pc, ac, (lc, _, _, dc) = step(pc, ac, xb, yb, kk)
            po, ao, (lo, _, _, do) = ostep(po, ao, xb, yb, kk)
            lo_f = float(lo)
            rel = max(rel, abs(float(lc) - lo_f) / max(abs(lo_f),
                                                       1e-9))
        return {
            "tokens_per_sec": round(tokens / dt, 1),
            "model_tflops": round(fl["model_flops"] * steps / dt
                                  / 1e12, 5),
            "attn_tflops": round(fl["attn_flops"] * steps / dt / 1e12,
                                 5),
            "n_params": int(fl["n_params"]),
            "n_active_params": int(fl["n_active_params"]),
            "step_ms": round(dt / steps * 1e3, 2),
            "compile_s": compile_s, "loss_first": round(loss0, 4),
            "loss_last": round(float(outs[0][-1]), 4),
            "loss_rel_vs_oracle_max": rel,
            "retraces_steady": int(retraces),
            "moe_dropped_tokens": int(outs[3][-1]),
        }

    base = dict(vocab=256, d_model=128, n_heads=4, seq_len=256,
                layers_per_stage=1, dp=2, pp=2, sp=2, n_micro=4,
                batch=16, lr=0.05, seed=1)
    dense = run_variant("dense", lm3d.LMConfig(**base))
    moe = run_variant("moe", lm3d.LMConfig(
        **base, n_experts=4, capacity_factor=8.0))
    # counted-drops probe: a deliberately tight per-expert capacity
    # must DROP (Switch semantics) and the schedule-total count it
    cfg_drop = lm3d.LMConfig(**base, n_experts=4, capacity_factor=0.25)
    mesh = cfg_drop.mesh()
    stepd = jax.jit(lm3d.make_train_step(cfg_drop, mesh))
    pd = lm3d.place_params(cfg_drop, mesh, lm3d.init_params(cfg_drop))
    wd = lm3d.sample_window(cfg_drop, 0, 1)
    _, _, (_, _, _, dropped) = stepd(
        pd, {}, jnp.asarray(wd[0, ..., :-1]),
        jnp.asarray(wd[0, ..., 1:]), jax.random.PRNGKey(0))
    drops_probe = int(dropped)

    # merged PR 10 cluster timeline artifact (window/compile spans)
    _core.set_flag("FLAGS_trace_dir", "")  # retire + final-flush
    telemetry._shard()
    timeline_out = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools",
        "lm3d_timeline.json")
    try:
        from tools.timeline import merge_shards
        tl = merge_shards(trace_dir, out=timeline_out)
        timeline = {"out": timeline_out, "n_events": tl["n_events"],
                    "n_shards": tl["n_shards"]}
    except Exception as e:  # evidence artifact, never a lane failure
        timeline = {"error": repr(e)[:200]}

    retr = telemetry.REGISTRY.get("executor_retraces_total")
    retraces_total = sum(c.value() for c in retr.children()) \
        if retr else 0.0
    n_micro, pp = base["n_micro"], base["pp"]
    ok = (dense["loss_rel_vs_oracle_max"] < 2e-5
          and moe["loss_rel_vs_oracle_max"] < 2e-5
          and dense["retraces_steady"] == 0
          and moe["retraces_steady"] == 0
          and drops_probe > 0
          and dense["loss_last"] < dense["loss_first"])
    return {"metric": "lm3d_tokens_per_sec",
            "value": dense["tokens_per_sec"], "unit": "tokens/s",
            "vs_baseline": 1.0, "ok": ok, "devices": n_dev,
            "mode": "dp2_pp2_sp2_virtual", "window": k,
            "bubble_frac_analytic": round((pp - 1)
                                          / (n_micro + pp - 1), 4),
            "dense": dense, "moe": moe,
            "moe_drops_probe_tokens": drops_probe,
            "executor_retraces_total": retraces_total,
            "timeline": timeline}


def bench_flash():
    """Pallas flash-attention sweep on the chip: compile (no interpret),
    parity vs einsum, block sizes. Per-config JSON rows go to stderr;
    the contract line (summary over all rows, measured-best blocks per
    shape included) is the return value."""
    from tools import flash_smoke
    chip = require_tpu("bench.py flash")
    rows = flash_smoke.sweep(
        emit=lambda row: print(row, file=sys.stderr))
    return flash_smoke.summarize(rows, chip.platform)


# where JAX_COMPILATION_CACHE_DIR is unset, the fixed in-checkout cache:
# the directory is part of the cache key, so it never moves
CACHE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         ".xla_cache")


def _cache_entries(cache_dir):
    return len([f for f in os.listdir(cache_dir) if not f.startswith(".")])


def main():
    which = sys.argv[1] if len(sys.argv) > 1 else "bert"
    benches = {"bert": bench_bert_base, "mnist": bench_mnist_mlp,
               "resnet": bench_resnet50, "allreduce": bench_allreduce_dp,
               "wide_deep": bench_wide_deep,
               "wide_deep_1b": bench_wide_deep_1b,
               "wide_deep_1b_syncw": bench_wide_deep_1b_syncw,
               "wide_deep_1b_async": bench_wide_deep_1b_async,
               "wide_deep_1b_ceiling": bench_wide_deep_1b_ceiling,
               "wide_deep_geo": bench_wide_deep_geo,
               "wide_deep_geo_sync": bench_wide_deep_geo_sync,
               "wide_deep_spill": bench_wide_deep_spill,
               "mnist_realdata": bench_mnist_realdata,
               "mnist_guard": bench_mnist_realdata_guard,
               "wide_deep_realdata": bench_wide_deep_realdata,
               "serve_mnist": bench_serving_mnist,
               "serve_wide_deep": bench_serving_wide_deep,
               "serve_http_overload": bench_serve_http_overload,
               "serve_fleet": bench_serve_fleet,
               "stream_ctr": bench_stream_ctr,
               "flash": bench_flash, "longctx": bench_longctx,
               "lm3d": bench_lm3d}
    if which not in benches:
        raise SystemExit(f"unknown bench '{which}'; one of "
                         f"{sorted(benches)}")
    if which in ("longctx", "lm3d") \
            and os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        # the CPU ring lane needs the 8-device virtual mesh BEFORE any
        # backend init in this process; XLA_FLAGS is read at backend
        # init, so setting it here is in time
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_count=8")
    # persist XLA executables across invocations: a second run in the
    # same checkout (or against the same JAX_COMPILATION_CACHE_DIR) loads
    # the train step from disk
    from paddle_tpu.inference import enable_compile_cache
    cache_dir = enable_compile_cache(CACHE_DIR)
    entries_before = _cache_entries(cache_dir)
    res = benches[which]()
    res.setdefault("device", device_stamp())
    # executable-cache reload evidence: a warm second invocation shows
    # entries_before > 0 and compile_s collapsing vs the cold run
    if LAST_COMPILE_S is not None:
        res.setdefault("compile_s", LAST_COMPILE_S)
        res.setdefault("xla_cache_entries_before", entries_before)
        res.setdefault("xla_cache_entries_after", _cache_entries(cache_dir))
    print(json.dumps(res))


if __name__ == "__main__":
    main()
