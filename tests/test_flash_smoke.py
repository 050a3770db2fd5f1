"""The flash hardware sweep (tools/flash_smoke.py) runs on the chip only
— these tests keep its plumbing (config runner, parity math, JSON
schema, summary) green on the CPU interpreter so a chip run produces
data, not debugging.
Reference counterpart: operators/benchmark/op_tester.cc (measure, don't
assert)."""
import json

import numpy as np
import pytest

from tools import flash_smoke


def test_run_config_ok_schema():
    row = flash_smoke.run_config(128, 64, 64, B=1, H=2, steps=2,
                                 interpret=True)
    assert row["status"] == "ok", row
    for key in ("seq_len", "blk_q", "blk_k", "vmem_kb_est", "fwd_ms",
                "fwdbwd_ms", "tflops_fwd", "max_err_fwd", "max_err_dq",
                "max_err_dk", "max_err_dv"):
        assert key in row, key
    assert row["max_err_fwd"] < 2e-2
    json.dumps(row)  # every row must be JSON-serializable


def test_run_config_dropout_checked_against_the_same_keep_mask():
    """Dropout has a dense twin after all: the keep mask is a hash of
    absolute positions, plain integer arithmetic, so the dense side drops
    the same entries and the four errors mean what they mean without."""
    row = flash_smoke.run_config(128, 64, 64, B=1, H=2, steps=2,
                                 dropout=0.1, interpret=True)
    assert row["status"] == "ok", row
    assert max(row[f"max_err_{n}"] for n in ("fwd", "dq", "dk", "dv")) < 2e-2
    assert "dropout_deterministic" not in row


def test_run_config_ragged_runs_on_kernel():
    row = flash_smoke.run_config(100, 64, 64, B=1, H=2, steps=2,
                                 interpret=True)
    assert row["status"] == "ok", row
    assert row["ragged"] is True
    assert row["max_err_fwd"] < 2e-2


def test_run_config_never_raises_on_compile_error(monkeypatch):
    # force a kernel failure; the harness must return a row, not raise
    from paddle_tpu.ops.pallas import flash_attention as fa

    def boom(*a, **k):
        raise RuntimeError("mosaic says no")

    monkeypatch.setattr(fa, "flash_attention", boom)
    row = flash_smoke.run_config(128, 64, 64, B=1, H=2, interpret=True)
    assert row["status"] == "compile_error"
    assert "mosaic says no" in row["error"]


def test_attention_paths_rehearsal_forces_each_path(monkeypatch, capsys):
    """tools/attention_paths.py (the on-chip table the attention ops'
    DENSE_MAX_SEQ rests on) on the CPU: the `flash` row really traced
    the kernels (forward op + the grad op's vjp), the `dense` row never
    did, both computed the same thing, the bound is restored, and no
    device number is printed off the chip."""
    from paddle_tpu.ops import attention_ops
    from tools import attention_paths
    calls = []
    real = attention_ops.flash_attention
    monkeypatch.setattr(attention_ops, "flash_attention",
                        lambda *a, **kw: calls.append(1) or real(*a, **kw))
    attention_paths.main(["--tiny", "--seqs", "16"])
    flash, dense = (json.loads(line)
                    for line in capsys.readouterr().out.splitlines())
    assert (flash["path"], dense["path"]) == ("flash", "dense")
    assert len(calls) == 2
    assert attention_ops.DENSE_MAX_SEQ == 128
    assert flash["batch"] * flash["seq"] == 256 and flash["temp_bytes"] > 0
    assert max(flash["max_diff_out_dq_dk_dv"]) < 1e-5
    assert "device_ms" not in flash and "device_ms" not in dense


def test_run_config_restores_interpret_mode():
    from paddle_tpu.ops.pallas import flash_attention as fa
    before = fa._INTERPRET
    flash_smoke.run_config(128, 64, 64, B=1, H=2, steps=1, interpret=True)
    assert fa._INTERPRET == before


def test_summarize_picks_best_and_reports_failures():
    rows = [
        {"status": "ok", "tflops_fwd": 1.0, "seq_len": 128, "blk_q": 64,
         "blk_k": 64, "fwd_ms": 1.0, "fwdbwd_ms": 3.0},
        {"status": "ok", "tflops_fwd": 5.0, "seq_len": 512, "blk_q": 256,
         "blk_k": 256, "fwd_ms": 0.5, "fwdbwd_ms": 1.5},
        {"status": "compile_error", "seq_len": 2048, "blk_q": 512,
         "blk_k": 512, "error": "VMEM OOM"},
    ]
    s = flash_smoke.summarize(rows, "tpu")
    assert s["value"] == 5.0
    assert s["configs_ok"] == 2 and s["configs_failed"] == 1
    assert s["best_config"]["blk_q"] == 256
    assert s["first_failure"]["error"] == "VMEM OOM"
    json.dumps(s)


def test_vmem_estimate_is_the_kernels_own_and_monotone_in_blocks():
    from paddle_tpu.ops.pallas import flash_attention as fa
    assert not hasattr(flash_smoke, "_vmem_kb_estimate")  # one estimate
    row = flash_smoke.run_config(128, 64, 64, B=1, H=2, steps=1,
                                 interpret=True)
    assert row["vmem_kb_est"] * 1024 == max(
        fa._working_set(k, 64, 64, 64, 64, 2) for k in fa.KERNELS)
    for kernel in fa.KERNELS:
        small = fa._working_set(kernel, 128, 128, 64, 64, 2)
        assert fa._working_set(kernel, 512, 512, 64, 64, 2) > small > 0
        # a 64-wide head takes whole 128-lane tiles; f32 operands twice
        assert fa._working_set(kernel, 128, 128, 128, 128, 2) == small
        assert fa._working_set(kernel, 128, 128, 64, 64, 4) > small
        assert fa._working_set(kernel, 128, 128, 64, 64, 2, True) > small


def test_sweep_plan_covers_the_cells_four_shapes():
    """Every candidate pair of the kernels' own lists on each of the
    four attention calls the benchmark's cells make, D, Dv, heads,
    window, bias and dropout as the cells have them, and a ragged leg."""
    from paddle_tpu.ops.pallas import flash_attention as fa
    plan = flash_smoke.sweep_plan()
    by_shape = {}
    for cfg in plan:
        by_shape.setdefault(cfg.get("shape"), []).append(cfg)
    assert set(by_shape) == {None, "bert_s512_pad", "phi_causal",
                             "phi_w512", "qwen_d256"}
    assert {(c["blk_q"], c["blk_k"]) for c in by_shape["phi_w512"]} == {
        (bq, bk) for bq in fa.BLOCK_Q_CANDIDATES
        for bk in fa.BLOCK_K_CANDIDATES}
    assert len(by_shape["bert_s512_pad"]) == 9      # nothing beyond s512
    bert, phi, qwen = (by_shape[n][0] for n in (
        "bert_s512_pad", "phi_w512", "qwen_d256"))
    assert (bert["B"], bert["H"], bert["S"], bert["D"], bert["bias"],
            bert["dropout"]) == (32, 12, 512, 64, True, 0.1)
    assert (phi["H"], phi["S"], phi["D"], phi["Dv"], phi["window"]) == (
        40, 4096, 64, 128, 512)
    assert (qwen["H"], qwen["D"], qwen["causal"]) == (16, 256, True)
    assert by_shape[None][0]["S"] % 128             # the ragged leg
    assert flash_smoke.sweep_plan(["qwen_d256"]) == by_shape["qwen_d256"]


@pytest.mark.parametrize("kw", [
    dict(window=40, D=16, Dv=32), dict(bias=True, dropout=0.1),
    dict(causal=True, D=32)], ids=["window_wide_v", "keypad_dropout",
                                   "causal"])
def test_run_config_takes_the_cells_forms(kw):
    """Window, V wider than Q/K, key-padding bias with dropout: each
    checked against dense attention (under dropout, with the kernels'
    own keep mask) and each kernel timed alone."""
    row = flash_smoke.run_config(128, 64, 128, B=2, H=2, steps=1,
                                 interpret=True, **kw)
    assert row["status"] == "ok", row
    for key in ("fwd_ms", "dkv_ms", "dq_ms", "fwdbwd_ms", "grid_steps",
                "kv_blocks", "v_dim", "window", "bias"):
        assert key in row, key
    assert max(row[f"max_err_{n}"] for n in ("fwd", "dq", "dk", "dv")) < 2e-2
    assert row["grid_steps"] == 2 and row["kv_blocks"] == 2


def test_best_blocks_keyed_by_the_whole_shape():
    """The sweep reports the best (blk_q, blk_k) a SHAPE (batch, heads,
    length, head widths, mask, dropout) in its summary; what the kernels
    compile comes from the package alone (`_block_sizes`, a rule of the
    shape, + block_override), never from a file a sweep left in the
    checkout."""
    from paddle_tpu.ops.pallas import flash_attention as fa

    def row(bq, bk, ms, **kw):
        return dict(dict(batch=1, heads=40, seq_len=4096, head_dim=64,
                         v_dim=128, causal=True, window=0, bias=False,
                         dropout=0.0, blk_q=bq, blk_k=bk, fwdbwd_ms=ms,
                         status="ok", tflops_fwd=1.0, fwd_ms=ms / 3), **kw)
    rows = [
        row(128, 128, 5.0), row(512, 1024, 3.0),
        row(1024, 1024, 1.0, status="parity_fail"),         # not ok: skip
        row(128, 128, 2.0, window=512), row(256, 256, 1.5, window=512),
        row(512, 512, 9.0, batch=32, heads=12, seq_len=512, v_dim=64,
            causal=False, bias=True, dropout=0.1),
    ]
    best = flash_smoke.best_blocks(rows)
    assert best == {"1:40:4096:64:128:1:0:0:0.0": [512, 1024],
                    "1:40:4096:64:128:1:512:0:0.0": [256, 256],
                    "32:12:512:64:64:0:0:1:0.1": [512, 512]}
    assert flash_smoke.summarize(rows, "tpu")["best_blocks"] == best
    assert not hasattr(fa, "_tuned_blocks")


# ------------------------------------------- the chooser's contract (PR 33)
def _fa():
    from paddle_tpu.ops.pallas import flash_attention as fa
    return fa


def _legal(block, length):
    """Mosaic's rule for a block's sequence axis at ANY head width: a
    multiple of 128 (it is the bias block's lane axis and, being one,
    also a multiple of the 8 sublanes), or the whole dimension."""
    return block == length or (block % 128 == 0 and block < length)


KERNELS = ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq")
# the attention calls of the benchmark's cells: (S, D, Dv, causal, window,
# key-padding bias) -> the blocks committed a kernel (PERF.md §6, PR 33)
CELL_BLOCKS = {
    "bert_s512_pad": ((512, 64, 64, False, 0, True), [(512, 512)] * 3),
    "phi_causal": ((4096, 64, 128, True, 0, False), [(1024, 1024)] * 3),
    "phi_w512": ((4096, 64, 128, True, 512, False), [(512, 512)] * 3),
    "qwen_d256": ((4096, 256, 256, True, 0, False), [(1024, 1024)] * 3),
}


def _chosen(kernel, S, D, Dv, causal, window, bias, Sk=None, itemsize=2):
    fa = _fa()
    mask = fa.Mask(causal, window, np.zeros((1, Sk or S)) if bias else None)
    return fa._block_sizes(kernel, S, Sk or S, D, Dv, mask, itemsize)


@pytest.mark.parametrize("cell", sorted(CELL_BLOCKS))
def test_the_cells_calls_get_the_committed_blocks(cell):
    """The pairs the sweep on the chip found fastest a kernel, or within
    15% of it (the window call's forward: 1024 x 1024 reads 1.43 ms, the
    512 x 512 chosen 1.63)."""
    shape, want = CELL_BLOCKS[cell]
    assert [_chosen(k, *shape) for k in KERNELS] == want


def test_kernels_of_one_call_may_get_different_pairs():
    """dK/dV holds two accumulators and two more blocks than the others:
    where the budget cuts its pair first, the three differ."""
    pairs = [_chosen(k, 32768, 128, 128, True, 0, False) for k in KERNELS]
    assert pairs == [(1024, 2048), (1024, 1024), (1024, 2048)]


@pytest.mark.parametrize("S,Sk", [(64, 100), (100, 100), (3, 5), (130, 75),
                                  (100, 256), (1900, 1900), (500, 2000),
                                  (381, 381)])
@pytest.mark.parametrize("kernel", KERNELS)
def test_short_and_ragged_lengths_take_the_exact_dimension(kernel, S, Sk):
    """A length under the smallest candidate is its own block; a longer
    one that no candidate divides gets a legal block (the boundary block
    is masked in-kernel) or, where a candidate passes it, itself."""
    bq, bk = _chosen(kernel, S, 64, 64, False, 0, False, Sk=Sk)
    assert _legal(bq, S) and _legal(bk, Sk)
    if S < 128:
        assert bq == S
    if Sk < 128:
        assert bk == Sk


@pytest.mark.parametrize("kernel", KERNELS)
def test_a_window_bounds_the_blocks(kernel):
    """Blocks much longer than the window compute scores the mask throws
    away: under a window the pair is never larger than the causal call's
    of the same shape, it shrinks with the window, and at Phi's w = 512
    it is smaller than the causal pair."""
    fa = _fa()
    causal = _chosen(kernel, 4096, 64, 128, True, 0, False)
    areas = []
    for window in (4096, 2048, 512, 128):
        bq, bk = _chosen(kernel, 4096, 64, 128, True, window, False)
        assert bq <= causal[0] and bk <= causal[1]
        areas.append(bq * bk)
        # what the pair computes stays within four times what is kept
        # (128 x 128, the smallest pair, computes twice at w = 128)
        computed = fa.visited_blocks(4096, 4096, bq, bk,
                                     fa.Mask(True, window)) * bq * bk
        kept = sum(min(t + 1, window) for t in range(4096))
        assert computed <= 4 * kept, (window, bq, bk)
    assert areas == sorted(areas, reverse=True)
    w512 = _chosen(kernel, 4096, 64, 128, True, 512, False)
    assert w512[0] * w512[1] < causal[0] * causal[1]


@pytest.mark.parametrize("itemsize", [2, 4], ids=["bf16", "f32"])
@pytest.mark.parametrize("D,Dv", [(16, 16), (64, 64), (64, 128), (128, 128),
                                  (256, 256), (512, 512)])
@pytest.mark.parametrize("S,window", [(512, 0), (4096, 0), (4096, 512),
                                      (32768, 0), (32768, 4096), (1000, 0)])
def test_every_choice_is_legal_and_inside_the_budget(S, window, D, Dv,
                                                     itemsize):
    """A shape the sweep did not cover falls to the rule's budget, not to
    a table miss: whatever the head width, item size and length, each
    kernel's pair is Mosaic-legal and its working set fits."""
    fa = _fa()
    for kernel in KERNELS:
        for bias in (False, True):
            bq, bk = _chosen(kernel, S, D, Dv, True, window, bias,
                             itemsize=itemsize)
            assert _legal(bq, S) and _legal(bk, S)
            assert fa._working_set(kernel, bq, bk, D, Dv, itemsize,
                                   bias) <= fa.VMEM_BUDGET


def test_block_override_still_wins_and_nothing_else_decides():
    """`block_override` pins all three kernels (the sweep and the tests);
    outside it the choice is `_block_sizes`' alone: a function of the
    call's shape, with no flag, environment variable or file behind it
    and no fixed block left for a caller to import."""
    import inspect
    fa = _fa()
    free = [_chosen(k, 512, 64, 64, False, 0, True) for k in KERNELS]
    with fa.block_override(256, 512):
        for kernel in KERNELS:
            assert _chosen(kernel, 512, 64, 64, False, 0, True) == (256, 512)
            assert _chosen(kernel, 64, 64, 64, False, 0, False) == (64, 64)
            assert _chosen(kernel, 4096, 256, 256, True, 512,
                           False) == (256, 512)
    assert [_chosen(k, 512, 64, 64, False, 0, True) for k in KERNELS] == free
    assert not hasattr(fa, "DEFAULT_BLOCK_Q") \
        and not hasattr(fa, "DEFAULT_BLOCK_K")
    source = inspect.getsource(fa._block_sizes) \
        + inspect.getsource(fa._cost_ns) + inspect.getsource(fa._working_set)
    for word in ("environ", "FLAGS", "globals_", "open(", "attrs"):
        assert word not in source, word
