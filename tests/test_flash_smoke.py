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


def test_run_config_dropout_deterministic():
    row = flash_smoke.run_config(128, 64, 64, B=1, H=2, steps=2,
                                 dropout=0.1, interpret=True)
    assert row["status"] == "ok", row
    assert row["dropout_deterministic"] is True


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


def test_vmem_estimate_monotone_in_blocks():
    a = flash_smoke._vmem_kb_estimate(128, 128, 64, bwd=True)
    b = flash_smoke._vmem_kb_estimate(512, 512, 64, bwd=True)
    assert b > a > 0


def test_best_blocks_reported_and_kernel_blocks_from_tracked_code():
    """The sweep reports the best (blk_q, blk_k) per seq len and head
    dim in its summary; what the kernel compiles comes from the package
    alone (defaults + block_override), never from a file a sweep left
    in the checkout."""
    from paddle_tpu.ops.pallas import flash_attention as fa

    rows = [
        {"seq_len": 512, "blk_q": 128, "blk_k": 128, "fwdbwd_ms": 5.0,
         "head_dim": 64, "status": "ok", "causal": False, "dropout": 0.0,
         "tflops_fwd": 1.0, "fwd_ms": 2.0},
        {"seq_len": 512, "blk_q": 256, "blk_k": 128, "fwdbwd_ms": 3.0,
         "head_dim": 64, "status": "ok", "causal": False, "dropout": 0.0,
         "tflops_fwd": 2.0, "fwd_ms": 1.0},
        {"seq_len": 512, "blk_q": 512, "blk_k": 512, "fwdbwd_ms": 1.0,
         "head_dim": 64, "status": "ok", "causal": True,
         "dropout": 0.0},  # causal: skip
        {"seq_len": 2048, "blk_q": 512, "blk_k": 256, "fwdbwd_ms": 9.0,
         "head_dim": 64, "status": "ok", "causal": False, "dropout": 0.0,
         "tflops_fwd": 1.5, "fwd_ms": 3.0},
    ]
    best = flash_smoke.best_blocks(rows)
    assert best == {"512:64": [256, 128], "2048:64": [512, 256]}
    assert flash_smoke.summarize(rows, "tpu")["best_blocks"] == best

    assert fa._block_sizes(512, 512) == (128, 128)
    assert fa._block_sizes(1900, 1900) == (128, 128)
    assert fa._block_sizes(64, 100) == (64, 100)  # small: exact
    with fa.block_override(256, 512):
        assert fa._block_sizes(512, 512) == (256, 512)
        assert fa._block_sizes(64, 64) == (64, 64)
    assert fa._block_sizes(512, 512) == (128, 128)
    assert not hasattr(fa, "_tuned_blocks")
