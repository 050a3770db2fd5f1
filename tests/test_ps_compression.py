"""Compressed PS data plane (docs/PS_DATA_PLANE.md "Compression").

Covers the three legs of the compression plane plus its contracts:
  * wire v3 quantized frames — fp16/int8 round-trip error bounds,
    hello negotiation compat BOTH directions (quant peer ↔ pre-quant
    peer always exchanges exact frames), dedup-token replay of a
    quantized frame (retry re-sends the exact quantized bytes), and
    the dequant-on-receive → FLAGS_ps_reject_nonfinite interaction;
  * DGC top-k dense grads — the error-feedback invariant (everything
    sent plus the residual equals the true accumulated gradient), the
    warm-up sparsity ramp, and the dgc_send server apply;
  * replica-chain regression — a quantized/DGC push chain-forwarded to
    a PR 6 warm standby keeps the replica bit-identical to the primary
    (the chain forwards the DECODED apply, never the compressed frame);
  * the geo async WAN lane — delta rounds riding the geo RoundPipeline
    under injected RTT, and the multiprocess 2-region acceptance
    scenario (slow): geo+DGC+int8 ≥5× plain-sync throughput at 50ms
    injected delay, converging to the sync oracle's loss neighborhood.
"""
import json
import os
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import faultinject as FI

REPO = FI.REPO
WORKLOAD = os.path.join(REPO, "tests", "dist_ps_workload.py")

pytestmark = pytest.mark.wan


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


@pytest.fixture(autouse=True)
def _compression_isolation():
    """Every test starts with compression off, a fresh client pool, and
    a fresh DGC compressor; flags touched by tests are restored."""
    from paddle_tpu.fluid import communicator, core, ps_membership
    from paddle_tpu.fluid import ps_rpc
    from paddle_tpu.fluid.ps_rpc import VarClient

    saved = {k: core.globals_[k] for k in
             ("FLAGS_ps_wire_quant", "FLAGS_dgc", "FLAGS_dgc_sparsity",
              "FLAGS_dgc_momentum", "FLAGS_dgc_warmup_steps",
              "FLAGS_dgc_min_elements", "FLAGS_ps_reject_nonfinite",
              "FLAGS_ps_replicas", "FLAGS_async_staleness",
              "FLAGS_rpc_retry_times")}
    ps_membership.reset_views()
    yield
    ps_membership.reset_views()
    VarClient.reset_pool()
    communicator.reset_dgc()
    communicator.reset_geo_pipeline()
    ps_rpc.reset_quant_wire_stats()
    for k, v in saved.items():
        core.globals_[k] = v


# ==========================================================================
# quantization codec units
# ==========================================================================
def test_int8_roundtrip_error_bound():
    """Per-row absmax int8: |x - dequant(quant(x))| <= absmax_row/254
    (half a quantization step), zero rows exact, 1-D arrays treated as
    one row."""
    from paddle_tpu.fluid.ps_rpc import _dequant_int8, _quant_int8

    rng = np.random.RandomState(7)
    x = (rng.randn(64, 16) * rng.uniform(0.01, 100, (64, 1))).astype(
        np.float32)
    x[5] = 0.0  # all-zero row must stay exactly zero
    q, scale = _quant_int8(x)
    assert q.dtype == np.int8 and scale.dtype == np.float32
    back = _dequant_int8(q, scale, np.dtype(np.float32))
    bound = np.abs(x).max(axis=1, keepdims=True) / 254.0 + 1e-12
    assert (np.abs(back - x) <= bound).all()
    np.testing.assert_array_equal(back[5], np.zeros(16, np.float32))

    v = rng.randn(33).astype(np.float32)  # 1-D: one row
    qv, sv = _quant_int8(v)
    assert sv.shape == (1,)
    backv = _dequant_int8(qv, sv, np.dtype(np.float32))
    assert (np.abs(backv - v) <= np.abs(v).max() / 254.0 + 1e-12).all()


def test_fp16_quant_wire_roundtrip_error_bound():
    """fp16 frames: relative error <= 2^-11 + eps for values inside the
    fp16 normal range, measured through a real server round trip."""
    from paddle_tpu.fluid import core
    from paddle_tpu.fluid.ps_rpc import VarClient, VarServer

    store = {}
    srv = VarServer(f"127.0.0.1:{free_port()}",
                    {"send_var": lambda name, value, trainer_id=0,
                     rows=None, height=0:
                     store.__setitem__(name, np.asarray(value)) or True
                     }).start()
    try:
        core.set_flag("FLAGS_ps_wire_quant", "fp16")
        cli = VarClient(f"127.0.0.1:{srv.port}", channels=1)
        x = np.random.RandomState(0).randn(32, 8).astype(np.float32)
        cli.send_var("w", x)
        np.testing.assert_allclose(store["w"], x, rtol=2 ** -11 + 1e-4)
        assert store["w"].dtype == np.float32
        cli.close()
    finally:
        srv.shutdown()


def test_int8_wire_end_to_end_counters_and_both_directions():
    """int8 frames through a real server: the pushed value lands within
    the per-row bound, the PULL response is quantized too (server-side
    flag, same connection), and the ps_wire bytes counters record the
    savings."""
    from paddle_tpu.fluid import core
    from paddle_tpu.fluid import ps_rpc
    from paddle_tpu.fluid.ps_rpc import (PROTO_BINARY_Q, VarClient,
                                         VarServer, quant_wire_stats)

    store = {}
    srv = VarServer(f"127.0.0.1:{free_port()}",
                    {"send_var": lambda name, value, trainer_id=0,
                     rows=None, height=0:
                     store.__setitem__(name, np.asarray(value)) or True,
                     "get_var": lambda name, trainer_id=0: store[name]
                     }).start()
    try:
        ps_rpc.reset_quant_wire_stats()
        core.set_flag("FLAGS_ps_wire_quant", "int8")
        cli = VarClient(f"127.0.0.1:{srv.port}", channels=1)
        assert cli._channels[0].proto == PROTO_BINARY_Q
        x = np.random.RandomState(1).randn(128, 16).astype(np.float32)
        cli.send_var("w", x)
        bound = np.abs(x).max(axis=1, keepdims=True) / 254.0 + 1e-12
        assert (np.abs(store["w"] - x) <= bound).all()
        # the pull response quantizes against the SERVER-side stored
        # value — one more half-step of error at most
        back = np.asarray(cli.get_var("w"))
        b2 = np.abs(store["w"]).max(axis=1, keepdims=True) / 254.0
        assert (np.abs(back - store["w"]) <= b2 + 1e-12).all()
        qs = quant_wire_stats()
        assert qs["frames_quantized_total"] >= 2  # push + pull response
        assert 0 < qs["bytes_sent_total"] < qs["bytes_raw_total"]
        # int8 + f32 scale per 16-wide row = (16 + 4)/64 of raw
        assert qs["bytes_raw_total"] / qs["bytes_sent_total"] > 3.0
        cli.close()
    finally:
        srv.shutdown()


def test_int8_nonfinite_payload_ships_raw():
    """A non-finite float32 array must NOT int8-quantize (rint(NaN) is
    undefined in int8) — it ships raw so the receiving guard sees the
    poison exactly."""
    from paddle_tpu.fluid import core
    from paddle_tpu.fluid.ps_rpc import VarClient, VarServer

    store = {}
    srv = VarServer(f"127.0.0.1:{free_port()}",
                    {"send_var": lambda name, value, trainer_id=0,
                     rows=None, height=0:
                     store.__setitem__(name, np.asarray(value)) or True
                     }).start()
    try:
        core.set_flag("FLAGS_ps_wire_quant", "int8")
        cli = VarClient(f"127.0.0.1:{srv.port}", channels=1)
        x = np.arange(64, dtype=np.float32).reshape(8, 8)
        x[3, 4] = np.nan
        x[6, 1] = np.inf
        cli.send_var("w", x)
        np.testing.assert_array_equal(store["w"], x)  # exact, poison too
        cli.close()
    finally:
        srv.shutdown()


# ==========================================================================
# wire-generation compat — quant peer ↔ pre-quant peer, both directions
# ==========================================================================
def test_quant_client_against_v2_and_legacy_servers_stays_exact():
    """A quant-flagged client negotiating with a pre-quant (v2-capped)
    server — and with a legacy v1 server — must deliver EXACT values:
    the hello settles on the lower generation and no quantized spec
    ever crosses the link."""
    from paddle_tpu.fluid import core
    from paddle_tpu.fluid.ps_rpc import (PROTO_BINARY, PROTO_PICKLE,
                                         VarClient, VarServer)

    core.set_flag("FLAGS_ps_wire_quant", "int8")
    x = np.random.RandomState(2).randn(32, 8).astype(np.float32)
    store = {}

    def h(name, value, trainer_id=0, rows=None, height=0):
        store[name] = np.asarray(value)
        return True

    v2 = VarServer(f"127.0.0.1:{free_port()}", {"send_var": h},
                   wire_version=2).start()
    leg = VarServer(f"127.0.0.1:{free_port()}", {"send_var": h},
                    legacy_wire=True).start()
    try:
        c2 = VarClient(f"127.0.0.1:{v2.port}", channels=1)
        assert c2._channels[0].proto == PROTO_BINARY
        c2.send_var("v2", x)
        np.testing.assert_array_equal(store["v2"], x)
        c1 = VarClient(f"127.0.0.1:{leg.port}", channels=1)
        assert c1._channels[0].proto == PROTO_PICKLE
        c1.send_var("v1", x)
        np.testing.assert_array_equal(store["v1"], x)
        c2.close()
        c1.close()
    finally:
        v2.shutdown()
        leg.shutdown()


def test_prequant_client_against_quant_server_stays_exact():
    """The reverse direction: a pre-quant client (v2-capped hello, and
    the full-legacy pickle lane) against a server whose quant flag is
    ON must still receive exact pull responses — response quantization
    is gated on the NEGOTIATED generation, not the flag alone."""
    from paddle_tpu.fluid import core
    from paddle_tpu.fluid.ps_rpc import (PROTO_BINARY, PROTO_PICKLE,
                                         VarClient, VarServer)

    x = np.random.RandomState(3).randn(16, 8).astype(np.float32)
    srv = VarServer(f"127.0.0.1:{free_port()}",
                    {"get_var": lambda name, trainer_id=0: x}).start()
    ep = f"127.0.0.1:{srv.port}"
    try:
        core.set_flag("FLAGS_ps_wire_quant", "int8")
        old_cli = VarClient(ep, channels=1, wire_version=2)
        assert old_cli._channels[0].proto == PROTO_BINARY
        np.testing.assert_array_equal(np.asarray(old_cli.get_var("w")), x)
        old_cli.close()
        os.environ["PADDLE_TPU_PS_PICKLE_WIRE"] = "1"
        try:
            pick_cli = VarClient(ep, channels=1)
            assert pick_cli._channels[0].proto == PROTO_PICKLE
            np.testing.assert_array_equal(
                np.asarray(pick_cli.get_var("w")), x)
            pick_cli.close()
        finally:
            os.environ.pop("PADDLE_TPU_PS_PICKLE_WIRE", None)
        # sanity: a CURRENT client on the same server IS quantized
        new_cli = VarClient(ep, channels=1)
        got = np.asarray(new_cli.get_var("w"))
        assert not np.array_equal(got, x)  # lossy — proves the gate
        assert (np.abs(got - x)
                <= np.abs(x).max(axis=1, keepdims=True) / 254.0
                + 1e-12).all()
        new_cli.close()
    finally:
        srv.shutdown()


def test_quantized_frame_dedup_retry_replays_verbatim():
    """A server death mid-call with quantization ON: the retry re-sends
    the CACHED quantized parts verbatim under the same dedup token —
    applied exactly once, and the applied value equals the local
    dequant(quant(x)) prediction bit-for-bit (no re-quantization on
    the retry path)."""
    from paddle_tpu.fluid import core
    from paddle_tpu.fluid.ps_rpc import (PROTO_BINARY_Q, VarClient,
                                         VarServer, _dequant_int8,
                                         _quant_int8)

    applied = []

    def h_send(name, value, trainer_id=0, rows=None, height=0):
        applied.append(np.asarray(value))
        return True

    core.set_flag("FLAGS_ps_wire_quant", "int8")
    port = free_port()
    ep = f"127.0.0.1:{port}"
    srv = VarServer(ep, {"send_var": h_send}).start()
    cli = VarClient(ep, channels=1)
    assert cli._channels[0].proto == PROTO_BINARY_Q
    srv2 = None
    try:
        # sever the negotiated connection server-side, like a crash —
        # the in-flight/next frame dies mid-stream
        srv.shutdown()
        srv2 = VarServer(ep, {"send_var": h_send}).start()
        big = np.random.RandomState(4).randn(1 << 12, 16).astype(
            np.float32)
        assert cli.send_var("w", big) is True
        assert len(applied) == 1  # exactly once
        q, scale = _quant_int8(big)
        np.testing.assert_array_equal(
            applied[0], _dequant_int8(q, scale, np.dtype(np.float32)))
        assert cli._channels[0].proto == PROTO_BINARY_Q
        assert srv2.stats()["send_var"]["calls"] == 1
        cli.close()
    finally:
        for s in (srv, srv2):
            try:
                if s is not None:
                    s.shutdown()
            except Exception:
                pass


# ==========================================================================
# dequant-on-receive feeds the pserver non-finite guard
# ==========================================================================
def _start_listen_and_serv(sync=False, fanin=1):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import core

    main = fluid.Program()
    ep = f"127.0.0.1:{free_port()}"
    with fluid.program_guard(main, fluid.Program()):
        main.global_block().append_op(
            type="listen_and_serv", inputs={}, outputs={},
            attrs={"endpoint": ep, "sync_mode": sync, "Fanin": fanin,
                   "optimize_blocks": [], "grad_to_block_id": []})
    scope = core.Scope()
    exe = fluid.Executor()
    th = threading.Thread(
        target=lambda: exe.run(main, scope=scope, feed={},
                               fetch_list=[]), daemon=True)
    th.start()
    return ep, th, scope


def _stop_listen_and_serv(ep, th):
    from paddle_tpu.fluid.ps_rpc import VarClient
    try:
        c = VarClient(ep, connect_timeout=5.0, channels=1, resolve=False)
        c.stop()
        c.close()
    except Exception:
        pass
    th.join(timeout=10)


def test_fp16_overflow_hits_server_nonfinite_reject():
    """An fp16-quantized value beyond the fp16 range arrives as Inf
    after dequant-on-receive — and the pserver's
    FLAGS_ps_reject_nonfinite=reject guard refuses it TYPED back to the
    sender. Quantization cannot smuggle poison past the guard."""
    from paddle_tpu.fluid import core
    from paddle_tpu.fluid.ps_rpc import VarClient

    core.set_flag("FLAGS_ps_wire_quant", "fp16")
    core.set_flag("FLAGS_ps_reject_nonfinite", "reject")
    ep, th, _scope = _start_listen_and_serv()
    try:
        cli = VarClient(ep, channels=1)
        big = np.full((4, 4), 1e38, np.float32)  # fp16 range: ±65504
        with pytest.raises(core.NumericFaultError):
            cli.send_var("w", big)
        # the server is intact and still serving exact-frame traffic
        core.set_flag("FLAGS_ps_wire_quant", "")
        ok = np.ones((2, 2), np.float32)
        assert cli.send_var("w2", ok) is True
        np.testing.assert_array_equal(
            np.asarray(cli.get_var("w2")), ok)
        cli.close()
    finally:
        core.set_flag("FLAGS_ps_reject_nonfinite", "")
        _stop_listen_and_serv(ep, th)


# ==========================================================================
# DGC — error feedback, warm-up, server apply
# ==========================================================================
def test_dgc_error_feedback_sum_invariant():
    """The DGC contract: after any number of compressed pushes, the
    scatter-sum of everything SENT plus the residual accumulator equals
    the sum of the true gradients (momentum 0 — pure error feedback)."""
    from paddle_tpu.fluid import core
    from paddle_tpu.fluid.communicator import DGCCompressor

    core.set_flag("FLAGS_dgc_min_elements", 1)
    core.set_flag("FLAGS_dgc_momentum", 0.0)
    core.set_flag("FLAGS_dgc_sparsity", 0.9)
    core.set_flag("FLAGS_dgc_warmup_steps", 0)
    comp = DGCCompressor()
    rng = np.random.RandomState(11)
    n = 400
    true_sum = np.zeros(n, np.float64)
    sent_sum = np.zeros(n, np.float64)
    for _ in range(13):
        g = rng.randn(n).astype(np.float32)
        true_sum += g.astype(np.float64)
        idx, vals = comp.compress("w@GRAD", g)
        assert idx.size == max(1, round(n * 0.1))
        np.add.at(sent_sum, idx, vals.astype(np.float64))
    residual = comp.residual("w@GRAD").astype(np.float64)
    np.testing.assert_allclose(sent_sum + residual, true_sum,
                               rtol=1e-5, atol=1e-5)
    st = comp.stats()
    assert st["compression_ratio"] == pytest.approx(10.0, rel=0.05)


def test_dgc_warmup_ramps_sparsity_and_momentum_masks():
    """Warm-up sends MORE early: the per-push selection shrinks toward
    the final sparsity over FLAGS_dgc_warmup_steps; and with momentum
    on, selected entries zero BOTH u and v (factor masking)."""
    from paddle_tpu.fluid import core
    from paddle_tpu.fluid.communicator import DGCCompressor

    core.set_flag("FLAGS_dgc_min_elements", 1)
    core.set_flag("FLAGS_dgc_sparsity", 0.99)
    core.set_flag("FLAGS_dgc_warmup_steps", 4)
    core.set_flag("FLAGS_dgc_momentum", 0.9)
    comp = DGCCompressor()
    rng = np.random.RandomState(5)
    n = 1000
    sizes = []
    for _ in range(6):
        idx, _vals = comp.compress("g", rng.randn(n).astype(np.float32))
        sizes.append(idx.size)
    # monotonically non-increasing toward the final 1% selection
    assert sizes == sorted(sizes, reverse=True)
    assert sizes[0] > sizes[-1]
    assert sizes[-1] == max(1, round(n * 0.01))
    # sub-threshold and non-f32 grads ship dense
    core.set_flag("FLAGS_dgc_min_elements", 512)
    assert comp.compress("tiny", np.ones(4, np.float32)) is None
    assert comp.compress("ints", np.ones(1024, np.int64)) is None


def test_dgc_send_reconstructs_dense_apply_on_server():
    """h_dgc_send against the real listen_and_serv: the (indices,
    values) frame lands as the scattered dense value — identical to
    what a dense send of the scatter would have produced."""
    from paddle_tpu.fluid.ps_rpc import VarClient

    ep, th, _scope = _start_listen_and_serv()
    try:
        cli = VarClient(ep, channels=1)
        shape = [8, 4]
        idx = np.asarray([0, 5, 17, 31], np.int64)
        vals = np.asarray([1.5, -2.0, 3.25, 0.5], np.float32)
        assert cli.call("dgc_send", name="g", values=vals, indices=idx,
                        shape=shape, trainer_id=0) is True
        want = np.zeros(32, np.float32)
        want[idx] = vals
        np.testing.assert_array_equal(
            np.asarray(cli.get_var("g")), want.reshape(8, 4))
        cli.close()
    finally:
        _stop_listen_and_serv(ep, th)


def test_push_dense_batch_compresses_and_falls_back_dense():
    """_push_dense_batch: with FLAGS_dgc on, an eligible grad rides
    dgc_send (server var == top-k scatter, residual holds the rest);
    against a server WITHOUT dgc_send the full accumulated grad ships
    dense — nothing lost, nothing double-sent, miss memoized."""
    from paddle_tpu.fluid import communicator, core
    from paddle_tpu.fluid.ps_rpc import VarClient, VarServer
    from paddle_tpu.ops.distributed_ops import _push_dense_batch

    core.set_flag("FLAGS_dgc", True)
    core.set_flag("FLAGS_dgc_min_elements", 1)
    core.set_flag("FLAGS_dgc_momentum", 0.0)
    core.set_flag("FLAGS_dgc_sparsity", 0.75)
    core.set_flag("FLAGS_dgc_warmup_steps", 0)

    ep, th, _scope = _start_listen_and_serv()
    try:
        g = np.random.RandomState(6).randn(10, 10).astype(np.float32)
        _push_dense_batch(ep, [("g@GRAD", g)], 0)
        comp = communicator.dgc_compressor()
        res = comp.residual("g@GRAD").reshape(10, 10)
        cli = VarClient.of(ep)
        got = np.asarray(cli.get_var("g@GRAD"))
        # sent + residual == g, and the sent part is the top-25%
        np.testing.assert_allclose(got + res, g, rtol=1e-6, atol=1e-7)
        assert (got != 0).sum() == 25
    finally:
        _stop_listen_and_serv(ep, th)

    # old server: no dgc_send handler anywhere in the handler map
    applied = []
    old = VarServer(f"127.0.0.1:{free_port()}",
                    {"send_var": lambda name, value, trainer_id=0,
                     rows=None, height=0:
                     applied.append(np.asarray(value)) or True}).start()
    try:
        from paddle_tpu.fluid import communicator
        comp = communicator.dgc_compressor()
        ep2 = f"127.0.0.1:{old.port}"
        g2 = np.random.RandomState(7).randn(8, 8).astype(np.float32)
        _push_dense_batch(ep2, [("h@GRAD", g2)], 0)
        (dense,) = applied
        # the fallback shipped the FULL accumulated grad, residual zero
        np.testing.assert_allclose(dense, g2, rtol=1e-6, atol=1e-7)
        np.testing.assert_array_equal(
            comp.residual("h@GRAD"), np.zeros(64, np.float32))
        assert "dgc_send" in VarClient.of(ep2)._missing_methods
    finally:
        old.shutdown()


# ==========================================================================
# replica-chain regression: compressed pushes keep the standby
# bit-identical (forward the decoded apply, not the compressed frame)
# ==========================================================================
def _start_pserver_thread(endpoint, bind="", standby=False,
                          replica_map=None, replica_of=""):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import core

    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        main.global_block().append_op(
            type="listen_and_serv", inputs={}, outputs={},
            attrs={"endpoint": endpoint, "sync_mode": False, "Fanin": 1,
                   "optimize_blocks": [], "grad_to_block_id": [],
                   "pserver_endpoints": [endpoint],
                   "bind_endpoint": bind, "standby": standby,
                   "replica_of": replica_of})
    scope = core.Scope()
    exe = fluid.Executor()
    th = threading.Thread(
        target=lambda: exe.run(main, scope=scope, feed={},
                               fetch_list=[]), daemon=True)
    th.start()
    return th, scope


def test_replica_chain_stays_bit_identical_under_quant_and_dgc(
        monkeypatch):
    """FLAGS_ps_replicas=2 with int8 wire quant AND DGC pushes: every
    apply the primary runs chain-forwards the DECODED values, so the
    warm standby's state is bit-identical to the primary's — the
    regression that would catch forwarding the compressed frame (a
    re-quantized forward drifts by a quantization step)."""
    from paddle_tpu.fluid import core, ps_membership
    from paddle_tpu.fluid.ps_rpc import VarClient

    slot = f"127.0.0.1:{free_port()}"
    rep = f"127.0.0.1:{free_port()}"
    monkeypatch.setenv("PADDLE_PS_REPLICA_MAP", f"{slot}={rep}")
    core.set_flag("FLAGS_ps_replicas", 2)
    core.set_flag("FLAGS_ps_wire_quant", "int8")
    core.set_flag("FLAGS_dgc", True)
    core.set_flag("FLAGS_dgc_min_elements", 1)
    core.set_flag("FLAGS_dgc_sparsity", 0.5)
    ps_membership.reset_views()

    th_p, scope_p = _start_pserver_thread(slot)
    th_r, scope_r = _start_pserver_thread(slot, bind=rep, standby=True,
                                          replica_of=slot)
    try:
        from paddle_tpu.ops.distributed_ops import _push_dense_batch
        cli = VarClient(slot, connect_timeout=30.0, channels=1)
        rng = np.random.RandomState(8)
        # host the table first (dense send), then a quantized sparse
        # row push applies row-wise SGD onto it on both ends
        cli.send_var("emb", np.ones((12, 6), np.float32))
        rows = np.asarray([1, 3, 9], np.int64)
        vals = rng.randn(3, 6).astype(np.float32) * 3.7
        cli.send_var("emb@GRAD", vals, rows=rows, height=0)
        # quantized dense push + DGC'd dense push
        cli.send_var("dense", rng.randn(5, 5).astype(np.float32))
        _push_dense_batch(slot, [("g@GRAD",
                                  rng.randn(6, 6).astype(np.float32))],
                          0)
        # geo delta (flat + row forms)
        cli.call("geo_delta", name="dense",
                 value=rng.randn(5, 5).astype(np.float32))
        deadline = time.time() + 10
        names = ["emb", "dense", "g@GRAD"]
        while time.time() < deadline:
            if all(scope_r.find_var(n) is not None
                   and scope_r.find_var(n).is_initialized()
                   for n in names):
                break
            time.sleep(0.05)
        for n in names:
            pv = np.asarray(scope_p.find_var(n).value().array)
            rv = np.asarray(scope_r.find_var(n).value().array)
            np.testing.assert_array_equal(pv, rv), n
        cli.close()
    finally:
        for ep, th in ((rep, th_r), (slot, th_p)):
            _stop_listen_and_serv(ep, th)


# ==========================================================================
# geo async WAN lane — in-process unit
# ==========================================================================
def test_geo_async_rounds_converge_under_injected_delay():
    """Single-region in-process unit of the WAN lane: geo training with
    FLAGS_async_staleness=2 + DGC + int8 quant under a 30ms injected
    server delay still converges, the geo RoundPipeline carries the
    delta rounds, and the local steps never block on the full RTT (the
    loop finishes far faster than steps × RTT would allow)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import communicator, core
    from paddle_tpu.fluid.communicator import drain_async_rounds
    from paddle_tpu.fluid.transpiler import (DistributeTranspiler,
                                             DistributeTranspilerConfig)

    # build the linear workload's geo trainer program against one
    # in-process pserver
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", shape=[4], dtype="float32")
        y = fluid.data("y", shape=[1], dtype="float32")
        pred = fluid.layers.fc(x, 1,
                               param_attr=fluid.ParamAttr(name="w"),
                               bias_attr=fluid.ParamAttr(name="b"))
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(0.1).minimize(loss)
    ps_ep = f"127.0.0.1:{free_port()}"
    cfg = DistributeTranspilerConfig()
    cfg.geo_sgd_mode = True
    cfg.geo_sgd_need_push_nums = 4
    t = DistributeTranspiler(cfg)
    with fluid.program_guard(main, startup):
        t.transpile(trainer_id=0, pservers=ps_ep, trainers=1,
                    sync_mode=False, program=main,
                    startup_program=startup)
    pprog = t.get_pserver_program(ps_ep)
    pstart = t.get_startup_program(ps_ep, pprog)

    from paddle_tpu.fluid import core as _core
    ps_scope = _core.Scope()
    ps_exe = fluid.Executor()

    def _serve():
        with fluid.scope_guard(ps_scope):
            ps_exe.run(pstart)
            ps_exe.run(pprog)

    th = threading.Thread(target=_serve, daemon=True)
    th.start()

    core.set_flag("FLAGS_async_staleness", 2)
    core.set_flag("FLAGS_dgc", True)
    core.set_flag("FLAGS_dgc_min_elements", 1)
    core.set_flag("FLAGS_dgc_sparsity", 0.5)
    core.set_flag("FLAGS_ps_wire_quant", "int8")
    rng = np.random.RandomState(7)
    X = rng.rand(8, 4).astype("float32")
    Y = (X @ np.array([[1.0], [-2.0], [0.5], [3.0]], np.float32)
         + 0.25)
    exe = fluid.Executor()
    scope = core.Scope()
    losses = []
    try:
        with FI.rpc_delay(30, jitter_ms=5):
            with fluid.scope_guard(scope):
                exe.run(startup)
                prog = t.get_trainer_program()
                t0 = time.perf_counter()
                steps = 44
                for _ in range(steps):
                    (lv,) = exe.run(prog, feed={"x": X, "y": Y},
                                    fetch_list=[loss])
                    losses.append(float(np.asarray(lv).reshape(-1)[0]))
                drain_async_rounds()
                dt = time.perf_counter() - t0
        assert losses[-1] < losses[0] * 0.25, losses
        pipe = communicator.active_geo_pipeline()
        assert pipe is not None
        st = pipe.stats()
        assert st["rounds_submitted"] >= 4
        assert st["rounds_submitted"] == st["rounds_acked"]
        # loose sanity bound: the loop must not have serialized every
        # sync point's delayed RPC chain into the steps (CI-safe)
        assert dt < 5.0, dt
        dgc = communicator.active_dgc_stats()
        assert dgc.get("pushes_total", 0) >= 4
    finally:
        core.set_flag("FLAGS_async_staleness", 0)
        _stop_listen_and_serv(ps_ep, th)


# ==========================================================================
# multiprocess 2-region WAN acceptance (slow)
# ==========================================================================
def _run_wan_cluster(tmpdir, tag, steps, env_extra, geo):
    env = dict(os.environ, PYTHONPATH=REPO + os.pathsep +
               os.environ.get("PYTHONPATH", ""), JAX_PLATFORMS="cpu",
               **{k: str(v) for k, v in env_extra.items()})
    ep = f"127.0.0.1:{free_port()}"
    # --sparse gives both lanes a real embedding table: geo row-delta
    # pushes are wide enough to clear the int8 profitability floor
    # (the toy dense params are 1-4 floats — correctly shipped raw)
    flags = (["--geo"] if geo else []) + ["--timing", "--sparse",
                                          "--emb-dim=16"]
    procs, outs = [], []
    ps_out = os.path.join(tmpdir, f"{tag}_ps.ready")
    logp = os.path.join(tmpdir, f"{tag}_ps.log")
    ps = subprocess.Popen(
        [sys.executable, WORKLOAD, "pserver", ep, "0", "2", str(steps),
         ps_out] + flags, env=env, stdout=open(logp, "wb"),
        stderr=subprocess.STDOUT)
    procs.append(ps)
    deadline = time.time() + 90
    while not os.path.exists(ps_out):
        assert ps.poll() is None, open(logp).read()[-3000:]
        assert time.time() < deadline, "pserver never became ready"
        time.sleep(0.2)
    for tid in range(2):
        out = os.path.join(tmpdir, f"{tag}_t{tid}.json")
        outs.append(out)
        procs.append(subprocess.Popen(
            [sys.executable, WORKLOAD, "trainer", ep, str(tid), "2",
             str(steps), out] + flags, env=env,
            stdout=open(os.path.join(tmpdir, f"{tag}_t{tid}.log"), "wb"),
            stderr=subprocess.STDOUT))
    try:
        for p in procs[1:]:
            p.wait(timeout=300)
            assert p.returncode == 0, (
                tag, open(os.path.join(
                    tmpdir, f"{tag}_t{procs.index(p) - 1}.log")
                ).read()[-3000:])
        ps.wait(timeout=30)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    return [json.load(open(o)) for o in outs]


@pytest.mark.slow
def test_two_region_wan_geo_dgc_quant_5x_sync_throughput(tmp_path):
    """THE acceptance scenario (ISSUE 11): an emulated 2-region cluster
    — two trainer processes, one pserver, 50ms injected RTT with 10ms
    jitter on every data RPC — where geo-delta rounds + DGC top-k +
    int8 quantized frames reach ≥5× the per-step throughput of plain
    sync under the SAME delay, while converging into the sync oracle's
    loss neighborhood (the loss gap is asserted AND reported)."""
    wan = {"PADDLE_TPU_PS_RPC_DELAY_MS": 50,
           "PADDLE_TPU_PS_RPC_DELAY_JITTER_MS": 10}
    steps = 30
    sync_res = _run_wan_cluster(str(tmp_path), "sync", steps, wan,
                                geo=False)
    geo_res = _run_wan_cluster(
        str(tmp_path), "geo", steps,
        dict(wan, FLAGS_async_staleness=2, FLAGS_dgc=1,
             FLAGS_dgc_min_elements=1, FLAGS_ps_wire_quant="int8",
             PADDLE_TPU_GEO_PUSH_NUMS=10),
        geo=True)

    sync_sps = sum(r["steps"] / r["elapsed_s"] for r in sync_res)
    geo_sps = sum(r["steps"] / r["elapsed_s"] for r in geo_res)
    speedup = geo_sps / sync_sps
    sync_last = sync_res[0]["losses"][-1]
    geo_last = geo_res[0]["losses"][-1]
    loss_gap = geo_last - sync_last
    print(f"WAN 2-region: sync {sync_sps:.1f} steps/s, compressed geo "
          f"{geo_sps:.1f} steps/s → {speedup:.1f}x; loss sync={sync_last:.5f} "
          f"geo={geo_last:.5f} gap={loss_gap:+.5f}")
    assert speedup >= 5.0, (sync_sps, geo_sps)
    # both converge, and geo lands in (or below) the sync oracle's
    # loss neighborhood — one-sided: equal step counts favor geo's
    # LOCAL steps over sync's averaged ones, so geo finishing further
    # down is expected; what compression must never do is leave it
    # stranded ABOVE the oracle
    assert geo_last < geo_res[0]["losses"][0] * 0.5
    assert loss_gap <= max(0.05, 0.25 * abs(sync_last)), loss_gap
    # compression evidence crossed the wire: DGC sparsified pushes and
    # quantized frames saved bytes
    dgc = geo_res[0]["dgc"]
    assert dgc.get("pushes_total", 0) > 0
    assert dgc["elements_sent"] < dgc["elements_total"]
    quant = geo_res[0]["quant"]
    assert 0 < quant["bytes_sent_total"] < quant["bytes_raw_total"]


# ==========================================================================
# thin-pipe microbench acceptance: int8 ≥2× effective MB/s at ≥1MB
# ==========================================================================
@pytest.mark.slow
def test_int8_frames_2x_effective_throughput_on_thin_pipe():
    """Wire microbench acceptance on the bandwidth-bound regime the
    compression plane targets: on an emulated 50 MB/s pipe
    (PADDLE_TPU_PS_RPC_BANDWIDTH_MBPS), int8 frames deliver ≥2× the
    raw-frame effective MB/s at ≥1MB payloads. (Raw loopback is
    CPU-bound at GB/s — the caveat lane; CPU, builder-run, not recorded.)"""
    from tools import rpc_microbench

    rows = rpc_microbench.run_quant(sizes=[1 << 20, 1 << 22],
                                    repeats=2, warmup=1,
                                    bandwidth_mbps=50)
    for r in rows:
        assert r["int8_speedup"] >= 2.0, rows
        assert r["int8_wire_ratio"] > 3.0, rows


@pytest.mark.rpcbench
def test_rpc_quant_microbench_smoke():
    """Tiny quant sweep smoke: all three modes measured, quantized
    modes record a real on-wire compression ratio."""
    from tools import rpc_microbench

    rows = rpc_microbench.run_quant(sizes=[1 << 16], repeats=1,
                                    warmup=1)
    (row,) = rows
    for key in ("raw_mb_s", "fp16_mb_s", "int8_mb_s"):
        assert row[key] > 0
    assert row["fp16_wire_ratio"] > 1.5
    assert row["int8_wire_ratio"] > 3.0
