"""PS capacity tier (docs/PS_DATA_PLANE.md "Capacity tier"): slab spill
to an mmap-backed CRC-stamped segment log with hot-set pinning, at-rest
fp16/int8 quantized rows (the PR 11 wire codec reused), frequency-gated
entry creation, decay-based shrink, and the streaming handoff/checkpoint
legs that never materialize a spilled table in RAM.

Marker: ``capacity`` (docs/ci.md). Everything here is in-process and
fast."""
import json
import os
import socket
import threading
import time
import tracemalloc

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import core, slab_spill
from tests import faultinject as FI

pytestmark = pytest.mark.capacity


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def _tiered(tmp_path, name="t", **kw):
    kw.setdefault("height", 100000)
    kw.setdefault("dim", 8)
    kw.setdefault("seed", 3)
    kw.setdefault("hot_rows", 48)
    kw.setdefault("spill_seg_rows", 32)
    return core.LazyEmbeddingTable(
        spill_path=str(tmp_path / f"{name}.slab"), **kw)


# ==========================================================================
# tier semantics
# ==========================================================================
def test_tiered_table_bit_identical_to_in_ram_oracle(tmp_path):
    """Raw-at-rest spill/promote churn is write-back-exact: a tiered
    table under a mixed get/apply stream serves bit-identical rows to
    the unbounded in-RAM oracle, while actually spilling."""
    oracle = core.LazyEmbeddingTable(height=100000, dim=8, seed=3)
    tbl = _tiered(tmp_path)
    rng = np.random.RandomState(0)
    for step in range(40):
        ids = rng.randint(0, 2000, size=64)
        np.testing.assert_array_equal(oracle.get_rows(ids),
                                      tbl.get_rows(ids))
        if step % 3 != 2:  # leave some promotes CLEAN (backing path)
            g = rng.randn(64, 8).astype(np.float32)
            oracle.apply_grad(ids, g, 0.1)
            tbl.apply_grad(ids, g, 0.1)
    ids = rng.randint(0, 2000, size=1024)
    np.testing.assert_array_equal(oracle.get_rows(ids),
                                  tbl.get_rows(ids))
    st = tbl.tier_stats()
    assert st["spilled_rows"] > 0 and st["resident_rows"] <= 48
    assert st["promoted_rows"] > 0 and st["spill_batches"] > 0
    # clean write-elision actually engaged (get-only churn is free)
    assert st["clean_evictions"] > 0


def test_unflagged_table_has_no_tier():
    tbl = core.LazyEmbeddingTable(height=1000, dim=4, seed=0)
    assert tbl._tier is None
    with pytest.raises(RuntimeError, match="shrink"):
        tbl.shrink()


def test_spill_tier_rejects_max_rows_combo(tmp_path):
    with pytest.raises(ValueError, match="cannot combine"):
        core.LazyEmbeddingTable(height=1000, dim=4, max_rows=10,
                                spill_path=str(tmp_path / "x.slab"),
                                hot_rows=5)
    # the gate-only tier never runs the max_rows eviction either —
    # accepting both would silently drop the RAM bound
    with pytest.raises(ValueError, match="cannot combine"):
        core.LazyEmbeddingTable(height=1000, dim=4, max_rows=10,
                                entry_threshold=3)


def test_cold_pull_is_one_read_per_segment_not_per_id(tmp_path):
    """The I/O fan-in contract: a get_rows touching K spilled segments
    costs K store reads, never one per id."""
    tbl = _tiered(tmp_path, hot_rows=16, spill_seg_rows=64)
    tbl.get_rows(np.arange(256))  # materialize; 240 spill in 4 segs
    st0 = tbl.tier_stats()
    reads0 = st0["store_reads"]
    # touch 120 cold ids spread over the spilled range
    cold_ids = [r for r in range(240) if r in tbl._tier.cold][:120]
    segs = {tbl._tier.cold[r][0] for r in cold_ids}
    tbl.get_rows(np.asarray(cold_ids))
    st1 = tbl.tier_stats()
    assert st1["store_reads"] - reads0 == len(segs)
    assert st1["store_reads"] - reads0 < len(cold_ids) // 4


def test_at_rest_int8_density_and_error_bound(tmp_path):
    """int8-at-rest: per-element error within absmax_row/254 and row
    density >= 3.5x vs the f32 slab (the acceptance gauge; dim 32)."""
    tbl = _tiered(tmp_path, dim=32, hot_rows=16, spill_seg_rows=64,
                  at_rest_quant="int8")
    ids = np.arange(400)
    ref = tbl.get_rows(ids).copy()      # materialize (spills cold tail)
    got = tbl.get_rows(ids)             # promotes back via dequant
    absmax = np.abs(ref).max(axis=1, keepdims=True)
    assert (np.abs(got - ref) <= absmax / 254 + 1e-7).all()
    st = tbl.tier_stats()
    assert st["density_x"] >= 3.5, st
    # after every row has been quantized ONCE, further spill/promote
    # round-trips are bit-exact (requant of dequantized values is
    # exact) — the error is one-shot, not cumulative
    tbl.get_rows(ids[:200])
    settled = tbl.get_rows(ids).copy()   # every row quantized by now
    tbl.get_rows(ids[200:])              # churn the residency again
    np.testing.assert_array_equal(settled, tbl.get_rows(ids))


def test_at_rest_fp16_roundtrip(tmp_path):
    tbl = _tiered(tmp_path, dim=16, hot_rows=8, at_rest_quant="fp16",
                  spill_seg_rows=32)
    ids = np.arange(100)
    ref = tbl.get_rows(ids).copy()
    got = tbl.get_rows(ids)
    # one fp16 round trip: exact for fp16-representable values, else
    # within fp16 eps relative error
    assert np.allclose(got, ref, rtol=1e-3, atol=1e-6)
    np.testing.assert_array_equal(got, tbl.get_rows(ids))  # stable


def test_at_rest_fp16_overflow_stores_raw(tmp_path):
    """A FINITE row beyond the fp16 range (|v| > 65504) must not come
    back as inf — the encoder detects the cast overflow and stores
    that segment raw (minting poison out of healthy values would
    corrupt training silently, or falsely trip the reject guard)."""
    tbl = _tiered(tmp_path, dim=4, hot_rows=4, at_rest_quant="fp16",
                  spill_seg_rows=8)
    big = np.full((1, 4), 1e6, np.float32)
    tbl.apply_grad([0], -big, 1.0)       # row 0 ~= +1e6 (finite)
    tbl.get_rows(np.arange(1, 16))       # evict row 0 to disk
    assert 0 in tbl._tier.cold
    out = tbl.get_rows([0])
    assert np.isfinite(out).all()
    assert out[0, 0] > 9e5                # the learned value survived


def test_entry_gating_and_grad_drop():
    """Frequency-gated entry creation (reference PSLib): below the
    threshold an id serves its deterministic init row WITHOUT earning a
    slot, and grads for unentered ids drop counted."""
    tbl = core.LazyEmbeddingTable(height=1000, dim=4, seed=1,
                                  entry_threshold=3)
    init = tbl._init_row(7)
    for _ in range(2):
        np.testing.assert_array_equal(tbl.get_rows([7])[0], init)
    assert tbl.touched_rows() == 0
    assert tbl._tier.entry_denied == 2
    tbl.get_rows([7])  # third pull: entered
    assert tbl.touched_rows() == 1
    tbl.apply_grad([8], np.ones((1, 4), np.float32), 0.1)
    assert tbl.touched_rows() == 1  # unentered id's grad dropped
    assert tbl._tier.grad_dropped_rows == 1
    tbl.apply_grad([7], np.ones((1, 4), np.float32), 0.1)
    assert not np.array_equal(tbl.get_rows([7])[0], init)


def test_decay_shrink_drops_idle_rows(tmp_path):
    """Decay-based shrink: rows not re-touched decay below the
    threshold and are dropped from BOTH tiers; a re-touched id
    re-initializes deterministically (the documented trade)."""
    tbl = _tiered(tmp_path, hot_rows=16, spill_seg_rows=16,
                  track_scores=True)
    tbl.get_rows(np.arange(64))          # 48 spill cold, 16 hot
    keep = [0, 1, 60, 61]
    for _ in range(4):
        tbl.get_rows(keep)               # keep scores high
    n = tbl.shrink(decay=0.25, threshold=0.5)
    assert n > 0
    assert set(keep) <= (set(tbl._index) | set(tbl._tier.cold))
    assert tbl.touched_rows() == len(keep)
    st = tbl.tier_stats()
    assert st["shrunk_rows"] == n
    # dropped id comes back as its deterministic init
    np.testing.assert_array_equal(tbl.get_rows([30])[0],
                                  tbl._init_row(30))


def test_poisoned_spilled_row_trips_reject_on_touch(tmp_path):
    """Dequant-on-touch feeds FLAGS_ps_reject_nonfinite: a poisoned
    row coming back from disk (raw-stored even under int8-at-rest so
    the poison is never masked) raises typed in reject mode and
    re-initializes counted in drop mode."""
    old = core.globals_["FLAGS_ps_reject_nonfinite"]
    try:
        for mode, quant in (("reject", "int8"), ("drop", "")):
            core.set_flag("FLAGS_ps_reject_nonfinite", "")
            tbl = _tiered(tmp_path, name=f"p-{mode}-{quant}",
                          hot_rows=8, spill_seg_rows=8,
                          at_rest_quant=quant)
            tbl.get_rows(np.arange(8))
            g = np.zeros((1, 8), np.float32)
            g[0, 3] = np.inf
            tbl.apply_grad([2], g, 1.0)       # poison row 2 (hot)
            tbl.get_rows(np.arange(8, 24))    # evict it to disk
            assert 2 in tbl._tier.cold
            core.set_flag("FLAGS_ps_reject_nonfinite", mode)
            if mode == "reject":
                with pytest.raises(core.NumericFaultError,
                                   match="non-finite at touch"):
                    tbl.get_rows([2])
            else:
                out = tbl.get_rows([2])
                np.testing.assert_array_equal(out[0], tbl._init_row(2))
                assert tbl.tier_stats()["poison_dropped_rows"] == 1
    finally:
        core.set_flag("FLAGS_ps_reject_nonfinite", old)


# ==========================================================================
# corrupt spill log — the PR 3 checkpoint contract on the disk tier
# ==========================================================================
@pytest.mark.faults
@pytest.mark.parametrize("mode", ["truncate", "flip", "delete"])
def test_corrupt_spill_rejected_typed_hot_rows_survive(tmp_path, mode):
    tbl = _tiered(tmp_path, name=f"c-{mode}", hot_rows=8,
                  spill_seg_rows=8)
    tbl.get_rows(np.arange(32))   # 24 cold in 3 segs, 8 hot
    hot_ids = list(tbl._index)
    hot_vals = tbl.get_rows(hot_ids).copy()
    victim = FI.corrupt_spill(tbl, mode)
    bad_ids = [r for r, (sid, _p) in tbl._tier.cold.items()
               if mode == "delete" or sid == victim]
    assert bad_ids
    with pytest.raises(core.SpillCorruptionError):
        tbl.get_rows(bad_ids[:2])
    assert tbl.tier_stats()["crc_failures"] >= 1
    # the pinned hot set keeps serving bit-identically
    np.testing.assert_array_equal(tbl.get_rows(hot_ids), hot_vals)
    # CheckpointError subclass: existing torn-state handlers catch it
    assert issubclass(core.SpillCorruptionError, core.CheckpointError)


def test_compaction_preserves_reads(tmp_path):
    """Freeing most segments triggers log compaction; surviving cold
    rows still read back exactly (offsets remapped, CRCs intact)."""
    tbl = _tiered(tmp_path, hot_rows=8, spill_seg_rows=8,
                  track_scores=True)
    tbl.get_rows(np.arange(512))
    store = tbl._tier.store
    ref = {r: tbl._tier.cold[r]
           for r in list(tbl._tier.cold)[:16]}
    vals = {r: None for r in ref}
    # dirty everything hot so the log holds real bytes, then shrink
    # away most cold rows to create dead-byte pressure
    keep = list(ref)
    for _ in range(3):
        tbl.get_rows(keep)
    before = store.compactions
    tbl.shrink(decay=0.3, threshold=0.5)
    assert store.compactions >= before  # may or may not have fired yet
    store.compact()
    out = tbl.get_rows(keep)
    assert out.shape == (len(keep), 8)
    # a second read after compaction is stable
    np.testing.assert_array_equal(out, tbl.get_rows(keep))


# ==========================================================================
# residency round-trips (export/import + streaming sections)
# ==========================================================================
@pytest.mark.parametrize("quant", ["", "int8"])
def test_export_import_round_trips_all_residencies(tmp_path, quant):
    """export_state→import_state across hot-RAM, spilled-raw and
    spilled-quantized residencies: LRU order, dtype, and row values
    preserved (int8 re-encode of dequantized values is exact)."""
    tbl = _tiered(tmp_path, name=f"rt-{quant}", hot_rows=32,
                  spill_seg_rows=16, at_rest_quant=quant)
    rng = np.random.RandomState(5)
    for _ in range(6):
        ids = rng.randint(0, 500, 48)
        tbl.apply_grad(ids, rng.randn(48, 8).astype(np.float32), 0.05)
    meta, ids, rows = tbl.export_state()
    assert rows.dtype == tbl.dtype
    tbl2 = core.LazyEmbeddingTable.from_state(meta, ids, rows)
    assert tbl2._tier is not None and tbl2.dtype == tbl.dtype
    # residency boundary identical: same hot LRU, same cold set
    assert list(tbl2._index) == list(tbl._index)
    assert set(tbl2._tier.cold) == set(tbl._tier.cold)
    probe = rng.randint(0, 500, 512)
    np.testing.assert_array_equal(tbl.get_rows(probe),
                                  tbl2.get_rows(probe))
    # pure hot-RAM residency round-trips through the same API
    small = core.LazyEmbeddingTable(height=100, dim=8, seed=1)
    small.get_rows([1, 2, 3])
    m2, i2, r2 = small.export_state()
    s2 = core.LazyEmbeddingTable.from_state(m2, i2, r2)
    assert s2._tier is None
    np.testing.assert_array_equal(small.get_rows([1, 2, 3]),
                                  s2.get_rows([1, 2, 3]))


def test_streaming_sections_bit_identical_and_rss_bounded(tmp_path):
    """The handoff leg: table_sections → build_table_from_sections of a
    part-spilled table is bit-identical (verbatim segment records,
    exact LRU/cold maps) with peak RSS far below the table's row bytes
    — sections stage through disk files like the real drain."""
    tbl = _tiered(tmp_path, dim=256, hot_rows=256, spill_seg_rows=1024,
                  name="big")
    rng = np.random.RandomState(0)
    for _ in range(10):
        ids = rng.randint(0, 20000, 2048)
        tbl.apply_grad(ids, rng.randn(2048, 256).astype(np.float32),
                       0.03)
    logical = tbl.touched_rows() * 256 * 4
    assert logical > 6e6  # the bound below must mean something

    stage = tmp_path / "stage"
    stage.mkdir()
    tracemalloc.start()
    base, _ = tracemalloc.get_traced_memory()
    secs = slab_spill.table_sections(tbl)
    for name, sec in secs.items():  # source leg: one section at a time
        blob = sec["read"]()
        assert len(blob) == sec["size"]
        (stage / name.replace(":", "_")).write_bytes(blob)
        del blob
    _, peak_src = tracemalloc.get_traced_memory()
    tracemalloc.reset_peak()

    def _sec(rel):
        return (stage / rel.replace(":", "_")).read_bytes()

    meta = json.loads(_sec("tier:meta"))
    tbl2 = slab_spill.build_table_from_sections(
        meta, _sec, spill_path=str(tmp_path / "big2.slab"))
    _, peak_dst = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    # "well below table size": the row payload never materializes —
    # what remains is one bounded section + O(spilled rows) of index
    # metadata (cold map/scores dicts, the documented constant)
    assert peak_src - base < logical / 2, (peak_src - base, logical)
    assert peak_dst < logical / 2, (peak_dst, logical)
    assert list(tbl2._index) == list(tbl._index)
    assert tbl2._tier.cold == tbl._tier.cold or \
        set(tbl2._tier.cold) == set(tbl._tier.cold)
    probe = rng.randint(0, 20000, 4096)
    np.testing.assert_array_equal(tbl.get_rows(probe),
                                  tbl2.get_rows(probe))


# ==========================================================================
# checkpoint / persistables streaming (io.py satellite)
# ==========================================================================
def test_checkpoint_streams_spilled_table_rss_bounded(tmp_path):
    """io.save_checkpoint of a spilled table streams the slab section
    file (manifest-CRC'd like any blob) at bounded RSS; load restores
    tier, residency, and values; corruption is rejected wholesale."""
    from paddle_tpu.fluid import io
    tbl = _tiered(tmp_path, dim=128, hot_rows=256, spill_seg_rows=1024,
                  name="ck")
    rng = np.random.RandomState(0)
    for _ in range(10):
        ids = rng.randint(0, 20000, 2048)
        tbl.apply_grad(ids, rng.randn(2048, 128).astype(np.float32),
                       0.03)
    logical = tbl.touched_rows() * 128 * 4
    main = fluid.Program()
    main.global_block().create_var(name="emb", shape=[100000, 128],
                                   dtype="float32", persistable=True)
    scope = core.Scope()
    scope.var("emb").set_value(tbl)
    ckdir = str(tmp_path / "ckpt")
    tracemalloc.start()
    base, _ = tracemalloc.get_traced_memory()
    io.save_checkpoint(None, ckdir, main_program=main, scope=scope,
                       global_step=1)
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert peak - base < logical / 2, (peak - base, logical)

    scope2 = core.Scope()
    io.load_checkpoint(None, ckdir, main_program=main, scope=scope2)
    tbl2 = scope2.find_var("emb").value()
    assert isinstance(tbl2, core.LazyEmbeddingTable)
    assert tbl2._tier is not None
    assert list(tbl2._index) == list(tbl._index)
    probe = rng.randint(0, 20000, 2048)
    np.testing.assert_array_equal(tbl.get_rows(probe),
                                  tbl2.get_rows(probe))

    # a flipped byte in the slab file fails the manifest CRC wholesale
    ck = io.latest_checkpoint(ckdir)
    FI.corrupt_checkpoint(ck, "flip")
    with pytest.raises(core.CheckpointError):
        io.validate_checkpoint(ck)


def test_save_persistables_roundtrips_slab_table(tmp_path):
    from paddle_tpu.fluid import io
    tbl = _tiered(tmp_path, hot_rows=16, spill_seg_rows=16, name="pv")
    tbl.get_rows(np.arange(64))
    main = fluid.Program()
    main.global_block().create_var(name="emb", shape=[100000, 8],
                                   dtype="float32", persistable=True)
    with fluid.scope_guard(core.Scope()) as _:
        pass
    scope = core.Scope()
    scope.var("emb").set_value(tbl)
    old = core._switch_scope(scope)
    try:
        pd = str(tmp_path / "persist")
        io.save_persistables(None, pd, main)
        # combined-stream save refuses slab tables typed
        with pytest.raises(ValueError, match="combined tensor stream"):
            io.save_persistables(None, pd, main, filename="all.bin")
        scope2 = core.Scope()
        core._switch_scope(scope2)
        io.load_persistables(None, pd, main)
        tbl2 = scope2.find_var("emb").value()
        assert isinstance(tbl2, core.LazyEmbeddingTable)
        np.testing.assert_array_equal(tbl.get_rows(np.arange(64)),
                                      tbl2.get_rows(np.arange(64)))
    finally:
        core._switch_scope(old)


# ==========================================================================
# live drain of a part-spilled table (PR 6 handoff acceptance)
# ==========================================================================
@pytest.mark.chaos
@pytest.mark.parametrize("quant", ["", "int8"])
def test_live_drain_streams_part_spilled_table_bit_identical(
        tmp_path, quant):
    """A real listen_and_serv drain of a part-spilled table: tier
    sections stream through the CRC-manifested handoff (staged on disk
    destination-side), the rebuilt table serves bit-identically with
    the SAME residency, and the slab/table_stats/table_shrink RPC
    surfaces work on the destination."""
    from paddle_tpu.fluid.ps_rpc import VarClient

    def start_ps(endpoint, bind="", standby=False):
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            main.global_block().append_op(
                type="listen_and_serv", inputs={}, outputs={},
                attrs={"endpoint": endpoint, "sync_mode": False,
                       "Fanin": 1, "optimize_blocks": [],
                       "grad_to_block_id": [],
                       "pserver_endpoints": [endpoint],
                       "bind_endpoint": bind, "standby": standby,
                       "replica_of": ""})
        scope = core.Scope()
        exe = fluid.Executor()
        th = threading.Thread(
            target=lambda: exe.run(main, scope=scope, feed={},
                                   fetch_list=[]), daemon=True)
        th.start()
        return th, scope

    from paddle_tpu.fluid import ps_membership
    ps_membership.reset_views()
    slot = f"127.0.0.1:{free_port()}"
    bind_b = f"127.0.0.1:{free_port()}"
    th_a, scope_a = start_ps(slot)
    th_b, scope_b = start_ps(slot, bind=bind_b, standby=True)
    try:
        time.sleep(0.8)
        tbl = core.LazyEmbeddingTable(
            height=100000, dim=16, seed=7,
            spill_path=str(tmp_path / f"drain{quant}.slab"),
            hot_rows=64, at_rest_quant=quant, spill_seg_rows=128,
            track_scores=True)
        rng = np.random.RandomState(1)
        for _ in range(6):
            ids = rng.randint(0, 5000, 256)
            tbl.apply_grad(ids, rng.randn(256, 16).astype(np.float32),
                           0.05)
        scope_a.var("emb").set_value(tbl)
        admin = VarClient(slot, connect_timeout=10.0, resolve=False)
        summary = admin.call("drain", dest=bind_b, _rpc_timeout=60.0)
        assert summary["epoch"] >= 1 and summary["sections"] >= 4
        tbl_b = scope_b.find_var("emb").value()
        assert tbl_b._tier is not None
        assert list(tbl_b._index) == list(tbl._index)
        assert set(tbl_b._tier.cold) == set(tbl._tier.cold)
        probe = rng.randint(0, 5000, 2048)
        np.testing.assert_array_equal(tbl.get_rows(probe),
                                      tbl_b.get_rows(probe))
        # telemetry + admin surfaces on the destination
        dest = VarClient(bind_b, connect_timeout=5.0, resolve=False)
        st = dest.call("stats")
        assert st["slab"]["tables"] == 1
        assert st["slab"]["spilled_rows"] > 0
        ts = dest.call("table_stats", name="emb")
        assert ts["tier"]["resident_rows"] == len(tbl_b._index)
        shr = dest.call("table_shrink", decay=0.0, threshold=0.5)
        assert shr["emb"] > 0
        admin.close()
        dest.close()
    finally:
        for ep, th in ((bind_b, th_b), (slot, th_a)):
            try:
                c = VarClient(ep, connect_timeout=5.0, channels=1,
                              resolve=False)
                c.stop()
                c.close()
            except Exception:
                pass
            th.join(timeout=10)
        ps_membership.reset_views()


@pytest.mark.chaos
def test_corrupted_tier_handoff_aborts_cleanly(tmp_path):
    """A byte flipped in a STREAMED tier section (post-manifest) fails
    the destination's per-section CRC; the drain aborts with the
    source still serving its spilled rows."""
    from paddle_tpu.fluid import ps_membership
    from paddle_tpu.fluid.ps_rpc import VarClient

    def start_ps(endpoint, bind="", standby=False):
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            main.global_block().append_op(
                type="listen_and_serv", inputs={}, outputs={},
                attrs={"endpoint": endpoint, "sync_mode": False,
                       "Fanin": 1, "optimize_blocks": [],
                       "grad_to_block_id": [],
                       "pserver_endpoints": [endpoint],
                       "bind_endpoint": bind, "standby": standby,
                       "replica_of": ""})
        scope = core.Scope()
        exe = fluid.Executor()
        th = threading.Thread(
            target=lambda: exe.run(main, scope=scope, feed={},
                                   fetch_list=[]), daemon=True)
        th.start()
        return th, scope

    ps_membership.reset_views()
    slot = f"127.0.0.1:{free_port()}"
    bind_b = f"127.0.0.1:{free_port()}"
    th_a, scope_a = start_ps(slot)
    th_b, _scope_b = start_ps(slot, bind=bind_b, standby=True)
    try:
        time.sleep(0.8)
        tbl = core.LazyEmbeddingTable(
            height=100000, dim=16, seed=7,
            spill_path=str(tmp_path / "ch.slab"), hot_rows=32,
            spill_seg_rows=64)
        tbl.get_rows(np.arange(512))
        probe = tbl.get_rows(np.arange(256)).copy()
        scope_a.var("emb").set_value(tbl)
        admin = VarClient(slot, connect_timeout=10.0, resolve=False)
        with FI.corrupt_handoff(section="tier:emb:seg") as inj:
            with pytest.raises(RuntimeError, match="failed validation"):
                admin.call("drain", dest=bind_b, _rpc_timeout=60.0)
        assert inj.fired == 1
        st = admin.call("stats")["membership"]
        assert st["state"] == "active"
        np.testing.assert_array_equal(tbl.get_rows(np.arange(256)),
                                      probe)
        admin.close()
    finally:
        from paddle_tpu.fluid.ps_rpc import VarClient as VC
        for ep, th in ((bind_b, th_b), (slot, th_a)):
            try:
                c = VC(ep, connect_timeout=5.0, channels=1,
                       resolve=False)
                c.stop()
                c.close()
            except Exception:
                pass
            th.join(timeout=10)
        ps_membership.reset_views()


# ==========================================================================
# microbench smoke (rpcbench lane twin)
# ==========================================================================
@pytest.mark.rpcbench
def test_spill_microbench_smoke():
    from tools import rpc_microbench as MB
    rows = MB.run_spill(n_rows=1500, dim=32, batch=256, repeats=2,
                        warmup=1, fracs=[1.0, 0.25])
    assert [r["resident_frac"] for r in rows] == [1.0, 0.25]
    assert all(r["pull_mb_s"] > 0 for r in rows)
    assert rows[1]["store_reads"] > 0
    assert 0.0 < rows[1]["hit_rate"] < 1.0
    # int8 sweep reports the density gauge
    rows8 = MB.run_spill(n_rows=1500, dim=32, batch=256, repeats=1,
                         warmup=1, fracs=[0.25], quant="int8")
    assert rows8[0]["density_x"] >= 3.0


# ==========================================================================
# trainer-driven shrink cron (FLAGS_ps_shrink_every_steps, PR 13)
# ==========================================================================
def _start_cron_pserver(endpoint):
    import threading
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        main.global_block().append_op(
            type="listen_and_serv", inputs={}, outputs={},
            attrs={"endpoint": endpoint, "sync_mode": False,
                   "Fanin": 1, "optimize_blocks": [],
                   "grad_to_block_id": [],
                   "pserver_endpoints": [endpoint]})
    scope = core.Scope()
    exe = fluid.Executor()
    th = threading.Thread(
        target=lambda: exe.run(main, scope=scope, feed={},
                               fetch_list=[]), daemon=True)
    th.start()
    return th, scope


def test_trainer_driven_shrink_cron_fires_every_n_rounds(tmp_path):
    """FLAGS_ps_shrink_every_steps: trainer 0's fetch_barrier closes a
    sync round; every N-th round ONE table_shrink admin RPC reaches the
    pserver (PSLib save/shrink cron analogue) — visible as the slab
    stats "shrink_runs" counter and decayed-out idle rows. Non-zero
    trainer ids never fire it."""
    import time as _time
    from paddle_tpu.fluid.ps_rpc import VarClient
    from paddle_tpu.ops import distributed_ops as dops

    ep = f"127.0.0.1:{free_port()}"
    th, scope = _start_cron_pserver(ep)
    prev = {k: core.globals_[k] for k in
            ("FLAGS_ps_shrink_every_steps", "FLAGS_ps_shrink_decay",
             "FLAGS_ps_shrink_threshold")}
    dops.reset_shrink_cron()
    try:
        _time.sleep(0.5)
        tbl = core.LazyEmbeddingTable(height=1000, dim=4, seed=1,
                                      track_scores=True)
        tbl.get_rows(np.arange(20))  # materialize + score 20 rows
        scope.var("emb").set_value(tbl)
        core.set_flag("FLAGS_ps_shrink_every_steps", 2)
        core.set_flag("FLAGS_ps_shrink_decay", 0.0)   # one run drops all
        core.set_flag("FLAGS_ps_shrink_threshold", 0.5)

        def round_program(tid):
            main = fluid.Program()
            with fluid.program_guard(main, fluid.Program()):
                main.global_block().append_op(
                    type="fetch_barrier", inputs={}, outputs={},
                    attrs={"endpoints": [ep], "trainer_id": tid})
            return main

        exe = fluid.Executor()
        tscope = core.Scope()
        with fluid.scope_guard(tscope):
            exe.run(round_program(1))   # trainer 1 never drives the cron
            exe.run(round_program(1))
            exe.run(round_program(0))   # round 1: below the period
            admin = VarClient(ep, connect_timeout=5.0, resolve=False)
            assert admin.call("table_stats",
                              name="emb")["tier"]["shrink_runs"] == 0
            exe.run(round_program(0))   # round 2: cron fires
        ts = admin.call("table_stats", name="emb")["tier"]
        assert ts["shrink_runs"] == 1
        assert ts["shrunk_rows"] == 20      # decay 0.0 drops every row
        assert ts["resident_rows"] == 0
        admin.close()
    finally:
        for k, v in prev.items():
            core.set_flag(k, v)
        dops.reset_shrink_cron()
        try:
            c = VarClient(ep, connect_timeout=5.0, channels=1,
                          resolve=False)
            c.stop()
            c.close()
        except Exception:
            pass
        th.join(timeout=10)
