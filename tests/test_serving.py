"""Online inference serving plane (ISSUE 7, docs/SERVING.md):
continuous batcher + predictor pool + serving-time embedding fetch.

Acceptance legs covered here:
  * batched-serving correctness — for any interleaving of >= 8
    concurrent predict() clients, per-row outputs are BIT-identical to
    the single-row unbatched oracle (pad rows provably inert);
  * per-bucket jit caching — steady-state traffic compiles nothing new;
  * serving-time sparse path — wide_deep-shaped lookups served through
    LIVE in-process pservers with the embedding cache: a cache-hit
    predict issues ZERO RPCs (server-counter-asserted), TTL expiry
    refetches, results bit-identical to the local-table oracle;
  * a pserver drain mid-serving is transparent to predict()
    (StaleClusterViewError re-route, PR 6);
  * io.save_inference_model -> Predictor round trip incl. wide_deep
    optimizer-slot pruning, bit-identical to Executor.run.
"""
import os
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import core

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

pytestmark = pytest.mark.serving


# ======================================================================
# harness
# ======================================================================
@pytest.fixture(scope="module")
def mlp():
    """Tiny forward model + single-row unbatched oracle rows."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", shape=[8], dtype="float32")
        h = fluid.layers.fc(x, 16, act="relu")
        out = fluid.layers.fc(h, 4, act="softmax")
    exe = fluid.Executor()
    scope = core.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    rng = np.random.RandomState(0)
    X = rng.rand(32, 8).astype(np.float32)
    oracle = []
    with fluid.scope_guard(scope):
        for i in range(len(X)):
            (o,) = exe.run(main, feed={"x": X[i:i + 1]}, fetch_list=[out],
                           scope=scope)
            oracle.append(np.asarray(o))
    return {"main": main, "scope": scope, "out": out.name, "exe": exe,
            "X": X, "oracle": oracle}


def _engine(m, **kw):
    from paddle_tpu.serving import ServingEngine
    kw.setdefault("max_batch", 8)
    kw.setdefault("max_queue_delay_ms", 4.0)
    kw.setdefault("num_workers", 2)
    return ServingEngine(program=m["main"], scope=m["scope"],
                         feed_names=["x"], fetch_names=[m["out"]], **kw)


@pytest.fixture
def _ps_isolation():
    """PS-backed serving tests start from a clean view registry/client
    pool (same shape as tests/test_ps_membership.py's fixture)."""
    from paddle_tpu.fluid import ps_membership, ps_rpc
    from paddle_tpu.fluid.ps_rpc import VarClient
    ps_membership.reset_views()
    prev = ps_rpc.install_row_cache(None)
    yield
    ps_rpc.install_row_cache(prev)
    ps_membership.reset_views()
    VarClient.reset_pool()


# ======================================================================
# batched-serving correctness (acceptance: >= 8 concurrent clients)
# ======================================================================
def test_concurrent_clients_bit_identical_to_single_row_oracle(mlp):
    """8 client threads hammer predict() with interleaved rows; every
    per-row output must equal the single-row Executor.run oracle BIT
    for bit — and batching must actually have happened (the assertion
    is vacuous on a one-row-per-batch run)."""
    eng = _engine(mlp)
    try:
        eng.warm()
        eng.reset_stats()
        X, oracle = mlp["X"], mlp["oracle"]
        errs = []

        def client(wid):
            rng = np.random.RandomState(100 + wid)
            for k in range(12):
                i = int(rng.randint(0, len(X)))
                try:
                    (got,) = eng.predict({"x": X[i]})
                    if got.shape != oracle[i].shape \
                            or not (got == oracle[i]).all():
                        errs.append((wid, k, i, "mismatch"))
                except BaseException as e:
                    errs.append((wid, k, i, repr(e)))
                if k % 5 == wid % 3:  # vary the interleavings
                    time.sleep(0.001)

        ths = [threading.Thread(target=client, args=(w,))
               for w in range(8)]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        assert not errs, errs[:5]
        st = eng.stats()
        assert st["requests"] == 8 * 12
        assert max(st["batch_size_hist"]) > 1, \
            f"no coalescing happened: {st['batch_size_hist']}"
    finally:
        eng.close()


def test_pad_rows_inert_and_pow2_buckets(mlp):
    """A 3-row group pads into the 4-bucket and a 5-row group into the
    8-bucket; the shared row's output is bit-identical in both (and to
    the oracle) — neither pad rows nor batch composition leak into a
    real row."""
    eng = _engine(mlp)
    try:
        X, oracle = mlp["X"], mlp["oracle"]
        (r3,) = eng.predict_many({"x": X[[0, 5, 9]]})
        (r5,) = eng.predict_many({"x": X[[0, 11, 20, 7, 30]]})
        np.testing.assert_array_equal(r3[0:1], oracle[0])
        np.testing.assert_array_equal(r5[0:1], oracle[0])
        for j, i in enumerate((0, 5, 9)):
            np.testing.assert_array_equal(r3[j:j + 1], oracle[i])
        st = eng.stats()
        assert set(st["bucket_hist"]) == {4, 8}
    finally:
        eng.close()


def test_steady_state_traffic_never_recompiles(mlp):
    """After warm(), arbitrary request sizes land in the warmed pow-2
    buckets: the scanned-jit bucket cache must not grow, and no bucket
    retraces (jax's per-jit cache stays at one entry per bucket)."""
    eng = _engine(mlp)
    try:
        eng.warm()
        buckets0 = eng.buckets_compiled()
        assert buckets0 == [1, 2, 4, 8]

        def jit_entries():
            sizes = []
            for f in eng._cb._multi_jit.values():
                cs = getattr(f, "_cache_size", None)
                if cs is not None:
                    sizes.append(cs())
            return sizes

        entries0 = jit_entries()
        rng = np.random.RandomState(3)
        for _ in range(25):
            n = int(rng.randint(1, 9))
            eng.predict_many({"x": mlp["X"][:n]})
        assert eng.buckets_compiled() == buckets0
        assert jit_entries() == entries0, "a warmed bucket retraced"
    finally:
        eng.close()


def test_partial_batch_flushes_on_queue_delay(mlp):
    """max_batch far above the offered load: a lone request must not
    wait for company beyond max_queue_delay_ms."""
    eng = _engine(mlp, max_batch=64, max_queue_delay_ms=10.0)
    try:
        eng.warm((1,))
        eng.reset_stats()
        (got,) = eng.predict({"x": mlp["X"][2]}, timeout=30.0)
        np.testing.assert_array_equal(got, mlp["oracle"][2])
        assert eng.stats()["batch_size_hist"] == {1: 1}
    finally:
        eng.close()


def test_async_submit_future_and_stats_surface(mlp):
    from paddle_tpu.fluid import profiler

    eng = _engine(mlp)
    try:
        eng.warm((1, 2, 4))
        eng.reset_stats()
        profiler.start_profiler(state="CPU")
        try:
            futs = [eng.submit({"x": mlp["X"][i]}) for i in (1, 2, 3)]
            for i, f in zip((1, 2, 3), futs):
                (got,) = f.wait(30.0)
                np.testing.assert_array_equal(got, mlp["oracle"][i])
                assert f.t_done >= f.t_submit
            events = list(profiler._prof.events)
        finally:
            profiler.stop_profiler(profile_path="")
        serve = [e for e in events if e.cat == "serve"]
        names = {e.name.split("[")[0] for e in serve}
        assert {"serve:queue_wait", "serve:exec"} <= names, names
        execs = [e for e in serve if e.name.startswith("serve:exec")]
        assert all(e.args and "bucket" in e.args and "n_valid" in e.args
                   for e in execs)

        st = eng.stats()
        assert st["requests"] == 3 and st["rows"] == 3
        assert st["qps"] > 0
        assert st["latency_ms"]["p50"] <= st["latency_ms"]["p99"]
        assert st["queue_wait_ms"]["p99"] >= 0
        assert sum(st["batch_size_hist"].values()) == st["batches"]
        assert st["mode"] == "scan" and st["workers"] == 2
    finally:
        eng.close()


def test_predict_validates_feeds(mlp):
    eng = _engine(mlp)
    try:
        with pytest.raises(KeyError, match="missing"):
            eng.predict({})
        with pytest.raises(ValueError, match="one sample"):
            eng.predict({"x": np.zeros((2, 8), np.float32)})
        with pytest.raises(ValueError, match="rows must be"):
            eng.predict_many({"x": np.zeros((2, 9), np.float32)})
    finally:
        eng.close()
    with pytest.raises(RuntimeError, match="closed"):
        eng.predict({"x": mlp["X"][0]})


def test_loadgen_closed_and_open_loop_smoke(mlp):
    """tools/serving_loadgen.py as a library: both loop disciplines
    drive the engine and report sane percentiles."""
    from tools import serving_loadgen as LG

    eng = _engine(mlp)
    try:
        eng.warm()
        feeds = [{"x": mlp["X"][i]} for i in range(8)]
        res = LG.run_closed_loop(eng.predict, feeds, clients=4,
                                 duration_s=0.25, warmup_s=0.1)
        assert res["n"] > 0 and res["qps"] > 0
        assert res["p50_ms"] <= res["p99_ms"]
        res2 = LG.run_open_loop(eng.submit, feeds, rate_qps=200.0,
                                duration_s=0.25)
        assert res2["n"] > 0 and res2["p99_ms"] > 0
        assert res2["qps"] == pytest.approx(200.0, rel=0.6)
    finally:
        eng.close()


# ======================================================================
# embedding cache (unit)
# ======================================================================
def test_embedding_cache_ttl_lru_and_counters():
    from paddle_tpu.serving import EmbeddingCache

    table = np.arange(40, dtype=np.float32).reshape(10, 4)
    fetches = []

    def fetch(ids):
        fetches.append(np.asarray(ids))
        return table[np.asarray(ids)]

    c = EmbeddingCache(ttl_s=10.0, max_entries=4)
    clock = [100.0]
    c._clock = lambda: clock[0]

    r = c.lookup("t", [1, 2, 1], fetch)
    np.testing.assert_array_equal(r, table[[1, 2, 1]])
    assert len(fetches) == 1  # duplicate id fetched once
    np.testing.assert_array_equal(fetches[0], [1, 2])
    assert (c.hits, c.misses) == (0, 3)

    r = c.lookup("t", [1, 2], fetch)
    np.testing.assert_array_equal(r, table[[1, 2]])
    assert len(fetches) == 1 and c.hits == 2

    # TTL expiry refetches and counts staleness
    clock[0] += 11.0
    c.lookup("t", [1], fetch)
    assert len(fetches) == 2 and c.expired == 1

    # LRU bound: 4 entries max
    c.lookup("t", [3, 4, 5, 6], fetch)
    assert len(c) == 4 and c.evictions > 0

    # per-table keys don't collide
    c.lookup("u", [1], fetch)
    st = c.stats()
    assert st["entries"] <= 4 and 0.0 <= st["hit_rate"] <= 1.0
    c.invalidate("u")
    c.invalidate()
    assert len(c) == 0

    # invalidate() fences an IN-FLIGHT miss fetch: rows read before the
    # table push must not fill the cache after the flush
    def fetch_racing_invalidate(ids):
        c.invalidate()  # lands while the "RPC" is in flight
        return table[np.asarray(ids)]

    c.lookup("t", [9], fetch_racing_invalidate)
    assert len(c) == 0, "pre-invalidate rows were cached after the flush"


def test_rewrite_sparse_lookups_validation(mlp):
    from paddle_tpu.serving import rewrite_sparse_lookups

    with pytest.raises(ValueError, match="no lookup_table"):
        rewrite_sparse_lookups(mlp["main"], ["127.0.0.1:1"])
    with pytest.raises(ValueError, match="empty endpoint"):
        rewrite_sparse_lookups(mlp["main"], [])


# ======================================================================
# serving-time sparse path against LIVE pservers (in-process harness)
# ======================================================================
def _emb_model(n_slots=2, height=40, dim=4):
    """dense + n_slots distributed embeddings -> fc -> sigmoid."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        dense = fluid.data("dense", shape=[4], dtype="float32")
        slots = [fluid.data("s%d" % i, shape=[1], dtype="int64")
                 for i in range(n_slots)]
        embs = []
        for i, s in enumerate(slots):
            e = fluid.layers.embedding(s, size=[height, dim],
                                       param_attr="emb%d" % i,
                                       is_distributed=True)
            embs.append(fluid.layers.reshape(e, [-1, dim]))
        cat = fluid.layers.concat([dense] + embs, axis=1)
        h = fluid.layers.fc(cat, 8, act="relu")
        out = fluid.layers.sigmoid(fluid.layers.fc(h, 1))
    feed_names = ["dense"] + ["s%d" % i for i in range(n_slots)]
    return main, startup, feed_names, out, ["emb%d" % i
                                            for i in range(n_slots)]


def _feed_rows(n, height, n_slots, seed=7):
    rng = np.random.RandomState(seed)
    feed = {"dense": rng.rand(n, 4).astype(np.float32)}
    for i in range(n_slots):
        feed["s%d" % i] = rng.randint(0, height, (n, 1)).astype(np.int64)
    return feed


def test_wide_deep_ps_serving_cache_zero_rpc_ttl_and_parity(
        _ps_isolation):
    """The serving sparse path end to end: distributed_lookup_table
    over the binary wire against two live pservers, fronted by the
    EmbeddingCache. Asserts (acceptance): bit-parity with the
    local-table oracle, ZERO RPCs on the cache-hit path (pserver
    prefetch_rows counters), and TTL expiry refetching."""
    from paddle_tpu.fluid.ps_rpc import VarClient
    from paddle_tpu.serving import (EmbeddingCache, ServingEngine,
                                    rewrite_sparse_lookups)
    from tools import serving_loadgen as LG

    main, startup, feed_names, out, tables = _emb_model()
    exe = fluid.Executor()
    scope = core.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    feed = _feed_rows(4, 40, 2)
    (oracle,) = exe.run(main, feed=feed, fetch_list=[out], scope=scope)
    oracle = np.asarray(oracle)

    eps = [f"127.0.0.1:{LG.free_port()}" for _ in range(2)]
    servers = [LG.start_inproc_pserver(ep) for ep in eps]
    try:
        for t in tables:
            LG.push_table(eps, t,
                          np.asarray(scope.find_var(t).value().array))
        ps_prog, hit = rewrite_sparse_lookups(main, eps)
        assert sorted(hit) == tables

        def prefetch_calls():
            n = 0
            for ep in eps:
                st = VarClient.of(ep).call("stats")
                n += st.get("prefetch_rows", {}).get("calls", 0)
            return n

        cache = EmbeddingCache(ttl_s=30.0, max_entries=1000)
        eng = ServingEngine(program=ps_prog, scope=scope,
                            feed_names=feed_names, fetch_names=[out],
                            max_batch=8, max_queue_delay_ms=2.0,
                            num_workers=2, embedding_cache=cache)
        try:
            assert eng.batch_mode == "fused"  # stateful program
            (got,) = eng.predict_many(feed)
            np.testing.assert_array_equal(got, oracle)  # bit-identical
            n1 = prefetch_calls()
            assert n1 > 0 and cache.misses > 0

            # cache-hit path: SAME rows -> zero new RPCs, same bits
            (got2,) = eng.predict_many(feed)
            np.testing.assert_array_equal(got2, oracle)
            assert prefetch_calls() == n1, \
                "cache-hit predict still issued RPCs"
            assert cache.hits > 0

            # TTL expiry: a stale row refetches (and stays bit-equal —
            # the table is unchanged)
            real_clock = time.monotonic
            cache._clock = lambda: real_clock() + 31.0
            (got3,) = eng.predict_many(feed)
            np.testing.assert_array_equal(got3, oracle)
            assert prefetch_calls() > n1
            assert cache.expired > 0
        finally:
            eng.close()
    finally:
        for ep, (th, _s) in zip(eps, servers):
            LG.stop_inproc_pserver(ep, th)


def test_serving_lookup_transparent_across_pserver_drain(_ps_isolation):
    """Satellite: a DRAINING/just-moved pserver mid-serving. The client
    holds the old view; the typed StaleClusterViewError re-route (PR 6)
    must be invisible to predict() — no error, results bit-identical."""
    from paddle_tpu.fluid.ps_rpc import VarClient
    from paddle_tpu.serving import ServingEngine, rewrite_sparse_lookups
    from tools import serving_loadgen as LG

    main, startup, feed_names, out, tables = _emb_model(n_slots=1)
    exe = fluid.Executor()
    scope = core.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    feed = _feed_rows(3, 40, 1, seed=11)
    (oracle,) = exe.run(main, feed=feed, fetch_list=[out], scope=scope)
    oracle = np.asarray(oracle)

    slot = f"127.0.0.1:{LG.free_port()}"
    bind_b = f"127.0.0.1:{LG.free_port()}"
    th_a, _ = LG.start_inproc_pserver(slot)
    th_b, _ = LG.start_inproc_pserver(slot, bind=bind_b, standby=True)
    try:
        for t in tables:
            LG.push_table([slot], t,
                          np.asarray(scope.find_var(t).value().array))
        ps_prog, _hit = rewrite_sparse_lookups(main, [slot])
        # no cache: every predict must actually cross the wire, so the
        # re-route is exercised rather than absorbed by a cache hit
        eng = ServingEngine(program=ps_prog, scope=scope,
                            feed_names=feed_names, fetch_names=[out],
                            max_batch=8, num_workers=2)
        try:
            (before,) = eng.predict_many(feed)
            np.testing.assert_array_equal(before, oracle)

            # live drain: the shard moves A -> B mid-serving
            admin = VarClient(slot, connect_timeout=5.0, resolve=False)
            summary = admin.call("drain", dest=bind_b, _rpc_timeout=60.0)
            assert summary["epoch"] == 1

            # the engine's next pulls hit the DRAINED owner with the old
            # view -> typed stale re-route inside the call, no error
            # surfaces and the rows come back bit-identical
            (after,) = eng.predict_many(feed)
            np.testing.assert_array_equal(after, oracle)
            from paddle_tpu.fluid import ps_membership
            assert ps_membership.current_epoch() == 1
        finally:
            eng.close()
    finally:
        LG.stop_inproc_pserver(bind_b, th_b)
        LG.stop_inproc_pserver(slot, th_a)


# ======================================================================
# io.save_inference_model -> Predictor round trip (satellite)
# ======================================================================
def test_wide_deep_save_load_serve_roundtrip(tmp_path):
    """Train a mini wide_deep (Adam -> slot vars exist), save the
    inference model, and serve it three ways — Executor.run on the
    loaded program, AnalysisPredictor, ServingEngine — all bit-identical
    on the same feed. The saved dir must NOT contain optimizer slot
    files (optimizer-slot pruning: pre-fix, save_inference_model wrote
    the TRAINING program's persistables, moments and all)."""
    from paddle_tpu import inference
    from paddle_tpu.models.wide_deep import wide_deep_net
    from paddle_tpu.serving import ServingEngine

    n_slots, height = 3, 30
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        dense = fluid.data("dense", shape=[4], dtype="float32")
        slots = [fluid.data("slot_%d" % i, shape=[1], dtype="int64")
                 for i in range(n_slots)]
        label = fluid.data("label", shape=[1], dtype="float32")
        prob = wide_deep_net(dense, slots, sparse_dim=height,
                             embedding_dim=4, hidden=(8,))
        loss = fluid.layers.mean(
            fluid.layers.log_loss(prob, label))
        fluid.optimizer.Adam(1e-2).minimize(loss)
    feed_names = (["dense"] + ["slot_%d" % i for i in range(n_slots)])

    exe = fluid.Executor()
    scope = core.Scope()
    rng = np.random.RandomState(0)

    def batch(n, seed):
        r = np.random.RandomState(seed)
        f = {"dense": r.rand(n, 4).astype(np.float32),
             "label": r.randint(0, 2, (n, 1)).astype(np.float32)}
        for i in range(n_slots):
            f["slot_%d" % i] = r.randint(0, height, (n, 1)).astype(
                np.int64)
        return f

    d = str(tmp_path / "wd_model")
    with fluid.scope_guard(scope):
        exe.run(startup)
        for s in range(3):
            exe.run(main, feed=batch(16, s), fetch_list=[loss],
                    scope=scope)
        fluid.io.save_inference_model(d, feed_names, [prob], exe, main)

    # optimizer-slot pruning: adam moments/beta pows never reach disk
    files = sorted(os.listdir(d))
    slot_files = [f for f in files
                  if "moment" in f or "beta" in f or "pow_acc" in f]
    assert not slot_files, f"optimizer slots leaked into the saved " \
                           f"inference dir: {slot_files}"
    assert any(f.startswith("deep_emb") for f in files)

    feed = {k: v for k, v in batch(5, 99).items() if k != "label"}
    row0 = {n: feed[n][0] for n in feed_names}

    # 1) classic path: load_inference_model + Executor.run
    exe2 = fluid.Executor()
    scope2 = core.Scope()
    with fluid.scope_guard(scope2):
        prog, feeds_l, fetches = fluid.io.load_inference_model(d, exe2)
        assert feeds_l == feed_names
        (want,) = exe2.run(prog, feed=feed, fetch_list=fetches,
                           scope=scope2)
        (want_row0,) = exe2.run(prog,
                                feed={n: feed[n][:1] for n in feed_names},
                                fetch_list=fetches, scope=scope2)
    want, want_row0 = np.asarray(want), np.asarray(want_row0)

    # 2) AnalysisPredictor on the same dir: bit-identical batch output
    pred = inference.create_predictor(inference.Config(d))
    assert pred.get_input_names() == feed_names
    got = pred.run([feed[n] for n in feed_names])[0]
    np.testing.assert_array_equal(np.asarray(got), want)

    # 3) ServingEngine over the predictor: row-exact scan mode — each
    # row bit-identical to the single-row Executor.run oracle
    eng = ServingEngine(pred, max_batch=4, num_workers=2)
    try:
        (row,) = eng.predict(row0)
        np.testing.assert_array_equal(row, want_row0)
    finally:
        eng.close()


# ======================================================================
# cross-process compile-cache cold start (satellite; multiprocess -> slow)
# ======================================================================
_COLD_START_SCRIPT = r"""
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np
import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import core
from paddle_tpu import inference
from paddle_tpu.serving import ServingEngine

model_dir, cache_dir, make = sys.argv[1], sys.argv[2], sys.argv[3] == "1"
# enable FIRST: anything compiled before the cache is on stays
# process-local (in-memory jit cache) and would surface as "new"
# entries in the next process
inference.enable_compile_cache(cache_dir)
if make:
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", shape=[16], dtype="float32")
        h = fluid.layers.fc(x, 16, act="relu")
        out = fluid.layers.fc(h, 4, act="softmax")
    exe = fluid.Executor()
    scope = core.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        fluid.io.save_inference_model(model_dir, ["x"], [out], exe, main)

cfg = inference.Config(model_dir)
cfg.set_optim_cache_dir(cache_dir)  # enable_compile_cache underneath
pred = inference.create_predictor(cfg)
eng = ServingEngine(pred, max_batch=4, num_workers=1)
try:
    eng.warm((1, 2, 4))
    (y,) = eng.predict({"x": np.linspace(0, 1, 16, dtype="float32")})
finally:
    eng.close()
entries = [f for f in os.listdir(cache_dir) if not f.startswith(".")]
print(json.dumps({"entries": len(entries),
                  "y": np.asarray(y).ravel().tolist()}))
"""


@pytest.mark.slow
def test_serving_cold_start_second_process_adds_zero_cache_entries(
        tmp_path):
    """enable_compile_cache serving cold start (extends the
    tests/test_feed_and_compile_cache.py cross-process smoke): a SECOND
    predictor process warming the same buckets over the same saved
    model must add ZERO new cache entries — every bucket executable
    loads from the persistent XLA cache — and serve identical bits."""
    import json
    import subprocess

    model_dir = str(tmp_path / "model")
    cache_dir = str(tmp_path / "xla_cache")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)  # cache_dir is the API then
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def run_once(make):
        out = subprocess.run(
            [sys.executable, "-c", _COLD_START_SCRIPT, model_dir,
             cache_dir, "1" if make else "0"],
            capture_output=True, text=True, env=env, timeout=300,
            cwd=root)
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    first = run_once(make=True)
    if first["entries"] == 0:
        pytest.skip("backend does not persist executables on this box")
    second = run_once(make=False)
    assert second["entries"] == first["entries"], \
        "second serving process recompiled (cache entries grew) " \
        "instead of loading bucket executables from the persistent cache"
    np.testing.assert_array_equal(first["y"], second["y"])
