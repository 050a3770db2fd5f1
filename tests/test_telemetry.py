"""Unified telemetry plane tests (docs/OBSERVABILITY.md).

Covers the three legs of ISSUE 10:
  * distributed trace correlation — trace_scope semantics, profiler
    stamping, RPC header propagation (client rpc span ↔ VarServer
    handler span linkage), dedup-retry replays and stale-view
    re-routes keeping the trace id, HTTP X-Trace-Id round trips;
  * metrics registry — primitives, stats-dict views, Prometheus
    exposition, GET /metrics == stats() on a live ingress, the opt-in
    sidecar server;
  * merged cluster timelines — FLAGS_trace_dir shard streaming (ring
    bound + metadata), hello clock-offset capture, tools/timeline.py
    merge clock correction and trace-id filtering.

In-process tests stay tier-1 non-slow; the 2-trainer×2-pserver
wide_deep timeline acceptance also carries `slow`.
"""
import json
import os
import socket
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

pytestmark = pytest.mark.obs

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


@pytest.fixture(autouse=True)
def _telemetry_isolation():
    """Flags restored; the shard writer and clock offsets reset so one
    test's FLAGS_trace_dir can't leak into the next."""
    from paddle_tpu.fluid import core, telemetry
    from paddle_tpu.fluid.ps_rpc import VarClient

    saved = {k: core.globals_[k] for k in
             ("FLAGS_trace_dir", "FLAGS_trace_shard_max_events",
              "FLAGS_profiler_max_events", "FLAGS_metrics_port")}
    yield
    for k, v in saved.items():
        core.globals_[k] = v
    telemetry.reset_trace_shard()
    telemetry.reset_clock_offsets()
    VarClient.reset_pool()


# ======================================================================
# metrics registry
# ======================================================================
def test_registry_primitives_labels_and_exposition():
    from paddle_tpu.fluid.telemetry import MetricsRegistry

    reg = MetricsRegistry()
    c = reg.counter("req_total", "requests", labelnames=("code",))
    c.labels(code="200").inc()
    c.labels(code="200").inc(2)
    c.labels(code="429").inc()
    g = reg.gauge("depth")
    g.set(7)
    h = reg.histogram("lat_s", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(9.0)

    assert c.value(code="200") == 3
    assert c.value(code="429") == 1
    assert g.value() == 7

    text = reg.exposition()
    assert '# TYPE req_total counter' in text
    assert 'req_total{code="200"} 3' in text
    assert 'req_total{code="429"} 1' in text
    assert "depth 7" in text
    assert 'lat_s_bucket{le="0.1"} 1' in text
    assert 'lat_s_bucket{le="1.0"} 2' in text
    assert 'lat_s_bucket{le="+Inf"} 3' in text
    assert "lat_s_count 3" in text

    # kind/label conflicts are refused, get-or-create is idempotent
    assert reg.counter("req_total", labelnames=("code",)) is c
    with pytest.raises(ValueError):
        reg.gauge("req_total")
    with pytest.raises(ValueError):
        reg.counter("req_total", labelnames=("other",))


def test_registry_view_exposes_stats_dict_numbers_exactly():
    """A registered view's numeric leaves surface as gauges whose
    values equal the dict's EXACTLY (floats repr-round-trip); strings
    and lists are skipped — the dict API stays authoritative."""
    from paddle_tpu.fluid.telemetry import MetricsRegistry

    reg = MetricsRegistry()
    stats = {"shed": 17, "hit_rate": 0.8749999731,
             "nested": {"p99": 12.5}, "mode": "scan",
             "buckets": [1, 2, 4], "flag": True}
    reg.register_view("eng", lambda: stats, labels={"engine": "e0"})
    got = reg.collect()
    assert got["eng_shed"]["samples"] == [({"engine": "e0"}, 17)]
    assert got["eng_hit_rate"]["samples"][0][1] == stats["hit_rate"]
    assert got["eng_nested_p99"]["samples"][0][1] == 12.5
    assert got["eng_flag"]["samples"][0][1] == 1
    assert "eng_mode" not in got and "eng_buckets" not in got
    # text round trip preserves the float bits
    text = reg.exposition()
    line = [ln for ln in text.splitlines()
            if ln.startswith("eng_hit_rate")][0]
    assert float(line.split()[-1]) == stats["hit_rate"]
    # a raising view is skipped, never breaks the scrape
    reg.register_view("bad", lambda: 1 / 0)
    assert "eng_shed" in reg.exposition()


def test_trace_scope_root_child_adopt_and_cross_process_form():
    from paddle_tpu.fluid import telemetry as T

    assert T.current_trace() is None
    with T.trace_scope() as root:
        assert root.parent_id is None
        with T.trace_scope() as child:
            assert child.trace_id == root.trace_id
            assert child.parent_id == root.span_id
            assert child.span_id != root.span_id
        # cross-process adoption: same trace id, NEW span id
        with T.trace_scope(trace_id="t123",
                           parent_span_id="s456") as remote:
            assert (remote.trace_id, remote.parent_id) == ("t123",
                                                           "s456")
        # verbatim adoption (fan-out pool threads)
        with T.trace_scope(adopt=root) as same:
            assert same is root
        assert T.current_trace() is root
    assert T.current_trace() is None


def test_profiler_stamps_trace_ids_and_ring_bounds_events():
    from paddle_tpu.fluid import core, profiler, telemetry

    core.globals_["FLAGS_profiler_max_events"] = 4
    profiler.start_profiler("CPU")
    try:
        with telemetry.trace_scope() as ctx:
            profiler.record_instant("traced")
        for i in range(6):
            profiler.record_instant(f"fill{i}")
        evs = profiler.snapshot_events()
        assert len(evs) == 4  # ring bound
        assert profiler.dropped_events() == 3
        assert all(e["trace_id"] is None for e in evs)  # traced dropped
        profiler.reset_profiler()
        with telemetry.trace_scope() as ctx:
            profiler.record_instant("traced2")
        (ev,) = profiler.snapshot_events()
        assert ev["trace_id"] == ctx.trace_id
        assert ev["span_id"] == ctx.span_id
    finally:
        profiler.stop_profiler(profile_path="")


# ======================================================================
# RPC propagation
# ======================================================================
def test_rpc_trace_propagates_to_handler_spans_and_offsets_recorded():
    """The tentpole contract in one process: a traced client call's
    rpc span and the server's handler span share the trace id; the
    handler span is a NEW span parented on the client's rpc span; the
    _hello handshake recorded a clock offset for the endpoint."""
    from paddle_tpu.fluid import profiler, telemetry
    from paddle_tpu.fluid.ps_rpc import VarClient, VarServer

    srv = VarServer("127.0.0.1:0", {"echo": lambda x=0: x + 1}).start()
    ep = f"127.0.0.1:{srv.port}"
    try:
        cli = VarClient(ep)
        assert cli._telemetry_ok
        off = telemetry.clock_offsets()[ep]
        assert abs(off[0]) < 5.0 and 0 < off[1] < 5.0  # same host
        profiler.start_profiler("CPU")
        try:
            with telemetry.trace_scope() as ctx:
                assert cli.call("echo", x=1) == 2
            rpc = [e for e in profiler.snapshot_events()
                   if e["cat"] == "rpc"]
            client_span = next(e for e in rpc
                               if e["name"].startswith("echo"))
            handler = next(e for e in rpc
                           if e["name"] == "rpc_handler:echo")
            assert client_span["trace_id"] == ctx.trace_id
            assert handler["trace_id"] == ctx.trace_id
            assert handler["parent_id"] == client_span["span_id"]
            assert handler["span_id"] != client_span["span_id"]
            assert handler["args"]["ok"] is True
            # untraced calls stamp nothing
            cli.call("echo", x=5)
            handlers = [e for e in profiler.snapshot_events()
                        if e["name"] == "rpc_handler:echo"]
            assert handlers[-1]["trace_id"] is None
        finally:
            profiler.stop_profiler(profile_path="")
    finally:
        srv.shutdown()


def test_legacy_peers_keep_working_without_trace_or_offset():
    """Both compat directions of the hello extension: an old-frame
    server (rejects _hello) never sees _trace and records no offset; a
    legacy-pinned client (PADDLE_TPU_PS_PICKLE_WIRE=1) never probes and
    still interoperates — traced calls succeed in both cases."""
    from paddle_tpu.fluid import telemetry
    from paddle_tpu.fluid.ps_rpc import VarClient, VarServer

    seen = []

    def echo(x=0, **kw):
        seen.append(sorted(kw))
        return x + 1

    srv = VarServer("127.0.0.1:0", {"echo": echo},
                    legacy_wire=True).start()
    ep = f"127.0.0.1:{srv.port}"
    try:
        cli = VarClient(ep)
        assert not cli._telemetry_ok
        assert ep not in telemetry.clock_offsets()
        with telemetry.trace_scope():
            assert cli.call("echo", x=1) == 2
        assert seen == [[]]  # no _trace kwarg leaked into the handler
    finally:
        srv.shutdown()

    os.environ["PADDLE_TPU_PS_PICKLE_WIRE"] = "1"
    try:
        srv2 = VarServer("127.0.0.1:0",
                         {"echo": lambda x=0: x + 1}).start()
        ep2 = f"127.0.0.1:{srv2.port}"
        cli2 = VarClient(ep2)
        assert not cli2._telemetry_ok
        with telemetry.trace_scope():
            assert cli2.call("echo", x=3) == 4
        srv2.shutdown()
    finally:
        os.environ.pop("PADDLE_TPU_PS_PICKLE_WIRE", None)


def test_dedup_retry_replays_same_trace_id_with_new_span_id():
    """A PR 3 retry (same dedup token) executes ONCE; the replay is
    still followable: the server records a replay marker carrying the
    SAME trace id with a fresh server-side span id."""
    from paddle_tpu.fluid import profiler
    from paddle_tpu.fluid import ps_rpc
    from paddle_tpu.fluid.ps_rpc import VarServer, _send_msg, _recv_msg

    calls = []
    srv = VarServer("127.0.0.1:0",
                    {"bump": lambda: calls.append(1) or True}).start()
    profiler.start_profiler("CPU")
    try:
        def raw_call(msg):
            s = socket.create_connection(("127.0.0.1", srv.port), 5.0)
            try:
                _send_msg(s, dict(msg))
                return _recv_msg(s)
            finally:
                s.close()

        msg = {"method": "bump", "_dedup": ("cliX", 0),
               "_trace": ("traceT", "spanS")}
        r1 = raw_call(msg)
        r2 = raw_call(msg)  # the retry: replayed, never re-executed
        assert r1["ok"] and r2["ok"] and r1["result"] == r2["result"]
        assert len(calls) == 1
        handlers = [e for e in profiler.snapshot_events()
                    if e["name"] == "rpc_handler:bump"]
        assert len(handlers) == 2
        execution, replay = handlers
        assert {e["trace_id"] for e in handlers} == {"traceT"}
        assert {e["parent_id"] for e in handlers} == {"spanS"}
        assert execution["span_id"] != replay["span_id"]
        assert replay["args"] == {"dedup_replay": True}
        assert srv.stats()["bump"]["dedup_replays"] == 1
    finally:
        profiler.stop_profiler(profile_path="")
        srv.shutdown()


def test_stale_view_reroute_keeps_trace_id_across_owners():
    """A PR 6 re-route is ONE logical call: the refusing old owner and
    the executing new owner both record handler spans under the SAME
    trace id (new span ids), parented on the one client rpc span."""
    from paddle_tpu.fluid import core, profiler, ps_membership, telemetry
    from paddle_tpu.fluid.ps_rpc import VarClient, VarServer

    ps_membership.reset_views()
    slot = f"127.0.0.1:{free_port()}"
    srv_b = VarServer("127.0.0.1:0",
                      {"get_var": lambda name, trainer_id=0:
                       np.arange(3, dtype=np.float32)}).start()
    bind_b = f"127.0.0.1:{srv_b.port}"
    moved = ps_membership.ClusterView.initial([slot]).moved(
        slot, bind_b, epoch=1)

    def refuse(name, trainer_id=0):
        err = core.StaleClusterViewError(
            f"shard {slot} moved to {bind_b}")
        err.view_dict = moved.to_dict()
        raise err

    srv_a = VarServer(slot, {"get_var": refuse}).start()
    try:
        ps_membership.install_view(ps_membership.ClusterView.initial(
            [slot]))
        profiler.start_profiler("CPU")
        try:
            cli = VarClient(slot)
            with telemetry.trace_scope() as ctx:
                out = cli.call("get_var", name="v")
            np.testing.assert_array_equal(
                np.asarray(out), np.arange(3, dtype=np.float32))
            assert ps_membership.current_epoch() == 1
            evs = profiler.snapshot_events()
            handlers = [e for e in evs
                        if e["name"] == "rpc_handler:get_var"]
            client_spans = [e for e in evs
                            if e["name"].startswith("get_var:")]
            assert len(handlers) == 2  # refusal on A + execution on B
            assert {e["trace_id"] for e in handlers} == {ctx.trace_id}
            assert len({e["span_id"] for e in handlers}) == 2
            # one logical call: every handler parent is the client span
            assert {e["parent_id"] for e in handlers} == \
                {client_spans[0]["span_id"]}
            oks = sorted(e["args"]["ok"] for e in handlers)
            assert oks == [False, True]
        finally:
            profiler.stop_profiler(profile_path="")
    finally:
        srv_a.shutdown()
        srv_b.shutdown()
        ps_membership.reset_views()


# ======================================================================
# serving: X-Trace-Id + /metrics
# ======================================================================
@pytest.fixture(scope="module")
def mlp_engine_parts():
    from tools.serving_loadgen import build_mlp_serving_model
    prog, scope, out_name, feeds = build_mlp_serving_model(n_feeds=4)
    return prog, scope, out_name, feeds


def _mk_engine(parts, **kw):
    from paddle_tpu.serving import ServingEngine
    prog, scope, out_name, _ = parts
    kw.setdefault("num_workers", 2)
    kw.setdefault("max_batch", 8)
    return ServingEngine(program=prog, scope=scope, feed_names=["x"],
                         fetch_names=[out_name], **kw)


def _post(url, body, headers=None):
    req = urllib.request.Request(url, data=json.dumps(body).encode(),
                                 headers=headers or {})
    return urllib.request.urlopen(req, timeout=30)


def test_http_x_trace_id_round_trips_and_spans_carry_it(
        mlp_engine_parts):
    """Satellite: X-Trace-Id in → same id out (on every status);
    minted when absent; the engine's serve spans run under it."""
    from paddle_tpu.fluid import profiler
    from paddle_tpu.serving import ServingIngress

    eng = _mk_engine(mlp_engine_parts, name="traced-mlp")
    ing = ServingIngress({"mlp": eng}).start()
    x = mlp_engine_parts[3][0]["x"].tolist()
    profiler.start_profiler("CPU")
    try:
        r = _post(ing.url + "/predict", {"feed": {"x": x}},
                  {"X-Trace-Id": "req-42"})
        assert r.status == 200
        assert r.headers.get("X-Trace-Id") == "req-42"
        # minted when the client sends none
        r2 = _post(ing.url + "/predict", {"feed": {"x": x}})
        minted = r2.headers.get("X-Trace-Id")
        assert minted and len(minted) == 16 and minted != "req-42"
        # error paths carry the header too (bad feed -> 400)
        try:
            _post(ing.url + "/predict", {"feed": {"wrong": x}},
                  {"X-Trace-Id": "req-43"})
            raise AssertionError("expected 400")
        except urllib.error.HTTPError as e:
            assert e.code == 400
            assert e.headers.get("X-Trace-Id") == "req-43"
        serve = [e for e in profiler.snapshot_events()
                 if e["cat"] == "serve"]
        traced = [e for e in serve if e["trace_id"] == "req-42"]
        names = {e["name"].split("[")[0] for e in traced}
        assert "serve:queue_wait" in names
        assert "serve:exec" in names
        exec_span = next(e for e in traced
                         if e["name"].startswith("serve:exec"))
        assert "req-42" in exec_span["args"]["trace_ids"]
    finally:
        profiler.stop_profiler(profile_path="")
        ing.close()


def test_ingress_metrics_endpoint_matches_stats_exactly(
        mlp_engine_parts):
    """Acceptance leg: GET /metrics exposes the shed / deadline /
    degraded / request counters and the cache hit counters with values
    EQUAL to stats() — same underlying objects, no drift possible."""
    import re
    from paddle_tpu.serving import AdmissionController, ServingIngress
    from paddle_tpu.serving.embedding_cache import EmbeddingCache

    cache = EmbeddingCache(ttl_s=60.0, max_entries=64)
    eng = _mk_engine(mlp_engine_parts, name="m0",
                     admission=AdmissionController(max_queue_rows=4),
                     num_workers=1, embedding_cache=cache)
    ing = ServingIngress({"mlp": eng}).start()
    x = mlp_engine_parts[3][0]["x"].tolist()
    try:
        # light concurrent flood so sheds and OKs both happen
        errs = []

        def client(wid):
            for _ in range(12):
                try:
                    _post(ing.url + "/predict", {"feed": {"x": x}})
                except urllib.error.HTTPError as e:
                    if e.code not in (429, 504):
                        errs.append(e.code)
                except Exception as e:  # noqa: BLE001
                    errs.append(repr(e))

        ths = [threading.Thread(target=client, args=(w,))
               for w in range(8)]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        assert not errs, errs[:3]

        text = urllib.request.urlopen(
            ing.url + "/metrics", timeout=30).read().decode()
        st = eng.stats()

        def metric(name, labels='engine="m0"'):
            m = re.search(rf"^{name}{{{labels}}} (\S+)$", text, re.M)
            assert m, f"{name} missing from /metrics"
            return float(m.group(1))

        assert metric("serving_requests_total") == st["requests"]
        assert metric("serving_shed_total") == st["shed"]
        assert metric("serving_deadline_expired_total") == \
            st["deadline_expired"]
        assert metric("serving_degraded_total") == st["degraded"]
        assert metric("serving_cache_hits") == \
            st["embedding_cache"]["hits"]
        assert metric("serving_cache_hit_rate") == \
            st["embedding_cache"]["hit_rate"]
        # ingress's own counters are views over the same dict
        ist = ing.stats()["ingress"]
        m = re.search(r"^serving_ingress_requests (\S+)$", text, re.M)
        # requests moved between the scrape and stats(); allow the gap
        assert m and float(m.group(1)) <= ist["requests"]
        assert "# TYPE serving_requests_total counter" in text
    finally:
        ing.close()


def test_metrics_sidecar_server_and_flag_gate():
    from paddle_tpu.fluid import core, telemetry

    # flag 0 = off
    core.globals_["FLAGS_metrics_port"] = 0
    assert telemetry.maybe_start_metrics_server() is None
    port = telemetry.start_metrics_server(0)
    try:
        assert port and telemetry.metrics_server_port() == port
        telemetry.REGISTRY.counter("sidecar_probe_total").inc(3)
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10) \
            .read().decode()
        assert "sidecar_probe_total 3" in text
        ok = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=10)
        assert ok.status == 200
        # idempotent: a second start returns the same port
        assert telemetry.start_metrics_server(0) == port
    finally:
        telemetry.stop_metrics_server()


def test_executor_compile_and_retrace_counters():
    """Satellite: compile/retrace cache-miss counters — a repeated
    window K is cached (no growth), a NEW K after warm-up counts as a
    retrace; steady state stays flat."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import core, telemetry

    reg = telemetry.REGISTRY
    compiles = reg.counter("executor_compiles_total",
                           labelnames=("kind",))
    retraces = reg.counter("executor_retraces_total",
                           labelnames=("kind",))

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", shape=[4], dtype="float32")
        y = fluid.layers.fc(x, 2)
        loss = fluid.layers.mean(y)
        fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor()
    scope = core.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        step0 = compiles.value(kind="step")
        w0 = compiles.value(kind="window")
        rw0 = retraces.value(kind="window")
        exe.run(main, feed={"x": np.ones((2, 4), np.float32)},
                fetch_list=[loss])
        assert compiles.value(kind="step") > step0
        feed2 = {"x": np.ones((2, 2, 4), np.float32)}
        exe.run(main, feed=feed2, fetch_list=[loss], n_steps=2)
        assert compiles.value(kind="window") == w0 + 1
        assert retraces.value(kind="window") == rw0
        # same K again: cached, nothing moves (steady state is flat)
        exe.run(main, feed=feed2, fetch_list=[loss], n_steps=2)
        assert compiles.value(kind="window") == w0 + 1
        # a NEW K after warm-up is a retrace
        exe.run(main, feed={"x": np.ones((4, 2, 4), np.float32)},
                fetch_list=[loss], n_steps=4)
        assert compiles.value(kind="window") == w0 + 2
        assert retraces.value(kind="window") == rw0 + 1
        assert reg.counter("jax_backend_compiles_total").value() > 0


def test_jax_compile_counters_move_when_jax_compiles_and_only_then(
        tmp_path):
    """The listener's counters where JAX compiles: seconds tracing,
    lowering and in the backend, persistent-cache hits and misses. They
    move on the step's first compile and stand still from then on: step
    2, on the state step 1 handed back, is the signature step 1 ran on
    (the start-up program's state goes in committed, as a step's does).
    Each leaves a cat="compile" span or instant in a recording
    profiler."""
    import jax
    import paddle_tpu.fluid as fluid
    from jax.experimental.compilation_cache import compilation_cache
    from paddle_tpu.fluid import core, profiler, telemetry

    def snap():
        return {n: telemetry.REGISTRY.counter(n).value() for n in (
            "jax_trace_seconds_total", "jax_lower_seconds_total",
            "jax_backend_compile_seconds_total",
            "jax_backend_compiles_total", "jax_compile_cache_hits_total",
            "jax_compile_cache_misses_total")}

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", shape=[4], dtype="float32")
        loss = fluid.layers.mean(fluid.layers.fc(x, 3))
        fluid.optimizer.SGD(0.1).minimize(loss)
    exe, scope = fluid.Executor(), core.Scope()  # installs the listener
    feed = {"x": np.ones((2, 4), np.float32)}
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_enable_compilation_cache",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compilation_cache.reset_cache()
    profiler.start_profiler(state="CPU")
    try:
        exe.run(startup, scope=scope)
        before = snap()
        exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
        first = snap()
        moved = {n for n in first if first[n] > before[n]}
        assert moved == set(first) - {"jax_compile_cache_hits_total"}
        for _ in range(3):
            exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
        assert snap() == first
        spans = {e["name"] for e in profiler.snapshot_events()
                 if e["cat"] == "compile"}
        assert {"compile:trace", "compile:lower", "compile:backend",
                "compile:cache_miss", "compile:step"} <= spans
        # the same step from a second executor: compiled again by jax,
        # found in the persistent cache this time
        other = fluid.Executor()
        other.run(main, feed=feed, fetch_list=[loss], scope=scope)
        assert snap()["jax_compile_cache_hits_total"] \
            > first["jax_compile_cache_hits_total"]
        assert snap()["jax_compile_cache_misses_total"] \
            == first["jax_compile_cache_misses_total"]
    finally:
        profiler.stop_profiler(profile_path="")
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


# ======================================================================
# trace shards + timeline merge
# ======================================================================
def test_trace_shard_streams_ring_bounded_with_metadata(tmp_path):
    from paddle_tpu.fluid import core, profiler, telemetry

    core.globals_["FLAGS_trace_dir"] = str(tmp_path)
    core.globals_["FLAGS_trace_shard_max_events"] = 1024
    assert profiler.is_profiling()  # shard-only mode records
    with telemetry.trace_scope() as ctx:
        with profiler.RecordEvent("step", cat="segment"):
            pass
    path = telemetry.flush_trace_shard()
    shard = json.load(open(path))
    assert shard["metadata"]["pid"] == os.getpid()
    assert shard["metadata"]["anchor_wall_us"] > 0
    (ev,) = shard["traceEvents"]
    assert ev["name"] == "step" and ev["cat"] == "segment"
    assert ev["args"]["trace_id"] == ctx.trace_id
    # ring: the shard never exceeds the bound, drops are counted
    for i in range(1030):
        profiler.record_instant(f"i{i}")
    telemetry.flush_trace_shard()
    shard = json.load(open(path))
    assert len(shard["traceEvents"]) == 1024
    assert shard["metadata"]["dropped_events"] > 0


def test_timeline_merge_clock_corrects_with_hello_offsets(tmp_path):
    """Synthetic 2-shard merge: the pserver shard's clock is 100 s
    ahead; the trainer's measured hello offset must pull its spans
    back so the rpc→handler nesting is monotone in ONE clock."""
    from tools.timeline import merge_shards

    ep = "127.0.0.1:7001"
    # trainer: rpc span [1.0, 1.4] s on its own clock
    trainer = {
        "traceEvents": [
            {"name": "send:w@" + ep, "ph": "X", "pid": 1, "tid": 1,
             "ts": 1.0e6, "dur": 0.4e6, "cat": "rpc",
             "args": {"trace_id": "T", "span_id": "a"}}],
        "metadata": {"pid": 1, "role": "trainer0", "endpoint": None,
                     "anchor_wall_us": 5e6, "anchor_perf_us": 0.0,
                     "peer_offsets": {
                         ep: {"offset_us": 100.0e6, "rtt_us": 400.0}}},
    }
    # pserver: handler span inside the rpc window, on a clock +100 s
    pserver = {
        "traceEvents": [
            {"name": "rpc_handler:send", "ph": "X", "pid": 2, "tid": 9,
             "ts": 101.1e6, "dur": 0.2e6, "cat": "rpc",
             "args": {"trace_id": "T", "span_id": "b",
                      "parent_id": "a"}}],
        "metadata": {"pid": 2, "role": "pserver0", "endpoint": ep,
                     # wall anchor deliberately WRONG (1h off) to prove
                     # the measured offset wins over the fallback
                     "anchor_wall_us": 3600e6,
                     "anchor_perf_us": 100.0e6,
                     "peer_offsets": {}},
    }
    (tmp_path / "trace-1.json").write_text(json.dumps(trainer))
    (tmp_path / "trace-2.json").write_text(json.dumps(pserver))
    out = str(tmp_path / "timeline.json")
    summary = merge_shards(str(tmp_path), out=out, trace_id="T")
    assert summary["n_shards"] == 2 and summary["n_events"] == 2
    assert summary["processes"]["pserver0"]["source"] == "hello-offset"
    assert summary["processes"]["pserver0"]["delta_us"] == -100.0e6
    merged = json.load(open(out))
    spans = {e["args"]["trace_id"] + ":" + e["args"]["span_id"]: e
             for e in merged["traceEvents"] if e.get("ph") == "X"}
    rpc, handler = spans["T:a"], spans["T:b"]
    # clock-corrected monotone nesting: the handler runs INSIDE the
    # client call's window
    assert rpc["ts"] <= handler["ts"]
    assert handler["ts"] + handler["dur"] <= rpc["ts"] + rpc["dur"]
    # wall fallback kicks in when no offset links the shards
    trainer["metadata"]["peer_offsets"] = {}
    (tmp_path / "trace-1.json").write_text(json.dumps(trainer))
    summary = merge_shards(str(tmp_path), out=None)
    assert summary["processes"]["pserver0"]["source"] == "wall-anchor"


def test_varserver_stats_view_lands_in_registry():
    from paddle_tpu.fluid import telemetry
    from paddle_tpu.fluid.ps_rpc import VarClient, VarServer

    srv = VarServer("127.0.0.1:0", {"echo": lambda x=0: x}).start()
    ep = f"127.0.0.1:{srv.port}"
    try:
        cli = VarClient(ep)
        cli.call("echo", x=1)
        text = telemetry.REGISTRY.exposition()
        assert f'ps_server_echo_calls{{endpoint="{ep}"}}' in text
    finally:
        srv.shutdown()
    # unregistered at shutdown: the next scrape drops the view
    assert f'endpoint="{ep}"' not in telemetry.REGISTRY.exposition()


# ======================================================================
# multiprocess acceptance (slow): 2-trainer × 2-pserver wide_deep
# ======================================================================
@pytest.mark.slow
def test_cluster_timeline_merge_wide_deep_2x2_acceptance(tmp_path):
    """ISSUE 10 acceptance: a 2-trainer×2-pserver wide_deep run with
    FLAGS_trace_dir set produces one shard per process;
    tools/timeline.py merge combines them into a timeline where a
    single training round's trace id links the trainer's rpc spans to
    the owning pserver's handler spans — clock-corrected, with the
    handler inside the client call's span window (monotone ordering)."""
    from tools.chaos_ps import Cluster
    from tools.timeline import merge_shards

    trace_dir = tmp_path / "shards"
    trace_dir.mkdir()
    run = Cluster(str(tmp_path), model="wide_deep", trainers=2,
                  n_pservers=2, steps=5, hb=10.0, step_sleep=0.0,
                  sparse_dim=64, batch=16, tag="obs",
                  env_extra={"FLAGS_trace_dir": str(trace_dir)})
    try:
        run.start_servers()
        run.start_trainers()
        run.join_trainers(timeout=420.0)
        # pserver shards flush on the ~2s background cadence — give the
        # last round's handler spans one beat to land before the kill
        time.sleep(4.0)
    finally:
        run.shutdown()

    out = str(tmp_path / "timeline.json")
    summary = merge_shards(str(trace_dir), out=out, ref="trainer0")
    assert summary["n_shards"] >= 4, summary  # 2 trainers + 2 pservers
    roles = set(summary["processes"])
    assert {"trainer0", "trainer1"} <= roles
    assert sum(1 for r in roles if r.startswith("pserver")) == 2
    # every pserver shard was aligned by a MEASURED hello offset
    for role, info in summary["processes"].items():
        if role.startswith("pserver"):
            assert info["source"] == "hello-offset", summary

    merged = json.load(open(out))
    events = [e for e in merged["traceEvents"] if e.get("ph") == "X"]
    pid_role = {e["pid"]: e["args"]["name"]
                for e in merged["traceEvents"] if e.get("ph") == "M"}
    trainer_pids = {p for p, r in pid_role.items()
                    if r.startswith("trainer")}
    pserver_pids = {p for p, r in pid_role.items()
                    if r.startswith("pserver")}

    # pick a training round's trace: a trainer rpc span whose trace id
    # also appears on a pserver handler span
    by_trace = {}
    for e in events:
        tid = (e.get("args") or {}).get("trace_id")
        if tid:
            by_trace.setdefault(tid, []).append(e)
    linked = 0
    for tid, evs in by_trace.items():
        rpc = [e for e in evs if e["pid"] in trainer_pids
               and e["cat"] == "rpc"
               and not e["name"].startswith("rpc_handler")]
        handlers = [e for e in evs if e["pid"] in pserver_pids
                    and e["name"].startswith("rpc_handler")]
        if not (rpc and handlers):
            continue
        linked += 1
        spans = {e["args"]["span_id"]: e for e in rpc}
        for h in handlers:
            parent = spans.get(h["args"].get("parent_id"))
            if parent is None:
                continue
            # clock-corrected monotone ordering: the handler span nests
            # inside its client rpc span (generous slack for the
            # single-sample offset estimate on a loaded 1-core box)
            slack = 50e3  # 50 ms in us
            assert parent["ts"] - slack <= h["ts"], (tid, parent, h)
            assert h["ts"] + h["dur"] <= \
                parent["ts"] + parent["dur"] + slack, (tid, parent, h)
    # rounds from BOTH trainers must have linked trainer→pserver traces
    assert linked >= 4, (linked, summary)


# ======================================================================
# the step record (docs/OBSERVABILITY.md "Step record")
# ======================================================================
STAGE_FIELDS = ("feed_s", "lookup_s", "place_s", "dispatch_s",
                "write_back_s", "fetch_s")


class HookedFeed:
    """A feed value that runs `hook` where the executor makes an array
    of it, inside `exe:feed`."""

    def __init__(self, value, hook):
        self.value, self.hook = value, hook

    def __array__(self, dtype=None, copy=None):
        self.hook()
        return self.value


def _step_program(width=8, stateful=False):
    import paddle_tpu.fluid as fluid
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", shape=[width], dtype="float32")
        y = fluid.data("y", shape=[1], dtype="int64")
        h = fluid.layers.fc(x, 16, act="relu")
        if stateful:  # no whole-block jit: the step runs in segments
            arr = fluid.layers.create_array("float32")
            i = fluid.layers.fill_constant([1], "int64", 0)
            fluid.layers.array_write(h, i, arr)
        p = fluid.layers.fc(h, 4, act="softmax")
        loss = fluid.layers.mean(fluid.layers.cross_entropy(p, y))
        fluid.optimizer.SGD(0.1).minimize(loss)
    feed = {"x": np.ones((4, width), np.float32),
            "y": np.zeros((4, 1), np.int64)}
    return main, startup, loss, feed


def _started(main, startup):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import core
    exe, scope = fluid.Executor(), core.Scope()
    exe.run(startup, scope=scope)
    return exe, scope


def _records_since(seq):
    from paddle_tpu.fluid import telemetry
    return [r for r in list(telemetry.STEPS) if r.seq > seq]


@pytest.mark.parametrize("path", [
    "one_dispatch", "window", "window_of_batches", "segmented",
    "interpreted", "compiled_program", "window_fallback"])
def test_every_run_leaves_one_step_record_with_nothing_switched_on(path):
    """No session, no shard, no flag: a record a run on every path
    through `Executor._run`, the stages' sum inside `run_s`; a run that
    re-enters `run()` on its thread joins the record that is open."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import core, profiler, telemetry
    main, startup, loss, feed = _step_program(
        stateful=path in ("segmented", "window_fallback"))
    stacked = {k: np.stack([v] * 3) for k, v in feed.items()}
    kwargs, mode, dispatches = {"feed": feed}, "compiled", True
    if path == "window":
        kwargs["n_steps"] = 3
    elif path == "window_of_batches":
        kwargs.update(feed=stacked, n_steps=3)
    elif path == "segmented":
        mode = "segmented"
    elif path == "window_fallback":  # three inner runs, one record
        kwargs.update(feed=stacked, n_steps=3)
        mode = "segmented"
    elif path == "interpreted":
        core.globals_["FLAGS_executor_mode"] = "interpreted"
        mode, dispatches = "interpreted", False
    try:
        exe, scope = _started(main, startup)
        assert not profiler.is_profiling()
        startup_record = telemetry.STEPS[-1]
        program = fluid.CompiledProgram(main) \
            if path == "compiled_program" else main
        for _ in range(4):
            exe.run(program, fetch_list=[loss], scope=scope, **kwargs)
    finally:
        core.globals_["FLAGS_executor_mode"] = "compiled"
    assert exe._last_run_mode == mode
    records = _records_since(startup_record.seq)
    assert len(records) == 4
    assert [r.seq for r in records] == list(range(records[0].seq,
                                                  records[0].seq + 4))
    assert len({r.block for r in records}) == 1
    assert records[0].block != startup_record.block
    assert records[0].compiles > 0 or path == "interpreted"
    for prev, r in zip(records, records[1:]):
        stages = sum(getattr(r, f) for f in STAGE_FIELDS)
        assert 0 < stages <= r.run_s and r.self_s == r.run_s - stages
        assert r.since_prev_s >= 0
        assert r.t0 == pytest.approx(prev.t0 + prev.run_s + r.since_prev_s,
                                     abs=1e-6)
        assert 0 <= r.cpu_s and r.gc_s >= 0
        assert (r.dispatch_s > 0) == dispatches
        # float32 and, on the device, int32: four bytes an element
        assert r.feed_s > 0 and r.feed_bytes == sum(
            4 * v.size for v in kwargs["feed"].values())
    assert telemetry.open_step() is None


def test_the_step_ring_holds_4096_records_and_drops_the_oldest():
    from paddle_tpu.fluid import profiler, telemetry
    assert telemetry.STEP_RING == telemetry.STEPS.maxlen == 4096
    for _ in range(4100):
        with profiler.RecordEvent(telemetry.RUN_SPAN, cat="executor"):
            pass
    records = list(telemetry.STEPS)
    assert len(records) == 4096
    assert records[0].seq == records[-1].seq - 4095


def test_a_collection_inside_a_step_is_in_its_record_and_the_registry():
    import gc
    from paddle_tpu.fluid import telemetry
    main, startup, loss, feed = _step_program()
    exe, scope = _started(main, startup)
    exe.run(main, feed=feed, fetch_list=[loss], scope=scope)

    def counters():
        return [telemetry.REGISTRY.get(name).value(generation=2) for name
                in ("python_gc_seconds_total",
                    "python_gc_collections_total")]
    before = counters()
    hooked = dict(feed, x=HookedFeed(feed["x"], gc.collect))
    exe.run(main, feed=hooked, fetch_list=[loss], scope=scope)
    record = telemetry.STEPS[-1]
    after = counters()
    assert record.gc_gen == 2
    assert 0 < record.gc_s <= record.feed_s <= record.run_s
    assert after[1] == before[1] + 1
    assert after[0] - before[0] == pytest.approx(record.gc_s, rel=0.5)
    # and the step after it held none of generation 2
    exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    assert telemetry.STEPS[-1].gc_gen < 2


def test_a_slow_step_is_flagged_once_and_says_where_it_sat(caplog):
    """A feed hook sleeps in a block's 3rd step and in its 12th: the
    first 8 records of a block are never judged, the 12th is flagged —
    counted, kept, one warning line — with the sleep in `feed_s`."""
    from paddle_tpu.fluid import profiler, telemetry
    main, startup, loss, feed = _step_program()
    exe, scope = _started(main, startup)
    slow_feed = dict(feed, x=HookedFeed(feed["x"], lambda: time.sleep(0.2)))
    counter = telemetry.REGISTRY.get("executor_slow_steps_total")
    before, kept = counter.value(), len(telemetry.SLOW_STEPS)
    profiler.start_profiler(state="CPU")
    try:
        with caplog.at_level("WARNING", logger="paddle_tpu.executor"):
            for step in range(1, 15):
                exe.run(main, feed=slow_feed if step in (3, 12) else feed,
                        fetch_list=[loss], scope=scope)
        instants = [e for e in profiler.snapshot_events()
                    if e["name"] == "exe:slow_step"]
    finally:
        profiler.stop_profiler(profile_path="")
    records = list(telemetry.STEPS)[-14:]
    assert records[2].feed_s >= 0.2 and records[11].feed_s >= 0.2
    # (a loaded machine may stall another step of the fourteen past the
    # rule's 50 ms: it is then flagged too, and as truly)
    flagged = list(telemetry.SLOW_STEPS)[kept:]
    assert flagged.count(records[11]) == 1
    assert not set(flagged) & set(records[:8])
    assert counter.value() == before + len(flagged)
    lines = [r.getMessage() for r in caplog.records
             if r.name == "paddle_tpu.executor" and r.levelname == "WARNING"]
    assert len(lines) == len(flagged)
    (text,) = [t for t in lines if f'"seq": {records[11].seq},' in t]
    assert text.startswith("slow step: ") and "\n" not in text
    assert "medians of block" in text
    (instant,) = [e for e in instants
                  if e["args"]["seq"] == records[11].seq]
    assert instant["cat"] == "executor"
    assert instant["args"]["feed_s"] == records[11].feed_s
    summary = telemetry.step_summary()
    assert records[11].seq in [r["seq"] for r in summary["slow"]]
    # the caller's own time counts too: it is the step's period that is
    # judged, and `since_prev_s` that holds it
    time.sleep(0.2)
    exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    assert telemetry.SLOW_STEPS[-1] is telemetry.STEPS[-1]
    assert telemetry.STEPS[-1].since_prev_s >= 0.2


def test_two_threads_running_two_executors_keep_their_records_apart():
    from paddle_tpu.fluid import telemetry
    steps, widths = 12, (8, 24)
    programs = [_step_program(width) for width in widths]
    ready = [_started(main, startup) for main, startup, _, _ in programs]
    seq = telemetry.STEPS[-1].seq
    errors = []

    def loop(k):
        try:
            (main, _, loss, feed), (exe, scope) = programs[k], ready[k]
            for _ in range(steps):
                exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
        except Exception as e:  # surfaced below
            errors.append(e)
    threads = [threading.Thread(target=loop, args=(k,)) for k in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors and not any(t.is_alive() for t in threads)
    by_block = {}
    for r in _records_since(seq):
        by_block.setdefault(r.block, []).append(r)
    assert sorted(len(rs) for rs in by_block.values()) == [steps, steps]
    sizes = set()
    for rs in by_block.values():
        (nbytes,) = {r.feed_bytes for r in rs}  # its own feed, every step
        sizes.add(nbytes)
        assert rs[0].since_prev_s == 0  # the thread's first run
        for prev, r in zip(rs, rs[1:]):
            # each thread's clock: a record starts where the one before
            # it on THAT thread ended, whatever the other thread did
            assert r.t0 == pytest.approx(
                prev.t0 + prev.run_s + r.since_prev_s, abs=1e-6)
            assert sum(getattr(r, f) for f in STAGE_FIELDS) <= r.run_s
    assert sizes == {4 * w * 4 + 4 * 4 for w in widths}


def test_step_summary_round_trips_through_the_shards_metadata(tmp_path):
    """`FLAGS_trace_dir`: the shard holds the six stages inside one
    `exe:run` a step, and its metadata the summary of the ring."""
    from paddle_tpu.fluid import core, telemetry
    main, startup, loss, feed = _step_program()
    exe, scope = _started(main, startup)
    core.globals_["FLAGS_trace_dir"] = str(tmp_path)
    for _ in range(5):
        exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    shard = json.load(open(telemetry.flush_trace_shard()))
    summary = telemetry.step_summary()
    assert shard["metadata"]["step_summary"] == json.loads(
        json.dumps(summary))
    block = summary["blocks"][str(telemetry.STEPS[-1].block)]
    mine = [r for r in telemetry.STEPS
            if r.block == telemetry.STEPS[-1].block]
    assert block["n"] == len(mine) == 5
    assert block["median"]["run_s"] == sorted(r.run_s for r in mine)[2]
    assert block["p95"]["run_s"] == max(r.run_s for r in mine)
    assert set(block["median"]) == set(block["p95"]) \
        == set(telemetry.StepRecord.__slots__[3:]) | {"self_s"}
    events = [e for e in shard["traceEvents"] if e["cat"] == "executor"]
    runs = [e for e in events if e["name"] == "exe:run"]
    assert len(runs) == 5 and len(events) == 5 * 7
    for run, record in zip(runs, mine):
        assert run["ts"] == pytest.approx(record.t0 * 1e6)
        inside = [e["name"] for e in events if e is not run
                  and run["ts"] <= e["ts"]
                  and e["ts"] + e["dur"] <= run["ts"] + run["dur"]]
        assert inside == ["exe:feed", "exe:lookup", "exe:place",
                          "compiled_step", "exe:write_back", "exe:fetch"]
        assert {e["args"]["trace_id"] for e in events
                if e["ts"] >= run["ts"]
                and e["ts"] <= run["ts"] + run["dur"]} \
            == {run["args"]["trace_id"]}
