"""Serving-plane load generator — closed- and open-loop traffic against
a ServingEngine, in-process or over the HTTP ingress
(docs/SERVING.md "Load-generator methodology" + "Ingress & overload").

Library (tests/test_serving*.py import these):
  * ``run_closed_loop(predict, feeds, clients, duration_s)`` — N client
    threads, each submits its next request the moment the previous one
    completes (throughput-under-concurrency; latency EXCLUDES client
    think time).
  * ``run_open_loop(submit, feeds, rate_qps, duration_s)`` — one pacing
    thread fires async submits on a fixed-rate schedule regardless of
    completions (latency-under-load; queueing delay INCLUDED — the
    number a p99 SLO is about). Reports ``behind`` when the pacer
    cannot hold the target rate.
  * ``HttpClient`` / ``run_http_closed_loop`` / ``run_http_open_loop``
    — the same two disciplines through a live ``ServingIngress``,
    classifying statuses (200/429/504/5xx) instead of raising: under
    deliberate overload a typed shed is a RESULT, not an error.
  * ``run_overload_scenario`` — measures 1× HTTP capacity closed-loop,
    then drives open-loop at 1× and ``overload_factor``× and reports
    accepted-request p99s, shed/expired counts, and the "every
    non-accepted request answered typed" check.
  * ``run_chaos_scenario`` — kills a pserver mid-HTTP-serving and
    reports degraded (stale-cache) responses, 5xx counts for
    cache-covered rows, and recovery after a PR 6-style promotion.
  * ``run_http_fleet_closed_loop`` / ``run_http_fleet_open_loop`` —
    the same two disciplines spread over a serving FLEET via
    ``serving.FleetRouter`` (round-robin + retry-on-503/reset, live
    directory view), reporting a per-endpoint status/latency breakdown
    and the reroute count (docs/SERVING.md "Fleet").
  * ``start_inproc_pserver`` / ``push_table`` — the in-process
    listen_and_serv harness the serving PS lanes and tests run against
    (same shape as tests/test_ps_membership.py's protocol harness).

CLI (manual runs)::

    JAX_PLATFORMS=cpu python tools/serving_loadgen.py \
        --clients 16 --duration 3 --max-batch 16 --mode closed
    python tools/serving_loadgen.py --mode open --rate 500 --naive
    python tools/serving_loadgen.py --mode http                 # closed over HTTP
    python tools/serving_loadgen.py --mode http --scenario overload
    python tools/serving_loadgen.py --mode http --scenario chaos
    python tools/serving_loadgen.py --mode http \
        --endpoints 127.0.0.1:8801,127.0.0.1:8802   # fleet round-robin
    python tools/serving_loadgen.py --mode http --directory 127.0.0.1:8700 \
        --fleet-loop open --rate 300                # follow the live view

Prints one JSON line: loadgen results + the engine's stats() surface
(including the shed / deadline_expired / degraded / breaker_open
overload counters).
"""
from __future__ import annotations

import argparse
import http.client
import json
import os
import sys
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _percentiles(lats_s: Sequence[float]) -> Dict[str, float]:
    from paddle_tpu.serving.engine import percentiles_ms
    return percentiles_ms(lats_s, suffix="_ms")


def run_closed_loop(predict: Callable[[dict], object],
                    feeds: Sequence[dict], clients: int = 16,
                    duration_s: float = 3.0,
                    warmup_s: float = 0.5) -> Dict[str, float]:
    """Closed loop: ``clients`` threads call ``predict(feed)`` back to
    back for ``duration_s`` (after ``warmup_s`` whose samples are
    discarded — first-touch compiles and cold caches must not land in
    the percentiles). Returns qps + latency percentiles over the
    measured window."""
    results: List[List] = [[] for _ in range(clients)]
    errors: List[BaseException] = []
    go = threading.Event()
    t_box = {}

    def worker(wid: int):
        rs = results[wid]
        go.wait()
        end = t_box["t0"] + warmup_s + duration_s
        i = wid
        while time.perf_counter() < end:
            feed = feeds[i % len(feeds)]
            i += clients
            t = time.perf_counter()
            try:
                predict(feed)
            except BaseException as e:  # surface, don't hang the join
                errors.append(e)
                return
            rs.append((time.perf_counter(), t))

    threads = [threading.Thread(target=worker, args=(w,), daemon=True)
               for w in range(clients)]
    for t in threads:
        t.start()
    t_box["t0"] = time.perf_counter()
    go.set()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    cut = t_box["t0"] + warmup_s
    done = sorted((td, td - ts) for rs in results for td, ts in rs
                  if ts >= cut)
    if not done:
        return {"qps": 0.0, "n": 0, "clients": clients,
                **_percentiles([])}
    span = done[-1][0] - cut
    out = {"qps": len(done) / span if span > 1e-9 else 0.0,
           "n": len(done), "clients": clients,
           "duration_s": round(span, 3)}
    out.update(_percentiles([lat for _t, lat in done]))
    return out


def run_open_loop(submit: Callable[[dict], object], feeds: Sequence[dict],
                  rate_qps: float, duration_s: float = 3.0,
                  timeout_s: float = 120.0) -> Dict[str, float]:
    """Open loop: submit async requests at ``rate_qps`` for
    ``duration_s``; latency = submit→fulfilment (futures must expose
    ``.wait(timeout)`` and ``.t_submit``/``.t_done`` stamps — the
    serving Request contract). ``behind`` counts schedule slots the
    pacer missed (the engine saturated: achieved rate < target)."""
    if rate_qps <= 0:
        raise ValueError("rate_qps must be > 0")
    period = 1.0 / float(rate_qps)
    futs = []
    behind = 0
    start = time.perf_counter()
    next_t = start
    i = 0
    while True:
        now = time.perf_counter()
        if now >= start + duration_s:
            break
        if now < next_t:
            time.sleep(next_t - now)
        fut = submit(feeds[i % len(feeds)])
        futs.append(fut)
        i += 1
        next_t += period
        if time.perf_counter() > next_t + period:
            behind += 1
    for f in futs:
        f.wait(timeout_s)
    lats = [f.t_done - f.t_submit for f in futs]
    span = (max(f.t_done for f in futs) - start) if futs else 0.0
    out = {"target_qps": float(rate_qps),
           "qps": len(futs) / span if span > 1e-9 else 0.0,
           "n": len(futs), "behind": behind,
           "duration_s": round(span, 3)}
    out.update(_percentiles(lats))
    return out


# ------------------------------------------------------------------ HTTP
class HttpClient:
    """One keep-alive connection to a ServingIngress; reconnects once
    on transport failure (a drained server sends Connection: close —
    the next call must not die on the stale socket). ``predict``
    returns ``(status, body_dict)`` instead of raising on 4xx/5xx:
    under deliberate overload a typed shed is a RESULT to count."""

    def __init__(self, host: str, port: int, timeout: float = 60.0):
        self.host, self.port, self.timeout = host, int(port), timeout
        self._conn: Optional[http.client.HTTPConnection] = None

    def _request(self, method: str, path: str, body=None, headers=None):
        last = None
        for attempt in (0, 1):
            try:
                if self._conn is None:
                    self._conn = http.client.HTTPConnection(
                        self.host, self.port, timeout=self.timeout)
                self._conn.request(method, path, body=body,
                                   headers=headers or {})
                r = self._conn.getresponse()
                data = r.read()
                if r.will_close:
                    self._conn.close()
                    self._conn = None
                try:
                    obj = json.loads(data) if data else {}
                except ValueError:
                    obj = {"raw": data.decode("utf-8", "replace")}
                return r.status, r, obj
            except (http.client.HTTPException, OSError) as e:
                last = e
                if self._conn is not None:
                    try:
                        self._conn.close()
                    except OSError:
                        pass
                    self._conn = None
        raise last

    def predict(self, feed: dict, model: Optional[str] = None,
                deadline_ms: Optional[float] = None, many: bool = False,
                extra_headers: Optional[dict] = None):
        path = ("/predict" if model is None
                else f"/models/{model}/predict")
        body = json.dumps({
            "feed": {k: (np.asarray(v).tolist()) for k, v in feed.items()},
            "many": many})
        headers = {"Content-Type": "application/json"}
        if deadline_ms is not None:
            headers["X-Deadline-Ms"] = str(float(deadline_ms))
        if extra_headers:
            headers.update(extra_headers)
        status, _r, obj = self._request("POST", path, body, headers)
        return status, obj

    def get(self, path: str):
        status, _r, obj = self._request("GET", path)
        return status, obj

    def close(self):
        if self._conn is not None:
            try:
                self._conn.close()
            except OSError:
                pass
            self._conn = None


def _status_key(status: int) -> str:
    if status == 200:
        return "ok"
    if status in (429, 503, 504):
        return str(status)
    return "5xx" if status >= 500 else str(status)


def run_http_closed_loop(host: str, port: int, feeds: Sequence[dict],
                         clients: int = 16, duration_s: float = 3.0,
                         warmup_s: float = 0.5,
                         deadline_ms: Optional[float] = None,
                         model: Optional[str] = None) -> Dict[str, float]:
    """Closed loop over the HTTP ingress: qps/percentiles of ACCEPTED
    (200) responses + a status histogram. Non-200s don't stop a client
    — they count."""
    results: List[List] = [[] for _ in range(clients)]
    counts: List[Dict[str, int]] = [{} for _ in range(clients)]
    degraded = [0] * clients
    go = threading.Event()
    t_box = {}

    def worker(wid: int):
        cli = HttpClient(host, port)
        rs = results[wid]
        cs = counts[wid]
        go.wait()
        end = t_box["t0"] + warmup_s + duration_s
        i = wid
        while time.perf_counter() < end:
            feed = feeds[i % len(feeds)]
            i += clients
            t = time.perf_counter()
            try:
                status, obj = cli.predict(feed, model=model,
                                          deadline_ms=deadline_ms)
            except OSError:
                cs["transport"] = cs.get("transport", 0) + 1
                continue
            key = _status_key(status)
            cs[key] = cs.get(key, 0) + 1
            if status == 200:
                rs.append((time.perf_counter(), t))
                if obj.get("degraded"):
                    degraded[wid] += 1
        cli.close()

    threads = [threading.Thread(target=worker, args=(w,), daemon=True)
               for w in range(clients)]
    for t in threads:
        t.start()
    t_box["t0"] = time.perf_counter()
    go.set()
    for t in threads:
        t.join()
    cut = t_box["t0"] + warmup_s
    done = sorted((td, td - ts) for rs in results for td, ts in rs
                  if ts >= cut)
    hist: Dict[str, int] = {}
    for cs in counts:
        for k, v in cs.items():
            hist[k] = hist.get(k, 0) + v
    span = (done[-1][0] - cut) if done else 0.0
    out = {"qps": len(done) / span if span > 1e-9 else 0.0,
           "n_ok": len(done), "clients": clients,
           "statuses": dict(sorted(hist.items())),
           "degraded_ok": int(sum(degraded)),
           "duration_s": round(span, 3)}
    out.update(_percentiles([lat for _t, lat in done]))
    return out


def run_http_open_loop(host: str, port: int, feeds: Sequence[dict],
                       rate_qps: float, duration_s: float = 3.0,
                       clients: int = 16,
                       deadline_ms: Optional[float] = None,
                       model: Optional[str] = None) -> Dict[str, float]:
    """Open loop over HTTP: a pacer schedules requests at ``rate_qps``
    regardless of completions; ``clients`` sender threads carry them.
    Latency is scheduled-time → response (client-side queueing counts
    against the server — the SLO view). This only holds the offered
    rate if the server answers FAST (accepted or typed-shed): senders
    blocked past their slot surface as ``behind``."""
    import queue as _queue

    if rate_qps <= 0:
        raise ValueError("rate_qps must be > 0")
    period = 1.0 / float(rate_qps)
    q: "_queue.Queue" = _queue.Queue()
    # accepted (200) latencies, BOTH clocks: from request send (what
    # the SERVER did to the request — the accepted-p99 contract) and
    # from the pacing schedule (includes client-side sender queueing:
    # honest about coordinated omission, but on a closed sender pool
    # at deliberate overload it measures the harness, not the server —
    # `behind` carries that debt explicitly)
    acc: List[tuple] = []       # (lat_from_send, lat_from_sched)
    hist: Dict[str, int] = {}
    degraded = [0]
    behind = [0]
    lock = threading.Lock()

    def sender():
        cli = HttpClient(host, port)
        while True:
            item = q.get()
            if item is None:
                break
            t_sched, feed = item
            t_start = time.perf_counter()
            if t_start > t_sched + period:
                with lock:
                    behind[0] += 1
            try:
                status, obj = cli.predict(feed, model=model,
                                          deadline_ms=deadline_ms)
            except OSError:
                with lock:
                    hist["transport"] = hist.get("transport", 0) + 1
                continue
            t_done = time.perf_counter()
            with lock:
                key = _status_key(status)
                hist[key] = hist.get(key, 0) + 1
                if status == 200:
                    acc.append((t_done - t_start, t_done - t_sched))
                    if obj.get("degraded"):
                        degraded[0] += 1
        cli.close()

    senders = [threading.Thread(target=sender, daemon=True)
               for _ in range(clients)]
    for t in senders:
        t.start()
    start = time.perf_counter()
    next_t = start
    i = 0
    while time.perf_counter() < start + duration_s:
        now = time.perf_counter()
        if now < next_t:
            time.sleep(min(next_t - now, 0.05))
            continue
        q.put((next_t, feeds[i % len(feeds)]))
        i += 1
        next_t += period
    for _ in senders:
        q.put(None)
    for t in senders:
        t.join()
    n_offered = i
    out = {"target_qps": float(rate_qps), "offered": n_offered,
           "accepted": len(acc),
           "accepted_rate": len(acc) / max(n_offered, 1),
           "behind": behind[0], "clients": clients,
           "statuses": dict(sorted(hist.items())),
           "degraded_ok": degraded[0]}
    out.update(_percentiles([lat for lat, _s in acc]))
    sched = _percentiles([s for _lat, s in acc])
    out.update({f"sched_{k}": v for k, v in sched.items()})
    return out


# ----------------------------------------------------------- fleet loops
def _fleet_router(endpoints, directory_ep, timeout_s=60.0):
    from paddle_tpu.serving import FleetRouter

    return FleetRouter(directory_ep=directory_ep,
                       endpoints=endpoints or None, timeout_s=timeout_s)


def _merge_by_endpoint(routers) -> Dict[str, Dict[str, float]]:
    """Aggregate the per-worker routers' per-endpoint breakdowns into
    one table with derived mean latency — the multi-endpoint report
    (docs/SERVING.md "Fleet") that shows WHERE the 503s/resets landed
    and that the retried requests were absorbed elsewhere."""
    agg: Dict[str, Dict[str, float]] = {}
    for r in routers:
        for ep, d in r.stats()["by_endpoint"].items():
            a = agg.setdefault(ep, {})
            for k, v in d.items():
                a[k] = a.get(k, 0) + v
    for d in agg.values():
        n = d.pop("lat_n", 0)
        s = d.pop("lat_sum_ms", 0.0)
        if n:
            d["lat_mean_ms"] = round(s / n, 3)
    return {ep: dict(sorted(d.items())) for ep, d in sorted(agg.items())}


def run_http_fleet_closed_loop(endpoints: Sequence[str], feeds,
                               clients: int = 16, duration_s: float = 3.0,
                               warmup_s: float = 0.5,
                               deadline_ms: Optional[float] = None,
                               model: Optional[str] = None,
                               directory_ep: Optional[str] = None
                               ) -> Dict[str, float]:
    """Closed loop spread over a serving FLEET: each client thread owns
    a ``FleetRouter`` (round-robin + retry across members on 503/
    connection-reset, live-view refresh when ``directory_ep`` is
    given). Reports the single-endpoint shape PLUS ``by_endpoint`` and
    ``reroutes`` — a rolling restart shows up as per-member 503 counts
    with zero client-visible failures."""
    from paddle_tpu.serving import NoLiveMembersError

    results: List[List] = [[] for _ in range(clients)]
    counts: List[Dict[str, int]] = [{} for _ in range(clients)]
    routers = [_fleet_router(list(endpoints), directory_ep)
               for _ in range(clients)]
    go = threading.Event()
    t_box = {}

    def worker(wid: int):
        router = routers[wid]
        rs, cs = results[wid], counts[wid]
        go.wait()
        end = t_box["t0"] + warmup_s + duration_s
        i = wid
        while time.perf_counter() < end:
            feed = feeds[i % len(feeds)]
            i += clients
            t = time.perf_counter()
            try:
                status, obj = router.predict(feed, model=model,
                                             deadline_ms=deadline_ms)
            except NoLiveMembersError:
                cs["no_live"] = cs.get("no_live", 0) + 1
                time.sleep(0.05)
                continue
            key = _status_key(status)
            cs[key] = cs.get(key, 0) + 1
            if status == 200:
                rs.append((time.perf_counter(), t))
        router.close()

    threads = [threading.Thread(target=worker, args=(w,), daemon=True)
               for w in range(clients)]
    for t in threads:
        t.start()
    t_box["t0"] = time.perf_counter()
    go.set()
    for t in threads:
        t.join()
    cut = t_box["t0"] + warmup_s
    done = sorted((td, td - ts) for rs in results for td, ts in rs
                  if ts >= cut)
    hist: Dict[str, int] = {}
    for cs in counts:
        for k, v in cs.items():
            hist[k] = hist.get(k, 0) + v
    span = (done[-1][0] - cut) if done else 0.0
    out = {"qps": len(done) / span if span > 1e-9 else 0.0,
           "n_ok": len(done), "clients": clients,
           "statuses": dict(sorted(hist.items())),
           "reroutes": int(sum(r.stats()["reroutes"] for r in routers)),
           "by_endpoint": _merge_by_endpoint(routers),
           "duration_s": round(span, 3)}
    out.update(_percentiles([lat for _t, lat in done]))
    return out


def run_http_fleet_open_loop(endpoints: Sequence[str], feeds,
                             rate_qps: float, duration_s: float = 3.0,
                             clients: int = 16,
                             deadline_ms: Optional[float] = None,
                             model: Optional[str] = None,
                             directory_ep: Optional[str] = None
                             ) -> Dict[str, float]:
    """Open loop over a fleet: same pacer/sender-pool contract as
    ``run_http_open_loop`` (scheduled-time latency, ``behind`` debt)
    with the routing layer of the closed-loop variant — the chaos
    scenario's load shape (a kill mid-run must NOT dent the accepted
    rate beyond the retried requests' extra hop)."""
    import queue as _queue

    from paddle_tpu.serving import NoLiveMembersError

    if rate_qps <= 0:
        raise ValueError("rate_qps must be > 0")
    period = 1.0 / float(rate_qps)
    q: "_queue.Queue" = _queue.Queue()
    acc: List[tuple] = []
    hist: Dict[str, int] = {}
    behind = [0]
    lock = threading.Lock()
    routers = [_fleet_router(list(endpoints), directory_ep)
               for _ in range(clients)]

    def sender(wid: int):
        router = routers[wid]
        while True:
            item = q.get()
            if item is None:
                break
            t_sched, feed = item
            t_start = time.perf_counter()
            if t_start > t_sched + period:
                with lock:
                    behind[0] += 1
            try:
                status, obj = router.predict(feed, model=model,
                                             deadline_ms=deadline_ms)
            except NoLiveMembersError:
                with lock:
                    hist["no_live"] = hist.get("no_live", 0) + 1
                continue
            t_done = time.perf_counter()
            with lock:
                key = _status_key(status)
                hist[key] = hist.get(key, 0) + 1
                if status == 200:
                    acc.append((t_done - t_start, t_done - t_sched))
        router.close()

    senders = [threading.Thread(target=sender, args=(w,), daemon=True)
               for w in range(clients)]
    for t in senders:
        t.start()
    start = time.perf_counter()
    next_t = start
    i = 0
    while time.perf_counter() < start + duration_s:
        now = time.perf_counter()
        if now < next_t:
            time.sleep(min(next_t - now, 0.05))
            continue
        q.put((next_t, feeds[i % len(feeds)]))
        i += 1
        next_t += period
    for _ in senders:
        q.put(None)
    for t in senders:
        t.join()
    n_offered = i
    out = {"target_qps": float(rate_qps), "offered": n_offered,
           "accepted": len(acc),
           "accepted_rate": len(acc) / max(n_offered, 1),
           "behind": behind[0], "clients": clients,
           "statuses": dict(sorted(hist.items())),
           "reroutes": int(sum(r.stats()["reroutes"] for r in routers)),
           "by_endpoint": _merge_by_endpoint(routers)}
    out.update(_percentiles([lat for lat, _s in acc]))
    sched = _percentiles([s for _lat, s in acc])
    out.update({f"sched_{k}": v for k, v in sched.items()})
    return out


# ------------------------------------------------------------------ harness
def start_inproc_pserver(endpoint: str, bind: str = "",
                         standby: bool = False,
                         pserver_endpoints: Sequence[str] = (),
                         sync: bool = False):
    """One in-process listen_and_serv loop on its own scope/thread —
    the serving PS lanes' pserver harness. Returns (thread, scope);
    stop with ``stop_inproc_pserver``."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import core

    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        main.global_block().append_op(
            type="listen_and_serv", inputs={}, outputs={},
            attrs={"endpoint": endpoint, "sync_mode": sync,
                   "Fanin": 1, "optimize_blocks": [],
                   "grad_to_block_id": [],
                   "pserver_endpoints": list(pserver_endpoints)
                   or [endpoint],
                   "bind_endpoint": bind, "standby": standby,
                   "replica_of": ""})
    scope = core.Scope()
    exe = fluid.Executor()
    th = threading.Thread(
        target=lambda: exe.run(main, scope=scope, feed={},
                               fetch_list=[]), daemon=True)
    th.start()
    return th, scope


def stop_inproc_pserver(physical_ep: str, thread) -> None:
    from paddle_tpu.fluid.ps_rpc import VarClient
    try:
        c = VarClient(physical_ep, connect_timeout=5.0, channels=1,
                      resolve=False)
        c.stop()
        c.close()
    except Exception:
        pass
    thread.join(timeout=10)


def push_table(endpoints: Sequence[str], name: str,
               table: np.ndarray) -> None:
    """Install a full embedding table on every pserver (each serves its
    ``id %% n`` shard out of it; prefetch_rows indexes by GLOBAL id, so
    shipping the whole array keeps the harness trivially bit-equal to
    the local oracle)."""
    from paddle_tpu.fluid.ps_rpc import VarClient
    for ep in endpoints:
        c = VarClient(ep, connect_timeout=30.0, channels=1)
        c.send_var(name, np.asarray(table))
        c.close()


def free_port() -> int:
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def build_mlp_serving_model(n_feeds: int = 64):
    """The mnist-shaped serving model every mnist lane measures.
    Returns (program, scope, out_name, feeds) with params initialized
    and ``feeds`` a list of single-row feed dicts."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import core

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", shape=[784], dtype="float32")
        h = fluid.layers.fc(x, 256, act="relu")
        out = fluid.layers.fc(h, 10, act="softmax")
    exe = fluid.Executor()
    scope = core.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    rng = np.random.RandomState(0)
    feeds = [{"x": rng.rand(784).astype(np.float32)}
             for _ in range(n_feeds)]
    return main, scope, out.name, feeds


# ------------------------------------------------------------- scenarios
def run_overload_scenario(clients: int = 16, duration_s: float = 2.0,
                          warmup_s: float = 0.5, max_batch: int = 16,
                          max_queue_rows: Optional[int] = None,
                          deadline_ms: float = 500.0,
                          overload_factor: float = 4.0,
                          workers: int = 2) -> Dict[str, object]:
    """The ISSUE 9 overload acceptance shape, as a library function
    (CLI ``--scenario overload`` runs it): measure 1× capacity
    closed-loop over HTTP, then drive open-loop at 1× and
    ``overload_factor``×. Reports
    accepted-request p99 at both loads, the shed rate, the status
    histogram (every non-200 must be a TYPED 429/504/503 — "5xx"/
    "transport" entries are the failure signal), and the engine's
    shed/deadline_expired counters."""
    from paddle_tpu.serving import (AdmissionController, ServingEngine,
                                    ServingIngress)

    if max_queue_rows is None:
        # the admission bound must sit BELOW the sender pool's
        # concurrency or a closed pool of blocking clients caps the
        # server queue at `clients` rows and the bound never engages —
        # the 4× leg would measure client-side pacing debt, not
        # server-side shedding
        max_queue_rows = max(4, clients // 2)
    main, scope, out_name, feeds = build_mlp_serving_model()
    eng = ServingEngine(
        program=main, scope=scope, feed_names=["x"],
        fetch_names=[out_name], max_batch=max_batch,
        max_queue_delay_ms=2.0, num_workers=workers,
        admission=AdmissionController(max_queue_rows=max_queue_rows,
                                      codel_target_ms=deadline_ms / 4,
                                      codel_interval_ms=deadline_ms / 2))
    eng.warm()
    ing = ServingIngress({"mlp": eng},
                         default_deadline_ms=deadline_ms).start()
    host, port = "127.0.0.1", ing.port
    try:
        eng.reset_stats()
        closed = run_http_closed_loop(host, port, feeds,
                                      clients=clients,
                                      duration_s=duration_s,
                                      warmup_s=warmup_s)
        cap = max(closed["qps"], 1.0)
        eng.reset_stats()
        open_1x = run_http_open_loop(host, port, feeds, rate_qps=cap,
                                     duration_s=duration_s,
                                     clients=clients)
        eng.reset_stats()
        open_4x = run_http_open_loop(
            host, port, feeds, rate_qps=cap * overload_factor,
            duration_s=duration_s, clients=clients)
        st = eng.stats()
        untyped = (open_4x["statuses"].get("5xx", 0)
                   + open_4x["statuses"].get("transport", 0))
        non200 = sum(v for k, v in open_4x["statuses"].items()
                     if k != "ok")
        # 1×-load reference: the closed loop at capacity IS sustained
        # 1× load (every request sees the full pipeline); the open-1×
        # leg is reported too, but its pacer runs slightly under
        # saturation whenever `behind` > 0, which flatters its p99 —
        # ratio-vs-closed is the stable acceptance number on a 1-core
        # box whose capacity measurement itself swings ±15%
        p99_1x = max(closed["p99_ms"], 1e-9)
        return {
            "scenario": "overload",
            "max_queue_rows": max_queue_rows,
            "deadline_ms": deadline_ms,
            "capacity_qps_1x": round(cap, 1),
            "closed_1x": closed, "open_1x": open_1x,
            "open_overload": open_4x,
            "overload_factor": overload_factor,
            "accepted_p99_ms_1x": closed["p99_ms"],
            "accepted_p99_ms_1x_open": open_1x["p99_ms"],
            "accepted_p99_ms_overload": open_4x["p99_ms"],
            "p99_ratio": round(open_4x["p99_ms"] / p99_1x, 2),
            "p99_ratio_vs_open_1x": round(
                open_4x["p99_ms"] / max(open_1x["p99_ms"], 1e-9), 2),
            "shed_rate_overload": round(
                non200 / max(open_4x["offered"], 1), 4),
            "untyped_failures": untyped,
            "all_refusals_typed": untyped == 0,
            "engine": st,
        }
    finally:
        ing.close()


def run_chaos_scenario(n_rows: int = 64, dim: int = 8,
                       n_feeds: int = 24, ttl_s: float = 0.3,
                       breaker_reset_s: float = 0.8
                       ) -> Dict[str, object]:
    """Pserver-death-mid-HTTP-serving: a raw VarServer serves the
    embedding rows, the engine fronts it with an EmbeddingCache and
    the circuit breaker on. Phase 1 warms the cache over HTTP; phase 2
    kills the server (connection-severing shutdown — the in-process
    SIGKILL equivalent) and expires the TTL, so every predict must
    serve BEYOND-TTL cache rows flagged degraded with zero 5xx; phase
    3 promotes a replacement endpoint via a PR 6 moved ClusterView and
    asserts the path un-degrades by itself. Returns phase counters;
    ``ok`` iff dark-window 5xx == 0 and recovery went fresh."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import core, ps_membership
    from paddle_tpu.fluid.ps_rpc import VarClient, VarServer, reset_breakers
    from paddle_tpu.serving import (EmbeddingCache, ServingEngine,
                                    ServingIngress, rewrite_sparse_lookups)

    rng = np.random.RandomState(3)
    table = rng.rand(n_rows, dim).astype(np.float32)

    def serve_table(name, rows, prefetch=False, trainer_id=0):
        return table[np.asarray(rows, np.int64)]

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = fluid.data("ids", shape=[1], dtype="int64")
        emb = fluid.layers.embedding(ids, size=[n_rows, dim],
                                     param_attr="emb_chaos",
                                     is_distributed=True)
        out = fluid.layers.fc(fluid.layers.reshape(emb, [-1, dim]), 4,
                              act="softmax")
    exe = fluid.Executor()
    scope = core.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)

    slot = f"127.0.0.1:{free_port()}"
    ps_prog, _ = rewrite_sparse_lookups(main, [slot],
                                        tables=["emb_chaos"])
    feeds = [{"ids": np.array([[i % n_rows]], np.int64)}
             for i in range(n_feeds)]

    flags_before = {k: core.globals_[k] for k in (
        "FLAGS_rpc_circuit_breaker", "FLAGS_rpc_breaker_failures",
        "FLAGS_rpc_breaker_reset_s", "FLAGS_rpc_retry_times",
        "FLAGS_rpc_deadline")}
    core.globals_["FLAGS_rpc_circuit_breaker"] = True
    core.globals_["FLAGS_rpc_breaker_failures"] = 1
    core.globals_["FLAGS_rpc_breaker_reset_s"] = breaker_reset_s
    core.globals_["FLAGS_rpc_retry_times"] = 0
    core.globals_["FLAGS_rpc_deadline"] = 2000
    ps_membership.reset_views()
    reset_breakers()
    VarClient.reset_pool()

    srv = VarServer(slot, {"prefetch_rows": serve_table}).start()
    cache = EmbeddingCache(ttl_s=ttl_s, max_entries=10000,
                           serve_stale=True)
    eng = ServingEngine(program=ps_prog, scope=scope,
                        feed_names=["ids"], fetch_names=[out],
                        max_batch=8, max_queue_delay_ms=1.0,
                        num_workers=2, embedding_cache=cache)
    ing = ServingIngress({"chaos": eng},
                         default_deadline_ms=3000.0).start()
    cli = HttpClient("127.0.0.1", ing.port)

    def drive(n):
        ok = degraded = err5xx = other = 0
        for i in range(n):
            status, obj = cli.predict(feeds[i % len(feeds)])
            if status == 200:
                ok += 1
                degraded += bool(obj.get("degraded"))
            elif status >= 500:
                err5xx += 1
            else:
                other += 1
        return {"ok": ok, "degraded": degraded, "5xx": err5xx,
                "other": other}

    try:
        warm = drive(n_feeds)           # fills the cache (fresh)
        srv.shutdown()                  # the in-process SIGKILL
        time.sleep(ttl_s + 0.05)        # every cached row beyond TTL
        dark = drive(n_feeds)           # must serve stale, degraded
        dark_stats = eng.stats()

        # PR 6-style promotion: a replacement serves the shard at a
        # NEW physical endpoint; the moved view re-points the slot
        new_ep = f"127.0.0.1:{free_port()}"
        srv2 = VarServer(new_ep, {"prefetch_rows": serve_table}).start()
        ps_membership.install_view(
            ps_membership.ClusterView.initial([slot]).moved(
                slot, new_ep, epoch=1))
        time.sleep(breaker_reset_s + 0.05)  # breaker half-open window
        recovered = drive(n_feeds)
        rec_fresh = drive(n_feeds)      # fully fresh once TTLs renew
        final_stats = eng.stats()
        srv2.shutdown()
        return {
            "scenario": "chaos", "warm": warm, "dark": dark,
            "recovered": recovered, "recovered_fresh": rec_fresh,
            "dark_degraded_responses": dark_stats["degraded"],
            "breaker": final_stats.get("breakers", {}),
            "cache": final_stats.get("embedding_cache", {}),
            "ok": (dark["5xx"] == 0 and dark["degraded"] == dark["ok"]
                   and dark["ok"] == n_feeds
                   and rec_fresh["degraded"] == 0
                   and rec_fresh["ok"] == n_feeds),
        }
    finally:
        cli.close()
        ing.close()
        try:
            srv.shutdown()
        except Exception:
            pass
        for k, v in flags_before.items():
            core.globals_[k] = v
        ps_membership.reset_views()
        reset_breakers()
        VarClient.reset_pool()


# ---------------------------------------------------------------------- CLI
def _build_mlp_engine(max_batch: int, delay_ms: float, workers: int):
    from paddle_tpu.serving import ServingEngine

    main, scope, out_name, feeds = build_mlp_serving_model()
    eng = ServingEngine(program=main, scope=scope, feed_names=["x"],
                        fetch_names=[out_name], max_batch=max_batch,
                        max_queue_delay_ms=delay_ms, num_workers=workers)
    return eng, feeds


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mode", choices=("closed", "open", "http"),
                    default="closed")
    ap.add_argument("--scenario", choices=("overload", "chaos"),
                    default=None,
                    help="http-mode scripted scenarios (ISSUE 9): "
                         "overload = 1x/4x open-loop shed run, chaos = "
                         "pserver kill mid-serving")
    ap.add_argument("--clients", type=int, default=16)
    ap.add_argument("--rate", type=float, default=500.0,
                    help="open-loop target QPS")
    ap.add_argument("--duration", type=float, default=3.0)
    ap.add_argument("--warmup", type=float, default=0.5)
    ap.add_argument("--max-batch", type=int, default=16)
    ap.add_argument("--delay-ms", type=float, default=2.0)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--deadline-ms", type=float, default=500.0,
                    help="http-mode per-request budget")
    ap.add_argument("--max-queue-rows", type=int, default=None,
                    help="http-mode admission bound (default: "
                         "clients/2 — must sit below the client "
                         "concurrency to engage)")
    ap.add_argument("--naive", action="store_true",
                    help="one-request-one-dispatch lane (max_batch=1)")
    ap.add_argument("--endpoints", default=None,
                    help="http-mode fleet targets, comma-separated "
                         "host:port — round-robin + retry-on-503/"
                         "reset across them instead of building a "
                         "local engine")
    ap.add_argument("--directory", default=None,
                    help="fleet directory endpoint (host:port) — the "
                         "router follows the live membership view; "
                         "combinable with --endpoints as the seed list")
    ap.add_argument("--fleet-loop", choices=("closed", "open"),
                    default="closed",
                    help="fleet-mode load shape (open paces --rate)")
    args = ap.parse_args(argv)
    import jax
    if not os.environ.get("JAX_PLATFORMS"):
        jax.config.update("jax_platforms", "cpu")

    if args.mode == "http":
        if args.endpoints or args.directory:
            # fleet mode: drive LIVE remote members (the chaos harness
            # and multi-process fleet lanes), no local engine at all
            eps = ([e.strip() for e in args.endpoints.split(",")
                    if e.strip()] if args.endpoints else [])
            rng = np.random.RandomState(0)
            feeds = [{"x": rng.rand(784).astype(np.float32)}
                     for _ in range(64)]
            if args.fleet_loop == "open":
                res = run_http_fleet_open_loop(
                    eps, feeds, rate_qps=args.rate,
                    duration_s=args.duration, clients=args.clients,
                    deadline_ms=args.deadline_ms, model="mlp",
                    directory_ep=args.directory)
            else:
                res = run_http_fleet_closed_loop(
                    eps, feeds, clients=args.clients,
                    duration_s=args.duration, warmup_s=args.warmup,
                    deadline_ms=args.deadline_ms, model="mlp",
                    directory_ep=args.directory)
            print(json.dumps({"mode": "http-fleet",
                              "loop": args.fleet_loop,
                              "result": res}, default=str))
            return 0
        if args.scenario == "overload":
            res = run_overload_scenario(
                clients=args.clients, duration_s=args.duration,
                warmup_s=args.warmup, max_batch=args.max_batch,
                max_queue_rows=args.max_queue_rows,
                deadline_ms=args.deadline_ms, workers=args.workers)
            print(json.dumps({"mode": "http", "result": res},
                             default=str))
            return 0 if res["all_refusals_typed"] else 1
        if args.scenario == "chaos":
            res = run_chaos_scenario()
            print(json.dumps({"mode": "http", "result": res},
                             default=str))
            return 0 if res["ok"] else 1
        # plain closed loop through a live ingress
        from paddle_tpu.serving import AdmissionController, ServingIngress

        eng, feeds = _build_mlp_engine(args.max_batch, args.delay_ms,
                                       args.workers)
        eng._admission = AdmissionController(
            max_queue_rows=(args.max_queue_rows
                            if args.max_queue_rows is not None
                            else max(4, args.clients // 2)))
        ing = ServingIngress({"mlp": eng},
                             default_deadline_ms=args.deadline_ms).start()
        try:
            eng.warm()
            eng.reset_stats()
            res = run_http_closed_loop(
                "127.0.0.1", ing.port, feeds, clients=args.clients,
                duration_s=args.duration, warmup_s=args.warmup)
            print(json.dumps({"mode": "http", "result": res,
                              "ingress": ing.stats()["ingress"],
                              "engine": eng.stats()}, default=str))
        finally:
            ing.close()
        return 0

    max_batch = 1 if args.naive else args.max_batch
    eng, feeds = _build_mlp_engine(max_batch, args.delay_ms, args.workers)
    try:
        eng.warm()
        eng.reset_stats()
        if args.mode == "closed":
            res = run_closed_loop(eng.predict, feeds,
                                  clients=args.clients,
                                  duration_s=args.duration,
                                  warmup_s=args.warmup)
        else:
            res = run_open_loop(eng.submit, feeds, rate_qps=args.rate,
                                duration_s=args.duration)
        st = eng.stats()
        print(json.dumps({"mode": args.mode, "naive": bool(args.naive),
                          "result": res, "engine": st,
                          "overload_counters": {
                              k: st[k] for k in (
                                  "shed", "deadline_expired",
                                  "degraded", "breaker_open")}},
                         default=str))
    finally:
        eng.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
