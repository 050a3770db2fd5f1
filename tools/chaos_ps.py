"""Scripted PS-membership chaos driver — drain / kill / rejoin
(docs/FAULT_TOLERANCE.md "Elastic membership").

Drives a real multiprocess sync PS cluster through membership faults and
checks the training outcome against a no-fault oracle:

  * ``drain_rejoin`` — live-drain pserver slot 0 to a warm standby
    mid-training, later drain it BACK (rejoin-in-place: the drained
    source is the destination of the reverse handoff). Trainers never
    restart; per-step losses must be bit-identical to the oracle.
  * ``failover`` — SIGKILL slot 0's primary mid-training with
    FLAGS_ps_replicas=2 and a warm replica attached; trainers stall at
    most ~2x the heartbeat timeout, then finish against the promoted
    replica, bit-identical to the oracle.
  * ``full`` — drain+rejoin on slot 0 AND a SIGKILL failover on slot 1,
    one run (the ISSUE 6 acceptance scenario).
  * ``serving_fleet`` — the self-healing SERVING fleet run (ISSUE 18,
    docs/SERVING.md "Fleet"): N engine subprocesses behind a
    FleetDirectory under open-loop fleet-routed load; a trainer table
    push must become visible in remote responses within a measured
    window, a rolling restart plus one SIGKILL must lose zero accepted
    requests with zero 5xx, and the autopilot must heal the fleet.
  * ``streaming`` — the streaming online-learning lane (ISSUE 20,
    docs/FAULT_TOLERANCE.md "Streaming online learning"): one cluster
    trains a zipfian click stream fully async (``sync_mode=False``
    Communicator, StreamLoader front end, per-step checkpoints) while
    a serving member answers authed HTTP over the SAME tables through
    the invalidation wire. Mid-run: a pserver SIGKILL (replica
    failover) and the shrink cron firing. Pass iff serving answered
    throughout with zero typed-error leaks, the async loss tail lands
    in the sync oracle's neighborhood, and event→served freshness p99
    is bounded and recorded.

Models: ``linear`` (tests/dist_ps_workload.py — tiny, fast) and
``wide_deep`` (the CTR model from paddle_tpu.models.wide_deep with
distributed embeddings, served by this module's ``worker`` subcommand).

CLI:
  python tools/chaos_ps.py --scenario full --model wide_deep \
      --trainers 3 --steps 12 --hb 2.0

Exit code 0 iff the faulted run finished AND matched the oracle
bit-for-bit. The ``chaos`` pytest marker's slow acceptance test calls
``run_scenario`` directly.
"""
import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:  # the driver's own admin RPCs import paddle_tpu
    sys.path.insert(0, REPO)
LINEAR_WORKLOAD = os.path.join(REPO, "tests", "dist_ps_workload.py")


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def _spawn(args, log_path, env_extra=None):
    env = dict(os.environ,
               PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""),
               JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    log = open(log_path, "wb+")
    proc = subprocess.Popen([sys.executable] + list(args), env=env,
                            stdout=log, stderr=log)

    def tail(n=3000):
        log.flush()
        log.seek(0)
        return log.read().decode(errors="replace")[-n:]

    return proc, tail


def _wait_file(path, timeout, procs=(), desc="file"):
    end = time.time() + timeout
    while time.time() < end:
        if os.path.exists(path):
            return
        for p, tail in procs:
            if p.poll() is not None:
                raise RuntimeError(
                    f"process died waiting for {desc}: {tail()}")
        time.sleep(0.1)
    raise TimeoutError(f"{desc} not ready within {timeout}s")


def _progress(path):
    try:
        with open(path) as f:
            return sum(1 for ln in f if ln.strip())
    except OSError:
        return 0


def admin_drain(owner_ep, dest_ep, timeout=120.0):
    """Drain the shard served at ``owner_ep`` (the slot's CURRENT
    primary) into the standby at ``dest_ep``. Returns the handoff
    summary dict from the source."""
    from paddle_tpu.fluid.ps_rpc import VarClient
    cli = VarClient(owner_ep, connect_timeout=min(10.0, timeout),
                    channels=1, resolve=False)
    try:
        return cli.call("drain", dest=dest_ep, _rpc_timeout=timeout)
    finally:
        cli.close()


def server_stats(ep):
    from paddle_tpu.fluid.ps_rpc import VarClient
    cli = VarClient(ep, connect_timeout=5.0, channels=1, resolve=False)
    try:
        return cli.call("stats", _rpc_timeout=10.0)
    finally:
        cli.close()


class Cluster:
    """One sync PS cluster run: n pservers (+ optional standbys and
    replicas for chosen slots), t trainers logging per-step losses."""

    def __init__(self, workdir, model="linear", trainers=2, n_pservers=2,
                 steps=20, hb=2.0, step_sleep=0.15, standby_slots=(),
                 replica_slots=(), sparse_dim=200, batch=32, tag="run",
                 env_extra=None, worker_extra=()):
        self.workdir = workdir
        self.model = model
        self.trainers = trainers
        self.steps = steps
        self.tag = tag
        os.makedirs(workdir, exist_ok=True)
        self.slot_eps = [f"127.0.0.1:{free_port()}"
                         for _ in range(n_pservers)]
        self.standby_eps = {i: f"127.0.0.1:{free_port()}"
                            for i in standby_slots}
        self.replica_eps = {i: f"127.0.0.1:{free_port()}"
                            for i in replica_slots}
        self.env = {"PADDLE_PS_HEARTBEAT_TIMEOUT": str(hb)}
        self.env.update(env_extra or {})
        self.worker_extra = tuple(worker_extra)
        if self.replica_eps:
            self.env["FLAGS_ps_replicas"] = "2"
            self.env["PADDLE_PS_REPLICA_MAP"] = ",".join(
                f"{self.slot_eps[i]}={ep}"
                for i, ep in self.replica_eps.items())
        self.step_sleep = step_sleep
        self.sparse_dim = sparse_dim
        self.batch = batch
        self.procs = []   # (name, proc, tail)
        self.pserver_procs = {}  # slot idx -> (proc, tail)

    # ------------------------------------------------------------ workers
    def _worker_args(self, role, idx, outfile, extra=()):
        eps = ",".join(self.slot_eps)
        if self.model == "linear":
            # model flags go to EVERY role: pservers transpile the same
            # program to host the sparse table shards
            base = [LINEAR_WORKLOAD, role, eps, str(idx),
                    str(self.trainers), str(self.steps), outfile,
                    "--sparse", f"--sparse-dim={self.sparse_dim}"]
            if role == "trainer":
                base += ["--progress", "--no-stop",
                         f"--step-sleep={self.step_sleep}"]
        else:
            base = [os.path.abspath(__file__), "worker", role, eps,
                    str(idx), str(self.trainers), str(self.steps),
                    outfile, f"--sparse-dim={self.sparse_dim}",
                    f"--batch={self.batch}",
                    f"--step-sleep={self.step_sleep}"]
        return base + list(self.worker_extra) + list(extra)

    def _out(self, name):
        return os.path.join(self.workdir, f"{self.tag}-{name}")

    def start_servers(self, timeout=120.0):
        waits = []
        for i, ep in enumerate(self.slot_eps):
            ready = self._out(f"ps{i}.ready")
            p, tail = _spawn(self._worker_args("pserver", i, ready),
                             self._out(f"ps{i}.log"),
                             dict(self.env,
                                  PADDLE_TPU_TRACE_ROLE=f"pserver{i}"))
            self.procs.append((f"ps{i}", p, tail))
            self.pserver_procs[i] = (p, tail)
            waits.append((ready, p, tail))
        for i, bind in self.standby_eps.items():
            ready = self._out(f"standby{i}.ready")
            p, tail = _spawn(
                self._worker_args("standby", i, ready,
                                  extra=[f"--bind={bind}"]),
                self._out(f"standby{i}.log"), self.env)
            self.procs.append((f"standby{i}", p, tail))
            waits.append((ready, p, tail))
        for i, bind in self.replica_eps.items():
            ready = self._out(f"replica{i}.ready")
            p, tail = _spawn(
                self._worker_args("standby", i, ready,
                                  extra=[f"--bind={bind}", "--replica"]),
                self._out(f"replica{i}.log"), self.env)
            self.procs.append((f"replica{i}", p, tail))
            waits.append((ready, p, tail))
        for ready, p, tail in waits:
            _wait_file(ready, timeout, [(p, tail)], desc=ready)

    def start_trainers(self):
        self.trainer_outs = []
        for t in range(self.trainers):
            out = self._out(f"t{t}.json")
            p, tail = _spawn(self._worker_args("trainer", t, out),
                             self._out(f"t{t}.log"),
                             dict(self.env,
                                  PADDLE_TPU_TRACE_ROLE=f"trainer{t}"))
            self.procs.append((f"t{t}", p, tail))
            self.trainer_outs.append((out, p, tail))

    def trainer_progress(self, t=0):
        return _progress(self.trainer_outs[t][0] + ".progress")

    def wait_progress(self, n, t=0, timeout=300.0):
        end = time.time() + timeout
        while time.time() < end:
            if self.trainer_progress(t) >= n:
                return
            p, tail = self.trainer_outs[t][1:]
            if p.poll() is not None:
                raise RuntimeError(
                    f"trainer {t} died at progress "
                    f"{self.trainer_progress(t)}: {tail()}")
            time.sleep(0.05)
        raise TimeoutError(
            f"trainer {t} stuck at {self.trainer_progress(t)}/{n}")

    def kill_pserver(self, slot):
        p, _tail = self.pserver_procs[slot]
        p.send_signal(signal.SIGKILL)
        p.wait(timeout=30)

    def join_trainers(self, timeout=600.0):
        losses = []
        for out, p, tail in self.trainer_outs:
            rc = p.wait(timeout=timeout)
            if rc != 0:
                raise RuntimeError(f"trainer exited rc={rc}: {tail()}")
            data = json.load(open(out))
            losses.append(data if isinstance(data, list)
                          else data.get("losses"))
        return losses

    def shutdown(self):
        for _name, p, _tail in self.procs:
            if p.poll() is None:
                p.kill()
        for _name, p, _tail in self.procs:
            try:
                p.wait(timeout=10)
            except Exception:
                pass


# ---------------------------------------------------------------------------
# scenarios
# ---------------------------------------------------------------------------
def run_scenario(scenario, workdir, model="linear", trainers=3,
                 n_pservers=2, steps=14, hb=2.0, drain_at=3, rejoin_at=7,
                 kill_at=5, step_sleep=0.15, sparse_dim=200, batch=32,
                 with_oracle=True):
    """Run one chaos scenario (+ a no-fault oracle) and compare
    per-trainer per-step losses bit-for-bit. Returns a result dict."""
    result = {"scenario": scenario, "model": model, "events": []}
    common = dict(model=model, trainers=trainers, n_pservers=n_pservers,
                  steps=steps, hb=hb, step_sleep=step_sleep,
                  sparse_dim=sparse_dim, batch=batch)
    if with_oracle:
        oracle = Cluster(workdir, tag="oracle", **common)
        try:
            oracle.start_servers()
            oracle.start_trainers()
            result["oracle_losses"] = oracle.join_trainers()
        finally:
            oracle.shutdown()

    standby_slots = (0,) if scenario in ("drain_rejoin", "full") else ()
    replica_slots = () if scenario == "drain_rejoin" else \
        ((1,) if scenario == "full" and n_pservers > 1 else (0,))
    run = Cluster(workdir, tag="chaos", standby_slots=standby_slots,
                  replica_slots=replica_slots, **common)
    try:
        run.start_servers()
        run.start_trainers()
        stall_bound = 3 * hb + 10
        if scenario in ("drain_rejoin", "full"):
            slot = run.slot_eps[0]
            standby = run.standby_eps[0]
            run.wait_progress(drain_at)
            summary = admin_drain(slot, standby)
            result["events"].append(("drain", slot, standby, summary))
            run.wait_progress(rejoin_at, timeout=stall_bound + 120)
            summary = admin_drain(standby, slot)  # rejoin-in-place
            result["events"].append(("rejoin", standby, slot, summary))
        if scenario in ("failover", "full"):
            kslot = 1 if scenario == "full" and n_pservers > 1 else 0
            base = max(drain_at, rejoin_at) if scenario == "full" \
                else 0
            run.wait_progress(base + kill_at, timeout=stall_bound + 180)
            t_kill = time.time()
            run.kill_pserver(kslot)
            result["events"].append(
                ("sigkill", run.slot_eps[kslot], None, None))
            # trainers must get moving again within ~2x hb (+slack)
            target = run.trainer_progress(0) + 2
            run.wait_progress(min(target, steps),
                              timeout=stall_bound + 60)
            result["failover_stall_s"] = time.time() - t_kill
        result["losses"] = run.join_trainers(timeout=600.0)
    finally:
        run.shutdown()
    if with_oracle:
        result["bit_identical"] = \
            result["losses"] == result["oracle_losses"]
    return result


# ---------------------------------------------------------------------------
# serving-fleet scenario (ISSUE 18): rolling restart + SIGKILL under load
# ---------------------------------------------------------------------------
def _scrape_metric_stat(host, port, name):
    """Pull one histogram's (_sum, _count) off a member's /metrics
    exposition — the registry-scraped freshness-window evidence."""
    import http.client as _http
    conn = _http.HTTPConnection(host, int(port), timeout=5.0)
    try:
        conn.request("GET", "/metrics")
        text = conn.getresponse().read().decode("utf-8", "replace")
    finally:
        conn.close()
    s = c = None
    for ln in text.splitlines():
        if ln.startswith(name + "_sum"):
            s = float(ln.rsplit(None, 1)[1])
        elif ln.startswith(name + "_count"):
            c = float(ln.rsplit(None, 1)[1])
    return s, c


def run_serving_fleet_scenario(workdir, members=3, n_rows=64, dim=8,
                               hb=1.0, rate_qps=60.0, duration_s=75.0,
                               clients=8):
    """The self-healing-fleet acceptance run (docs/SERVING.md "Fleet"):

    the driver hosts the embedding table (a raw VarServer), the
    trainer-side ``InvalidationPublisher``, the ``FleetDirectory`` and
    an ``Autopilot``; ``members`` serving engines run as REAL
    subprocesses (``serving-member`` subcommand). Under open-loop
    fleet-routed load it then injects, in order:

      1. a trainer table push + invalidation broadcast — every member
         must reflect the new rows in its HTTP responses within a
         bounded, MEASURED window (wall-clock here, plus the members'
         registry-scraped staleness histograms);
      2. a rolling restart — each original member SIGTERMed (directory
         drain → ingress drain → exit) and replaced, zero lost
         accepted requests;
      3. one SIGKILL — heartbeat eviction within ~2×hb, the autopilot
         heals the fleet back to ``members``.

    ``ok`` iff the load saw ZERO 5xx / fleet-dark errors, every
    response is accounted (accepted or typed-shed), freshness was
    in-bounds on every member, and the fleet healed.
    """
    import threading

    import numpy as np

    os.makedirs(workdir, exist_ok=True)
    from paddle_tpu.fluid.ps_rpc import VarServer
    from paddle_tpu.serving import (Autopilot, FleetDirectory,
                                    InvalidationPublisher, SLO)
    from paddle_tpu.serving.fleet import scrape_http_member
    from tools.serving_loadgen import HttpClient, run_http_fleet_open_loop

    result = {"scenario": "serving_fleet", "members": members,
              "events": []}
    rng = np.random.RandomState(7)
    table = rng.rand(n_rows, dim).astype(np.float32)
    tlock = threading.Lock()

    def serve_table(name, rows, prefetch=False, trainer_id=0):
        with tlock:
            return table[np.asarray(rows, np.int64)].copy()

    table_ep = f"127.0.0.1:{free_port()}"
    pub_ep = f"127.0.0.1:{free_port()}"
    dir_ep = f"127.0.0.1:{free_port()}"
    srv = VarServer(table_ep, {"prefetch_rows": serve_table}).start()
    pub = InvalidationPublisher(pub_ep).start()
    directory = FleetDirectory(dir_ep, heartbeat_timeout_s=hb).start()

    member_procs = {}       # name -> (proc, tail, ready_path)
    next_idx = [0]
    spawn_lock = threading.Lock()

    def spawn_member():
        with spawn_lock:
            i = next_idx[0]
            next_idx[0] += 1
        name = f"m{i}"
        ready = os.path.join(workdir, f"{name}.ready")
        p, tail = _spawn(
            [os.path.abspath(__file__), "serving-member", name,
             table_ep, pub_ep, dir_ep, ready,
             f"--rows={n_rows}", f"--dim={dim}", f"--hb={hb}"],
            os.path.join(workdir, f"{name}.log"))
        member_procs[name] = (p, tail, ready)
        return name

    def wait_member(name, timeout=120.0):
        p, tail, ready = member_procs[name]
        _wait_file(ready, timeout, [(p, tail)], desc=f"member {name}")
        return int(open(ready).read().strip())

    def wait_view(n, timeout=60.0, desc=""):
        end = time.time() + timeout
        while time.time() < end:
            if len(directory.view().endpoints()) == n:
                return
            time.sleep(0.05)
        raise TimeoutError(
            f"fleet view stuck at {len(directory.view().endpoints())} "
            f"members, want {n} {desc}")

    def scrape_all():
        out = []
        for ep in directory.view().endpoints():
            host, port = ep.rsplit(":", 1)
            try:
                out.append(scrape_http_member(ep))
            except Exception:
                out.append(None)
        return out

    autopilot = None
    load_box = {}
    try:
        ports = {}
        for _ in range(members):
            name = spawn_member()
            ports[name] = wait_member(name)
        wait_view(members, desc="at start")

        feeds = [{"ids": np.array([[i % n_rows]], np.int64)}
                 for i in range(32)]

        def load():
            load_box["res"] = run_http_fleet_open_loop(
                [], feeds, rate_qps=rate_qps, duration_s=duration_s,
                clients=clients, model="fleet", directory_ep=dir_ep)
        load_th = threading.Thread(target=load, daemon=True)
        load_th.start()
        time.sleep(1.0)  # let the loop establish against the fleet

        # ---- 1. trainer push: update rows, broadcast, measure until
        # every member's HTTP response reflects the new values
        push_ids = list(range(8))
        with tlock:
            table[push_ids] += 1.0
            expect = [float(table[i].sum()) for i in push_ids]
        t_push = time.time()
        pub.publish("emb_fleet", push_ids)
        fresh_by_member = {}
        deadline = t_push + 10.0
        pending = dict(ports)
        while pending and time.time() < deadline:
            for name, port in list(pending.items()):
                cli = HttpClient("127.0.0.1", port)
                try:
                    status, obj = cli.predict(
                        {"ids": [[push_ids[0]]]}, model="fleet")
                finally:
                    cli.close()
                if status == 200:
                    got = float(np.asarray(obj["outputs"][0])
                                .reshape(-1)[0])
                    if abs(got - expect[0]) < 1e-3:
                        fresh_by_member[name] = time.time() - t_push
                        del pending[name]
            if pending:
                time.sleep(0.02)
        result["freshness_s"] = {k: round(v, 4)
                                 for k, v in fresh_by_member.items()}
        result["events"].append(("push", push_ids, None, None))
        fresh_ok = len(fresh_by_member) == members
        result["freshness_window_s"] = (
            round(max(fresh_by_member.values()), 4)
            if fresh_by_member else None)

        # ---- 2. rolling restart of every ORIGINAL member — surge
        # style: the replacement JOINS before the old member drains,
        # so the routable fleet never dips below target strength
        for name in list(ports):
            repl = spawn_member()
            wait_member(repl)
            wait_view(members + 1, desc=f"surge {repl} for {name}")
            p, tail, _ready = member_procs[name]
            p.send_signal(signal.SIGTERM)
            rc = p.wait(timeout=120)
            result["events"].append(("sigterm", name, rc, None))
            wait_view(members, desc=f"after rolling {name}->{repl}")

        # ---- 3. SIGKILL one member; eviction + autopilot heal. The
        # autopilot arms only now: its min_members healing must not
        # race the DELIBERATE drains of phase 2 (a real deployment
        # coordinates restarts with the controller the same way)
        slo = SLO(p99_ms=5000.0, max_shed_rate=1.0,
                  max_queue_rows=1 << 20, min_members=members,
                  max_members=members + 2)
        autopilot = Autopilot(
            scrape_all, slo,
            spawn_fn=spawn_member,
            drain_fn=lambda: None,  # scale-down is not this scenario
            interval_s=0.5, cooldown_s=2.0).start()
        victim = next(n for n, (p, _t, _r) in member_procs.items()
                      if p.poll() is None)
        vp = member_procs[victim][0]
        t_kill = time.time()
        vp.send_signal(signal.SIGKILL)
        vp.wait(timeout=30)
        wait_view(members - 1, timeout=2 * hb + 20,
                  desc="eviction after SIGKILL")
        result["evict_s"] = round(time.time() - t_kill, 3)
        result["events"].append(("sigkill", victim, None, None))
        wait_view(members, timeout=120, desc="autopilot heal")
        result["heal_s"] = round(time.time() - t_kill, 3)

        load_th.join(timeout=duration_s + 120)
        res = load_box.get("res") or {}
        result["load"] = res

        # registry-scraped staleness evidence off one live member
        for ep in directory.view().endpoints():
            host, port = ep.rsplit(":", 1)
            try:
                s, c = _scrape_metric_stat(
                    host, port, "serving_cache_staleness_window_seconds")
            except Exception:
                continue
            if c:
                result["staleness_hist"] = {
                    "count": c, "mean_s": round(s / c, 6)}
                break

        statuses = dict(res.get("statuses") or {})
        bad = {k: v for k, v in statuses.items()
               if k not in ("ok", "429", "504")}
        accounted = (sum(statuses.values()) == res.get("offered", -1))
        result["checks"] = {
            "zero_5xx_or_dark": not bad,
            "all_requests_accounted": accounted,
            "freshness_all_members": fresh_ok,
            "evicted_within_2xhb": result["evict_s"] <= 2 * hb + 10,
            "healed": True,
        }
        result["ok"] = all(result["checks"].values())
        return result
    finally:
        if autopilot is not None:
            autopilot.stop()
        for name, (p, tail, _r) in member_procs.items():
            if p.poll() is None:
                p.kill()
        for name, (p, _t, _r) in member_procs.items():
            try:
                p.wait(timeout=10)
            except Exception:
                pass
        directory.close()
        pub.close()
        srv.shutdown()


def run_serving_member():
    """``serving-member`` subcommand: one fleet engine process — MLP-
    free value-reflective model (``out = sum(emb[id])``, so a table
    push is directly observable in the HTTP response), EmbeddingCache
    + InvalidationSubscriber, ingress, FleetMember. SIGTERM runs the
    zero-lost drain (directory first, then ingress) and exits 0."""
    name, table_ep, pub_ep, dir_ep, ready_file = sys.argv[2:7]
    n_rows = int(_flag_value("--rows", 64) or 64)
    dim = int(_flag_value("--dim", 8) or 8)
    hb = float(_flag_value("--hb", 1.0) or 1.0)
    ttl_s = float(_flag_value("--ttl", 30.0) or 30.0)

    import threading

    import jax
    jax.config.update("jax_platforms", "cpu")
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import core
    from paddle_tpu.serving import (EmbeddingCache, FleetMember,
                                    InvalidationSubscriber, ServingEngine,
                                    ServingIngress, rewrite_sparse_lookups)

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = fluid.data("ids", shape=[1], dtype="int64")
        emb = fluid.layers.embedding(ids, size=[n_rows, dim],
                                     param_attr="emb_fleet",
                                     is_distributed=True)
        out = fluid.layers.reduce_sum(
            fluid.layers.reshape(emb, [-1, dim]), dim=1)
    exe = fluid.Executor()
    scope = core.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    ps_prog, _ = rewrite_sparse_lookups(main, [table_ep],
                                        tables=["emb_fleet"])
    cache = EmbeddingCache(ttl_s=ttl_s, max_entries=100000,
                           serve_stale=True)
    eng = ServingEngine(program=ps_prog, scope=scope, feed_names=["ids"],
                        fetch_names=[out], max_batch=8,
                        max_queue_delay_ms=1.0, num_workers=2,
                        embedding_cache=cache)
    ing = ServingIngress({"fleet": eng}).start()
    sub = InvalidationSubscriber(pub_ep, cache, name=name,
                                 poll_wait_s=0.5).start()
    member = FleetMember(name, dir_ep, f"127.0.0.1:{ing.port}",
                         ingress=ing, beat_interval_s=max(0.1, hb / 4))
    member.start()

    done = threading.Event()

    def on_term(_sig, _frm):
        # drain OFF the signal thread: member.drain() does wire RPCs +
        # the ingress inflight wait — too much for a handler frame
        threading.Thread(target=lambda: (member.drain(), done.set()),
                         daemon=True).start()

    signal.signal(signal.SIGTERM, on_term)
    open(ready_file, "w").write(str(ing.port))
    done.wait()
    sub.stop()
    ing.close()
    eng.close()


# ---------------------------------------------------------------------------
# streaming scenario (ISSUE 20): async train + serve one cluster, survive
# a pserver SIGKILL and a shrink-cron firing under authed HTTP load
# ---------------------------------------------------------------------------
def click_stream(offset, n_rows=64, seed=7):
    """Seekable synthetic zipfian click stream: event #i is derived
    from a counter-keyed RandomState, so ``click_stream(k)`` replays
    event k bit-identically no matter where a previous reader stopped —
    the StreamLoader seek contract. Yields ``(x, ids, y)`` samples:
    4 dense features, one zipf-hot clicked id, and a linear label with
    a per-id bias (learnable, so loss trends down)."""
    import numpy as np
    w_true = np.array([1.0, -2.0, 0.5, 3.0], np.float32)
    i = int(offset)
    while True:
        rs = np.random.RandomState((seed * 1000003 + i) % (2**31 - 1))
        rid = min(n_rows - 1, int(rs.zipf(1.5)) - 1)
        x = rs.rand(4).astype(np.float32)
        bias = np.random.RandomState(seed ^ (rid + 1)).uniform(-1.0, 1.0)
        y = np.array([float(x @ w_true) * 0.1 + bias], np.float32)
        yield (x, np.array([rid], np.int64), y)
        i += 1


def build_stream_model(n_rows=64, dim=8, lr=0.05):
    """The streaming CTR-ish model: dense features + one distributed
    embedding (``emb_stream``), trained with SGD. Returns
    ``(main, startup, feed_vars, loss)``."""
    import paddle_tpu.fluid as fluid
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", shape=[4], dtype="float32")
        ids = fluid.data("ids", shape=[1], dtype="int64")
        y = fluid.data("y", shape=[1], dtype="float32")
        emb = fluid.layers.embedding(
            ids, size=[n_rows, dim], is_distributed=True,
            param_attr=fluid.ParamAttr(name="emb_stream"))
        emb = fluid.layers.reshape(emb, [-1, dim])
        feat = fluid.layers.concat([x, emb], axis=1)
        pred = fluid.layers.fc(feat, 1,
                               param_attr=fluid.ParamAttr(name="w"),
                               bias_attr=fluid.ParamAttr(name="b"))
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(lr).minimize(loss)
    return main, startup, [x, ids, y], loss


def run_stream_worker():
    """``stream-worker`` subcommand — pserver / standby / trainer roles
    of the streaming cluster. Default mode is fully async
    (``sync_mode=False``: per-var Communicator merge queues, recv
    double buffer); ``--sync`` builds the SYNC oracle cluster the
    driver compares the loss tail against. The async trainer also:

      * feeds from a StreamLoader over ``click_stream`` (resumable
        event offsets, per-step auto-checkpoints under ``--ckpt-dir``
        riding the PR 3 MANIFEST);
      * hosts the InvalidationPublisher at ``--pub-ep`` so the serving
        member's cache tracks its pushes;
      * leaves the shrink cron to ``FLAGS_ps_shrink_every_steps`` in
        the environment (ticked at the async recv step boundary).
    """
    role, eps, idx, trainers, steps, outfile = sys.argv[2:8]
    idx, trainers, steps = int(idx), int(trainers), int(steps)
    n_rows = int(_flag_value("--rows", 64) or 64)
    dim = int(_flag_value("--dim", 8) or 8)
    batch = int(_flag_value("--batch", 8) or 8)
    seed = int(_flag_value("--seed", 7) or 7)
    step_sleep = float(_flag_value("--step-sleep", 0) or 0)
    sync = "--sync" in sys.argv
    pub_ep = _flag_value("--pub-ep")
    ckpt_dir = _flag_value("--ckpt-dir")
    resume = "--resume" in sys.argv

    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import core
    from paddle_tpu.fluid.transpiler import DistributeTranspiler

    main, startup, feeds, loss = build_stream_model(n_rows, dim)
    t = DistributeTranspiler()
    with fluid.program_guard(main, startup):
        t.transpile(trainer_id=idx if role == "trainer" else 0,
                    pservers=eps, trainers=trainers, sync_mode=sync,
                    program=main, startup_program=startup)
    exe = fluid.Executor()
    scope = core.Scope()
    if role in ("pserver", "standby"):
        ep = eps.split(",")[idx]
        if role == "standby":
            bind = _flag_value("--bind")
            pprog = t.get_pserver_program(
                ep, bind_endpoint=bind, standby=True,
                replica_of=ep if "--replica" in sys.argv else "")
        else:
            pprog = t.get_pserver_program(ep)
        pstart = t.get_startup_program(ep, pprog)
        with fluid.scope_guard(scope):
            exe.run(pstart)
            open(outfile, "w").write("ready")
            exe.run(pprog)
        return

    # ------------------------------------------------------- trainer role
    comm = pub = None
    if not sync:
        from paddle_tpu.fluid.communicator import Communicator
        comm = Communicator()
        comm.start()
    if pub_ep:
        from paddle_tpu.fluid import ps_rpc
        from paddle_tpu.serving import InvalidationPublisher
        pub = InvalidationPublisher(pub_ep).start()
        ps_rpc.install_invalidation_publisher(pub)

    x, ids, y = feeds
    loader = fluid.DataLoader.from_stream(feed_list=[x, ids, y],
                                          batch_size=batch)
    loader.set_event_source(
        lambda off: click_stream(off, n_rows=n_rows, seed=seed))
    losses = []
    try:
        with fluid.scope_guard(scope):
            exe.run(startup)
            prog = t.get_trainer_program()
            if ckpt_dir:
                if resume:
                    exe.resume_from(ckpt_dir, program=prog, scope=scope,
                                    dataloader=loader)
                exe.set_auto_checkpoint(ckpt_dir, every_n_steps=1,
                                        program=prog, scope=scope,
                                        dataloader=loader)
            open(outfile + ".up", "w").write("up")
            t_loop = time.time()
            for step, feed in enumerate(loader):
                if step >= steps:
                    break
                (lv,) = exe.run(prog, feed=feed, fetch_list=[loss])
                losses.append(float(np.asarray(lv).reshape(-1)[0]))
                with open(outfile + ".progress", "a") as pf:
                    pf.write(f"{step} {losses[-1]!r} "
                             f"{loader.stream_offset}\n")
                if step_sleep:
                    time.sleep(step_sleep)
    finally:
        if comm is not None:
            comm.stop()   # drains merge queues in submit order
        if pub is not None:
            pub.close()
    # wall of the training loop INCLUDING the async plane's stop-drain
    # (the sync leg pays its barriers inline; excluding the drain would
    # flatter async) and any step_sleep pacing
    json.dump({"losses": losses, "offset": loader.stream_offset,
               "train_wall_s": round(time.time() - t_loop, 4)},
              open(outfile, "w"))


def run_stream_server():
    """``stream-server`` subcommand — the serving member of the
    streaming cluster: value-reflective model (``out = sum(emb[id])``)
    whose lookups are rewritten against the TRAINING pservers
    (``rewrite_sparse_lookups`` — same ``id % n_pservers`` shards), an
    EmbeddingCache kept fresh by the trainer's invalidation wire, and
    an authed HTTP ingress (FLAGS_serving_auth_token from the env).
    Replica failover rides PADDLE_PS_REPLICA_MAP, also from the env."""
    name, eps_csv, pub_ep, ready_file = sys.argv[2:6]
    n_rows = int(_flag_value("--rows", 64) or 64)
    dim = int(_flag_value("--dim", 8) or 8)
    ttl_s = float(_flag_value("--ttl", 30.0) or 30.0)

    import threading

    import jax
    jax.config.update("jax_platforms", "cpu")
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import core
    from paddle_tpu.serving import (EmbeddingCache, InvalidationSubscriber,
                                    ServingEngine, ServingIngress,
                                    rewrite_sparse_lookups)

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = fluid.data("ids", shape=[1], dtype="int64")
        emb = fluid.layers.embedding(
            ids, size=[n_rows, dim], is_distributed=True,
            param_attr=fluid.ParamAttr(name="emb_stream"))
        out = fluid.layers.reduce_sum(
            fluid.layers.reshape(emb, [-1, dim]), dim=1)
    exe = fluid.Executor()
    scope = core.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    ps_prog, _ = rewrite_sparse_lookups(main, eps_csv.split(","),
                                        tables=["emb_stream"])
    cache = EmbeddingCache(ttl_s=ttl_s, max_entries=100000,
                           serve_stale=True)
    eng = ServingEngine(program=ps_prog, scope=scope, feed_names=["ids"],
                        fetch_names=[out], max_batch=8,
                        max_queue_delay_ms=1.0, num_workers=2,
                        embedding_cache=cache)
    ing = ServingIngress({"stream": eng}).start()
    sub = InvalidationSubscriber(pub_ep, cache, name=name,
                                 poll_wait_s=0.5).start()

    done = threading.Event()

    def on_term(_sig, _frm):
        threading.Thread(target=done.set, daemon=True).start()

    signal.signal(signal.SIGTERM, on_term)
    open(ready_file, "w").write(str(ing.port))
    done.wait()
    sub.stop()
    ing.close()
    eng.close()


def _scrape_histogram_quantile(host, port, name, q=0.99):
    """Bucket-resolution quantile off a /metrics exposition: the
    smallest bucket upper bound covering fraction ``q`` of the
    samples. Returns ``(upper_bound_s_or_None, count)``."""
    import http.client as _http
    conn = _http.HTTPConnection(host, int(port), timeout=5.0)
    try:
        conn.request("GET", "/metrics")
        text = conn.getresponse().read().decode("utf-8", "replace")
    finally:
        conn.close()
    buckets, total = [], 0.0
    for ln in text.splitlines():
        if ln.startswith(name + "_bucket"):
            le = ln.split('le="', 1)[1].split('"', 1)[0]
            buckets.append((float(le), float(ln.rsplit(None, 1)[1])))
        elif ln.startswith(name + "_count"):
            total = float(ln.rsplit(None, 1)[1])
    if not total:
        return None, 0
    buckets.sort()
    for le, cum in buckets:
        if cum >= q * total:
            return le, int(total)
    return float("inf"), int(total)


def _dig(obj, key):
    """First value for ``key`` anywhere in a nested dict/list."""
    if isinstance(obj, dict):
        if key in obj:
            return obj[key]
        for v in obj.values():
            got = _dig(v, key)
            if got is not None:
                return got
    elif isinstance(obj, (list, tuple)):
        for v in obj:
            got = _dig(v, key)
            if got is not None:
                return got
    return None


def run_streaming_scenario(workdir, n_rows=64, dim=8, batch=8, steps=40,
                           hb=2.0, kill_at=15, shrink_every=10,
                           step_sleep=0.12, clients=3, auth_token="s3cret",
                           with_oracle=True):
    """The ISSUE 20 acceptance lane. Sequence:

      1. SYNC oracle: 2 pservers + 1 sync trainer over the same click
         stream — the loss-neighborhood reference.
      2. Chaos cluster: 2 pservers (slot 1 carries a warm replica),
         1 fully-async streaming trainer (Communicator, per-step
         checkpoints, invalidation publisher, shrink cron), 1 authed
         serving member over the SAME table shards.
      3. Closed-loop authed HTTP load for the whole run; one
         deliberately unauthed probe must bounce with a typed 401.
      4. At trainer step ``kill_at``: SIGKILL pserver slot 1's primary
         — the replica promotes; trainer AND serving re-route.

    Checks: trainer exits 0; every load response is ok or a typed
    refusal (zero 5xx/transport-dark); accepted p99 under the serving
    bar; async loss tail within the sync oracle's neighborhood; shrink
    ran on a surviving pserver; event→served freshness p99 bounded and
    recorded off the member's /metrics histogram."""
    import threading

    import numpy as np

    os.makedirs(workdir, exist_ok=True)
    from paddle_tpu.serving.engine import percentiles_ms
    from tools.serving_loadgen import HttpClient

    result = {"scenario": "streaming", "steps": steps, "events": []}
    me = os.path.abspath(__file__)

    # ---- 1. sync oracle ------------------------------------------------
    oracle_losses = None
    if with_oracle:
        eps = [f"127.0.0.1:{free_port()}" for _ in range(2)]
        eps_csv = ",".join(eps)
        procs = []
        try:
            waits = []
            for i in range(2):
                ready = os.path.join(workdir, f"oracle-ps{i}.ready")
                p, tail = _spawn(
                    [me, "stream-worker", "pserver", eps_csv, str(i),
                     "1", str(steps), ready, "--sync",
                     f"--rows={n_rows}", f"--dim={dim}"],
                    os.path.join(workdir, f"oracle-ps{i}.log"))
                procs.append((p, tail))
                waits.append((ready, p, tail))
            for ready, p, tail in waits:
                _wait_file(ready, 120, [(p, tail)], desc=ready)
            out = os.path.join(workdir, "oracle-t0.json")
            p, tail = _spawn(
                [me, "stream-worker", "trainer", eps_csv, "0", "1",
                 str(steps), out, "--sync", f"--rows={n_rows}",
                 f"--dim={dim}", f"--batch={batch}"],
                os.path.join(workdir, "oracle-t0.log"))
            rc = p.wait(timeout=600)
            if rc != 0:
                raise RuntimeError(f"oracle trainer rc={rc}: {tail()}")
            odata = json.load(open(out))
            oracle_losses = odata["losses"]
            result["oracle_tail"] = oracle_losses[-5:]
            result["oracle_train_wall_s"] = odata.get("train_wall_s")
        finally:
            for p, _t in procs:
                if p.poll() is None:
                    p.kill()

    # ---- 2. chaos cluster ---------------------------------------------
    eps = [f"127.0.0.1:{free_port()}" for _ in range(2)]
    eps_csv = ",".join(eps)
    replica_ep = f"127.0.0.1:{free_port()}"
    pub_ep = f"127.0.0.1:{free_port()}"
    env = {
        "PADDLE_PS_HEARTBEAT_TIMEOUT": str(hb),
        "FLAGS_ps_replicas": "2",
        "PADDLE_PS_REPLICA_MAP": f"{eps[1]}={replica_ep}",
        # emb_stream must host as an init-on-touch LazyEmbeddingTable
        # (threshold far below 64x8) with per-row touch scores (no
        # spill tier needed) so the cron's table_shrink has a
        # shrinkable table — the run's shrink evidence
        "FLAGS_lazy_sparse_table_threshold": "1",
        "FLAGS_ps_slab_track_scores": "1",
    }
    procs = {}

    def spawn(tag, args, env_extra=None):
        p, tail = _spawn(args, os.path.join(workdir, f"{tag}.log"),
                         dict(env, **(env_extra or {})))
        procs[tag] = (p, tail)
        return p, tail

    load_stop = threading.Event()
    load_box = {"lat": [], "statuses": {}, "errors": 0}

    def load_loop(port):
        rng = np.random.RandomState(11)
        hdr = {"X-Auth-Token": auth_token}
        while not load_stop.is_set():
            cli = HttpClient("127.0.0.1", port, timeout=10.0)
            try:
                while not load_stop.is_set():
                    rid = min(n_rows - 1, int(rng.zipf(1.5)) - 1)
                    t0 = time.perf_counter()
                    try:
                        status, _obj = cli.predict(
                            {"ids": [[rid]]}, model="stream",
                            extra_headers=hdr)
                    except OSError:
                        load_box["errors"] += 1
                        break   # reconnect
                    dt = time.perf_counter() - t0
                    key = "ok" if status == 200 else str(status)
                    load_box["statuses"][key] = \
                        load_box["statuses"].get(key, 0) + 1
                    if status == 200:
                        load_box["lat"].append(dt)
                    time.sleep(0.01)
            finally:
                cli.close()

    try:
        waits = []
        for i in range(2):
            ready = os.path.join(workdir, f"ps{i}.ready")
            p, tail = spawn(
                f"ps{i}",
                [me, "stream-worker", "pserver", eps_csv, str(i), "1",
                 str(steps), ready, f"--rows={n_rows}", f"--dim={dim}"])
            waits.append((ready, p, tail))
        ready = os.path.join(workdir, "replica1.ready")
        p, tail = spawn(
            "replica1",
            [me, "stream-worker", "standby", eps_csv, "1", "1",
             str(steps), ready, f"--rows={n_rows}", f"--dim={dim}",
             f"--bind={replica_ep}", "--replica"])
        waits.append((ready, p, tail))
        for ready, p, tail in waits:
            _wait_file(ready, 120, [(p, tail)], desc=ready)

        tout = os.path.join(workdir, "t0.json")
        ckpt = os.path.join(workdir, "ckpt")
        spawn("t0",
              [me, "stream-worker", "trainer", eps_csv, "0", "1",
               str(steps), tout, f"--rows={n_rows}", f"--dim={dim}",
               f"--batch={batch}", f"--step-sleep={step_sleep}",
               f"--pub-ep={pub_ep}", f"--ckpt-dir={ckpt}"],
              {"FLAGS_ps_shrink_every_steps": str(shrink_every)})
        _wait_file(tout + ".up", 120, [procs["t0"]], desc="trainer up")

        sready = os.path.join(workdir, "server.ready")
        spawn("server",
              [me, "stream-server", "s0", eps_csv, pub_ep, sready,
               f"--rows={n_rows}", f"--dim={dim}"],
              {"FLAGS_serving_auth_token": auth_token})
        _wait_file(sready, 120, [procs["server"]], desc="serving member")
        port = int(open(sready).read().strip())

        # ---- 3. authed load + the unauthed 401 probe
        threads = [threading.Thread(target=load_loop, args=(port,),
                                    daemon=True) for _ in range(clients)]
        for th in threads:
            th.start()
        cli = HttpClient("127.0.0.1", port)
        try:
            status, obj = cli.predict({"ids": [[0]]}, model="stream")
        finally:
            cli.close()
        result["unauthed_status"] = status
        result["events"].append(("auth_probe", status,
                                 (obj or {}).get("error"), None))

        # ---- 4. pserver SIGKILL at kill_at
        prog_file = tout + ".progress"
        end = time.time() + 300
        while _progress(prog_file) < kill_at:
            p, tail = procs["t0"]
            if p.poll() is not None:
                raise RuntimeError(f"trainer died early: {tail()}")
            if time.time() > end:
                raise TimeoutError("trainer stuck before kill_at")
            time.sleep(0.05)
        t_kill = time.time()
        procs["ps1"][0].send_signal(signal.SIGKILL)
        procs["ps1"][0].wait(timeout=30)
        result["events"].append(("sigkill", eps[1], replica_ep, None))

        p, tail = procs["t0"]
        rc = p.wait(timeout=600)
        result["trainer_rc"] = rc
        result["failover_to_finish_s"] = round(time.time() - t_kill, 3)
        if rc != 0:
            raise RuntimeError(f"async trainer rc={rc}: {tail()}")
        tdata = json.load(open(tout))
        result["async_tail"] = tdata["losses"][-5:]
        result["stream_offset"] = tdata["offset"]
        result["async_train_wall_s"] = tdata.get("train_wall_s")
        result["async_steps_run"] = len(tdata["losses"])

        # post-train serving tail: keep the load running against the
        # failed-over cluster so the post-kill window carries real
        # traffic (and the subscriber drains the last invalidations
        # into the freshness histogram before the scrape)
        time.sleep(4.0)

        # freshness histogram BEFORE the load stops (live member)
        p99, cnt = _scrape_histogram_quantile(
            "127.0.0.1", port, "serving_event_freshness_seconds")
        result["freshness_p99_s"] = p99
        result["freshness_samples"] = cnt

        load_stop.set()
        for th in threads:
            th.join(timeout=30)

        # shrink evidence off the surviving slot-0 pserver: shrink_runs
        # lives in the table's tier stats (table_stats RPC), not the
        # per-method "stats" counters
        try:
            from paddle_tpu.fluid.ps_rpc import VarClient
            cli = VarClient(eps[0], connect_timeout=5.0, channels=1,
                            resolve=False)
            try:
                ts = cli.call("table_stats", name="emb_stream",
                              _rpc_timeout=10.0)
            finally:
                cli.close()
        except Exception:
            ts = {}
        shrink_runs = int(_dig(ts, "shrink_runs") or 0)
        result["shrink_runs"] = shrink_runs

        lat = load_box["lat"]
        statuses = load_box["statuses"]
        pct = percentiles_ms(lat, suffix="_ms") if lat else {}
        result["load"] = {"statuses": statuses,
                          "transport_errors": load_box["errors"],
                          "accepted": len(lat), **pct}
        bad = {k: v for k, v in statuses.items()
               if k not in ("ok", "429", "504", "503")}

        losses = np.asarray(tdata["losses"], float)
        checks = {
            "trainer_exit_0": rc == 0,
            "serving_answered": len(lat) > 0,
            "zero_typed_error_leaks": (not bad
                                       and load_box["errors"] == 0),
            "unauthed_rejected_401": result["unauthed_status"] == 401,
            "accepted_p99_bounded": bool(pct) and pct["p99_ms"] <= 500.0,
            "losses_finite": bool(np.isfinite(losses).all()),
            "shrink_cron_fired": shrink_runs >= 1,
            "freshness_bounded": (cnt > 0 and p99 is not None
                                  and p99 <= 10.0),
        }
        if oracle_losses is not None:
            otail = float(np.mean(oracle_losses[-5:]))
            atail = float(np.mean(losses[-5:]))
            result["oracle_tail_mean"] = round(otail, 5)
            result["async_tail_mean"] = round(atail, 5)
            # neighborhood, not bit-parity: unbounded staleness trades
            # exactness for throughput; the tail must still be in the
            # oracle's regime (converged, not diverged)
            checks["loss_in_oracle_neighborhood"] = \
                atail <= max(2.5 * otail, otail + 0.05)
        result["checks"] = checks
        result["ok"] = all(checks.values())
        return result
    finally:
        load_stop.set()
        for _tag, (p, _t) in procs.items():
            if p.poll() is None:
                p.kill()
        for _tag, (p, _t) in procs.items():
            try:
                p.wait(timeout=10)
            except Exception:
                pass


# ---------------------------------------------------------------------------
# wide_deep worker subcommand (pserver / standby / trainer roles)
# ---------------------------------------------------------------------------
def _flag_value(name, default=None):
    for a in sys.argv:
        if a.startswith(name + "="):
            return a.split("=", 1)[1]
    return default


def run_worker():
    role, eps, idx, trainers, steps, outfile = sys.argv[2:8]
    idx, trainers, steps = int(idx), int(trainers), int(steps)
    sparse_dim = int(_flag_value("--sparse-dim", 200) or 200)
    batch = int(_flag_value("--batch", 32) or 32)
    step_sleep = float(_flag_value("--step-sleep", 0) or 0)

    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import core
    from paddle_tpu.fluid.transpiler import DistributeTranspiler
    from paddle_tpu.models import wide_deep

    def build():
        return wide_deep.build_wide_deep_program(
            num_dense=4, num_slots=3, sparse_dim=sparse_dim,
            embedding_dim=4, hidden=(16, 16), lr=1e-2, with_auc=False,
            is_distributed=True, optimizer=fluid.optimizer.SGD(1e-2))

    main, startup, feeds, loss, _auc = build()
    from paddle_tpu.fluid.transpiler import DistributeTranspilerConfig
    cfg = DistributeTranspilerConfig()
    if "--async-overlap" in sys.argv:
        # ps_round comm tail (docs/PS_DATA_PLANE.md "Async overlap");
        # FLAGS_async_staleness rides the env into this subprocess
        cfg.async_overlap = True
    t = DistributeTranspiler(cfg)
    with fluid.program_guard(main, startup):
        t.transpile(trainer_id=idx if role == "trainer" else 0,
                    pservers=eps, trainers=trainers, sync_mode=True,
                    program=main, startup_program=startup)
    exe = fluid.Executor()
    scope = core.Scope()
    if role in ("pserver", "standby"):
        ep = eps.split(",")[idx]
        if role == "standby":
            bind = _flag_value("--bind")
            pprog = t.get_pserver_program(
                ep, bind_endpoint=bind, standby=True,
                replica_of=ep if "--replica" in sys.argv else "")
        else:
            pprog = t.get_pserver_program(ep)
        pstart = t.get_startup_program(ep, pprog)
        with fluid.scope_guard(scope):
            exe.run(pstart)
            open(outfile, "w").write("ready")
            exe.run(pprog)
        return

    from paddle_tpu.fluid.ps_rpc import VarClient, WorkerHeartBeat
    hb_interval = max(0.25, float(
        os.environ.get("PADDLE_PS_HEARTBEAT_TIMEOUT", 60.0)) / 4)
    beat = WorkerHeartBeat(eps.split(","), idx,
                           interval=hb_interval).start()
    nb = wide_deep.ctr_reader(batch, num_dense=4, num_slots=3,
                              sparse_dim=sparse_dim, seed=idx)
    losses = []
    try:
        with fluid.scope_guard(scope):
            exe.run(startup)
            prog = t.get_trainer_program()
            for s in range(steps):
                (lv,) = exe.run(prog, feed=nb(), fetch_list=[loss])
                losses.append(float(np.asarray(lv).reshape(-1)[0]))
                with open(outfile + ".progress", "a") as pf:
                    pf.write(f"{s} {losses[-1]!r}\n")
                if step_sleep:
                    time.sleep(step_sleep)
            # flush the async-overlap staleness pipe before the
            # pservers are released (no-op in plain sync mode)
            from paddle_tpu.fluid.communicator import drain_async_rounds
            drain_async_rounds()
    finally:
        beat.stop()
    json.dump(losses, open(outfile, "w"))


def main():
    if len(sys.argv) > 1 and sys.argv[1] == "worker":
        run_worker()
        return 0
    if len(sys.argv) > 1 and sys.argv[1] == "serving-member":
        run_serving_member()
        return 0
    if len(sys.argv) > 1 and sys.argv[1] == "stream-worker":
        run_stream_worker()
        return 0
    if len(sys.argv) > 1 and sys.argv[1] == "stream-server":
        run_stream_server()
        return 0
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scenario", default="full",
                    choices=["drain_rejoin", "failover", "full",
                             "serving_fleet", "streaming"])
    ap.add_argument("--model", default="linear",
                    choices=["linear", "wide_deep"])
    ap.add_argument("--trainers", type=int, default=3)
    ap.add_argument("--pservers", type=int, default=2)
    ap.add_argument("--steps", type=int, default=None,
                    help="default 14 (membership) / 40 (streaming)")
    ap.add_argument("--hb", type=float, default=2.0)
    ap.add_argument("--drain-at", type=int, default=3)
    ap.add_argument("--rejoin-at", type=int, default=7)
    ap.add_argument("--kill-at", type=int, default=None,
                    help="default 5 (membership) / 15 (streaming)")
    ap.add_argument("--workdir", default=None)
    ap.add_argument("--no-oracle", action="store_true")
    ap.add_argument("--trace-dir", default=None,
                    help="stream FLAGS_trace_dir shards from every "
                         "chaos process and run a tools/timeline.py "
                         "merge smoke over them afterwards "
                         "(docs/OBSERVABILITY.md)")
    args = ap.parse_args()
    workdir = args.workdir or os.path.join(
        tempfile.gettempdir(), f"chaos_ps_{int(time.time())}")
    if args.trace_dir:
        # subprocesses inherit the env; the chaos trainers/pservers
        # each stream a shard the merge smoke below combines
        os.makedirs(args.trace_dir, exist_ok=True)
        os.environ["FLAGS_trace_dir"] = args.trace_dir
    if args.scenario == "serving_fleet":
        res = run_serving_fleet_scenario(workdir, hb=args.hb)
        print(json.dumps({k: v for k, v in res.items()
                          if k != "load"}, indent=1, default=str))
        print("load:", json.dumps(res.get("load", {}), default=str))
        return 0 if res.get("ok") else 1
    if args.scenario == "streaming":
        res = run_streaming_scenario(workdir, steps=args.steps or 40,
                                     hb=args.hb,
                                     kill_at=args.kill_at or 15,
                                     with_oracle=not args.no_oracle)
        print(json.dumps(res, indent=1, default=str))
        if res.get("ok"):
            # the measured freshness p99 as one bench-shaped row on
            # stdout (whoever runs the scenario keeps it; nothing is
            # written into the checkout)
            print(json.dumps({
                "metric": "streaming_chaos_freshness_p99",
                "value": res.get("freshness_p99_s"),
                "unit": "s (bucket upper bound)",
                "vs_baseline": 1.0,
                "ok": res.get("ok"),
                "freshness_samples": res.get("freshness_samples"),
                "p99_ms": (res.get("load") or {}).get("p99_ms"),
                "statuses": (res.get("load") or {}).get("statuses"),
                "shrink_runs": res.get("shrink_runs"),
                "async_tail_mean": res.get("async_tail_mean"),
                "oracle_tail_mean": res.get("oracle_tail_mean"),
                "note": "tools/chaos_ps.py --scenario streaming: "
                        "async stream train+serve, pserver SIGKILL + "
                        "shrink cron mid-run; CPU",
            }))
        return 0 if res.get("ok") else 1
    res = run_scenario(args.scenario, workdir, model=args.model,
                       trainers=args.trainers, n_pservers=args.pservers,
                       steps=args.steps or 14, hb=args.hb,
                       drain_at=args.drain_at, rejoin_at=args.rejoin_at,
                       kill_at=args.kill_at or 5,
                       with_oracle=not args.no_oracle)
    print(json.dumps(
        {k: v for k, v in res.items() if "losses" not in k}, indent=1,
        default=str))
    if args.trace_dir:
        # timeline-merge smoke: the shards the run just streamed must
        # combine into one clock-corrected timeline (exit non-zero on
        # an empty/unmergeable dir — the chaos driver doubles as the
        # obs plane's multiprocess canary)
        from tools import timeline as _timeline
        summary = _timeline.merge_shards(
            args.trace_dir,
            out=os.path.join(args.trace_dir, "timeline.json"))
        print("trace merge:", json.dumps(summary, indent=1))
        if summary["n_events"] == 0:
            print("trace merge produced ZERO events — shards empty?")
            return 1
    if res.get("oracle_losses") is not None:
        print("bit_identical:", res["bit_identical"])
        if not res["bit_identical"]:
            for t, (a, b) in enumerate(zip(res["losses"],
                                           res["oracle_losses"])):
                if a != b:
                    print(f"trainer {t} diverged: chaos={a[-3:]} "
                          f"oracle={b[-3:]}")
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
