"""Communicator — async grad merge/send threads for PS training
(reference: python/paddle/fluid/communicator.py:27,91 wrapping C++
operators/distributed/communicator.h — AsyncCommunicator:237 merge queues,
HalfAsyncCommunicator:299, GeoCommunicator:383).

TPU framing: the pserver applies updates on arrival
(ops/distributed_ops.py listen_and_serv async loop), so correctness never
needs client-side queues — but the reference's merge behavior matters for
RPC load: with a running Communicator, async-mode send ops enqueue grads
here instead of issuing one RPC each; per-var merge threads sum up to
``max_merge_var_num`` pending grads and ship one merged send (the
AsyncCommunicator contract). SYNC mode needs no communicator at all."""
from __future__ import annotations

import logging
import queue
import threading
import time
from typing import Dict, Optional, Tuple

import numpy as np

from . import core

__all__ = ["Communicator", "LargeScaleKV", "RoundPipeline",
           "round_pipeline", "active_round_pipeline",
           "drain_async_rounds", "reset_round_pipeline",
           "DGCCompressor", "dgc_compressor", "dgc_enabled",
           "reset_dgc", "topk_sparsify", "geo_round_pipeline",
           "active_geo_pipeline", "reset_geo_pipeline"]

_LOG = logging.getLogger("paddle_tpu.ps")


# ---------------------------------------------------------------------------
# DGC — deep gradient compression (docs/PS_DATA_PLANE.md "Compression";
# reference WITH_DGC, paddle/fluid/operators/dgc_op + DGCMomentumOptimizer;
# Lin et al., "Deep Gradient Compression", ICLR 2018). Dense grads on the
# sync send / ps_round paths sparsify to their top-k elements before the
# wire; the unsent mass stays in a LOCAL error-feedback accumulator and
# ships in later pushes, so the sum of everything sent plus the residual
# always equals the true accumulated gradient (the convergence contract —
# tested in tests/test_ps_compression.py).
# ---------------------------------------------------------------------------
def dgc_enabled() -> bool:
    return bool(core.globals_["FLAGS_dgc"])


def topk_sparsify(flat: np.ndarray, sparsity: float):
    """Top-k-by-magnitude selection: keep ceil(n*(1-sparsity)) entries
    (at least 1). Returns (sorted int64 indices, their values) —
    sorted so the server-side scatter order is deterministic."""
    n = int(flat.size)
    k = max(1, int(round(n * (1.0 - float(sparsity)))))
    if k >= n:
        idx = np.arange(n, dtype=np.int64)
    else:
        idx = np.argpartition(np.abs(flat), n - k)[n - k:]
        idx = np.sort(idx).astype(np.int64)
    return idx, np.ascontiguousarray(flat[idx])


class DGCCompressor:
    """Per-trainer DGC state: for each grad name a momentum-corrected
    velocity ``u`` (u = m*u + g) and an error-feedback accumulator
    ``v`` (v += u). Each push selects the top-k of |v|, zeroes the
    selected entries of BOTH u and v (the paper's momentum factor
    masking), and ships (indices, values); everything unselected stays
    local and accumulates into later pushes. Warm-up ramps sparsity
    exponentially toward FLAGS_dgc_sparsity over the first
    FLAGS_dgc_warmup_steps pushes per grad."""

    def __init__(self):
        self._lock = threading.Lock()
        self._state: Dict[str, dict] = {}
        self._stats = {"elements_total": 0, "elements_sent": 0,
                       "bytes_raw_total": 0, "bytes_sent_total": 0,
                       "pushes_total": 0, "dense_fallbacks_total": 0}

    @staticmethod
    def _sparsity_at(step: int) -> float:
        final = min(0.9999, max(0.0,
                    float(core.globals_["FLAGS_dgc_sparsity"])))
        warm = int(core.globals_["FLAGS_dgc_warmup_steps"])
        if warm > 0 and step < warm and final > 0:
            # exponential ramp (the paper's per-epoch 75%→99.9%
            # schedule, per-push): drop rate approaches `final` as
            # (1-final)^((step+1)/warm)
            return 1.0 - (1.0 - final) ** (float(step + 1) / warm)
        return final

    def compress(self, name: str, grad: np.ndarray):
        """Fold ``grad`` into the local accumulators and select this
        push's top-k. Returns (indices, values) over the FLAT grad, or
        None when the grad should ship dense (non-f32 or smaller than
        FLAGS_dgc_min_elements)."""
        g = np.asarray(grad)
        if g.dtype != np.float32 \
                or g.size < int(core.globals_["FLAGS_dgc_min_elements"]):
            return None
        m = float(core.globals_["FLAGS_dgc_momentum"])
        with self._lock:
            st = self._state.get(name)
            if st is None or st["u"].size != g.size:
                st = self._state[name] = {
                    "u": np.zeros(g.size, np.float32),
                    "v": np.zeros(g.size, np.float32), "step": 0}
            u, v = st["u"], st["v"]
            flat = g.reshape(-1)
            if m > 0.0:
                u *= np.float32(m)
                u += flat
            else:
                u[:] = flat
            v += u
            idx, vals = topk_sparsify(
                v, self._sparsity_at(st["step"]))
            st["step"] += 1
            v[idx] = 0.0
            u[idx] = 0.0  # momentum factor masking
            self._stats["elements_total"] += int(g.size)
            self._stats["elements_sent"] += int(idx.size)
            self._stats["bytes_raw_total"] += int(g.nbytes)
            self._stats["bytes_sent_total"] += int(idx.nbytes
                                                   + vals.nbytes)
            self._stats["pushes_total"] += 1
        return idx, vals

    def restore_dense(self, name: str, idx: np.ndarray,
                      vals: np.ndarray) -> np.ndarray:
        """Undo a compress() whose dgc_send met an old server ("no
        method"): put the selected mass back and return the FULL flat
        accumulator to ship dense instead — the residual clears, so
        nothing is lost or double-sent across the fallback."""
        with self._lock:
            st = self._state[name]
            v = st["v"]
            v[idx] += vals  # selected entries were zeroed above
            full = v.copy()
            v[:] = 0.0
            st["u"][:] = 0.0
            self._stats["dense_fallbacks_total"] += 1
        return full

    def note_external(self, total_elems: int, sent_elems: int,
                      raw_bytes: int, sent_bytes: int) -> None:
        """Fold an externally-compressed push (the geo-delta top-k
        lane keeps its error feedback in @GEO_OLD, not in u/v) into
        the same dgc_* counters so dgc_compression_ratio covers the
        whole compressed plane."""
        with self._lock:
            self._stats["elements_total"] += int(total_elems)
            self._stats["elements_sent"] += int(sent_elems)
            self._stats["bytes_raw_total"] += int(raw_bytes)
            self._stats["bytes_sent_total"] += int(sent_bytes)
            self._stats["pushes_total"] += 1

    def residual(self, name: str):
        """Copy of the error-feedback accumulator (tests/debugging)."""
        with self._lock:
            st = self._state.get(name)
            return None if st is None else st["v"].copy()

    def stats(self) -> dict:
        with self._lock:
            out = dict(self._stats)
        out["compression_ratio"] = round(
            out["elements_total"] / max(1, out["elements_sent"]), 2)
        return out


_dgc: Optional[DGCCompressor] = None
_dgc_lock = threading.Lock()
_dgc_view = None


def dgc_compressor() -> DGCCompressor:
    """Process-global compressor (one trainer per process, like the
    round pipeline); registers the ``dgc`` metrics view — the
    ``dgc_compression_ratio`` gauge — on first use."""
    global _dgc, _dgc_view
    with _dgc_lock:
        if _dgc is None:
            _dgc = DGCCompressor()
            from . import telemetry
            _dgc_view = telemetry.REGISTRY.register_view(
                "dgc", _dgc.stats)
        return _dgc


def active_dgc_stats() -> dict:
    """Compression counters of the live compressor ({} when DGC never
    ran in this process) — the subprocess-evidence surface the WAN
    scenario collects."""
    d = _dgc
    return {} if d is None else d.stats()


def reset_dgc():
    global _dgc, _dgc_view
    with _dgc_lock:
        _dgc = None
        view, _dgc_view = _dgc_view, None
    if view is not None:
        from . import telemetry
        telemetry.REGISTRY.unregister_view(view)


class RoundPipeline:
    """The half-async round engine of the async overlap plane
    (docs/PS_DATA_PLANE.md "Async overlap"; reference
    HalfAsyncCommunicator, operators/distributed/communicator.h:299).

    A sync trainer's comm tail (push grads → send barrier → pull params
    → fetch barrier) is submitted here as ONE callable per round; a
    single FIFO worker thread runs rounds in submit order — the
    server's sync protocol needs exactly one send per trainer per round
    and in-order barrier arrivals, so rounds never overlap EACH OTHER
    on the wire, only the trainer's compute. The ps_rpc.AckWindow
    bounds how many submitted-but-unacked rounds may be in flight
    (FLAGS_async_staleness); a full pipe blocks ``submit`` — i.e. the
    step. Round callables return the round's pulled params (the
    double-buffer fill); ``take_fresh_pulls`` hands the NEWEST
    completed buffer to the main thread exactly once, which installs it
    into the scope at the next step boundary.

    Ordered non-round tasks (``submit_task``) ride the same FIFO — the
    async sparse-grad pushes of step i+1 must reach the server after
    round i's release and before round i+1's sends, exactly where the
    sync path would have put them."""

    def __init__(self, name: str = "ps-async-rounds"):
        from .ps_rpc import AckWindow
        self._name = name
        self._q: "queue.Queue" = queue.Queue()
        self._ack = AckWindow()
        self._thread: Optional[threading.Thread] = None
        self._lock = threading.Lock()
        self._running = True
        # newest completed pull buffer: (round_id, {param: ndarray});
        # _installed tracks what the main thread already consumed
        self._latest = (-1, None)
        self._installed = -1
        # queued-or-executing side tasks: the AckWindow only tracks
        # ROUNDS, but drain() must also cover a sparse push that was
        # dequeued and is still on the wire (otherwise a drain with no
        # round behind the push returns early and the push is lost to
        # a following server stop)
        self._tasks_cv = threading.Condition()
        self._tasks_pending = 0

    # ------------------------------------------------------------ submit
    def _ensure_thread(self):
        with self._lock:
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(
                    target=self._loop, name=self._name, daemon=True)
                self._thread.start()

    def submit(self, fn, staleness: int, label: str = "round") -> int:
        """Submit one round; blocks while ``staleness`` rounds are in
        flight (the full-pipe backpressure) and re-raises any deferred
        background error typed on this (the main) thread."""
        from . import profiler as _profiler
        self._ensure_thread()
        if self._ack.inflight() >= max(1, int(staleness)) \
                and _profiler.is_profiling():
            with _profiler.RecordEvent(
                    f"{label}:stall[pipe_full]", cat="comm",
                    args={"inflight": self._ack.inflight()}):
                rid = self._ack.acquire_slot(staleness)
        else:
            rid = self._ack.acquire_slot(staleness)
        self._q.put(("round", rid, fn, label))
        return rid

    def submit_task(self, fn, label: str = "task") -> None:
        """FIFO side task (async sparse push): ordered with the rounds,
        outside the staleness accounting; errors surface at the next
        submit()/drain()."""
        self._ensure_thread()
        with self._tasks_cv:
            self._tasks_pending += 1
        self._q.put(("task", -1, fn, label))

    # -------------------------------------------------------------- loop
    def _loop(self):
        from . import profiler as _profiler
        while True:
            kind, rid, fn, label = self._q.get()
            if kind == "stop":
                return
            try:
                if _profiler.is_profiling():
                    with _profiler.RecordEvent(
                            f"{label}[{rid}]" if kind == "round"
                            else label, cat="comm"):
                        result = fn()
                else:
                    result = fn()
                if kind == "round" and isinstance(result, dict) \
                        and result:
                    with self._lock:
                        if rid > self._latest[0]:
                            self._latest = (rid, result)
                err = None
            except BaseException as e:  # noqa: BLE001 — deferred, typed
                err = e
                _LOG.warning("%s: background %s %s failed: %r",
                             self._name, kind, label, e)
            if kind == "round":
                self._ack.ack(err)
            else:
                if err is not None:
                    self._ack.record_error(err)
                with self._tasks_cv:
                    self._tasks_pending -= 1
                    self._tasks_cv.notify_all()

    # ------------------------------------------------------ double buffer
    def take_fresh_pulls(self):
        """The newest completed round's pulled params, or None when the
        main thread already installed them — the at-a-step-boundary
        half of the double-buffered dense pull."""
        with self._lock:
            rid, buf = self._latest
            if buf is None or rid <= self._installed:
                return None
            self._installed = rid
            return buf

    # -------------------------------------------------------------- drain
    def inflight(self) -> int:
        return self._ack.inflight()

    def stats(self) -> dict:
        """Round-pipeline counters (docs/OBSERVABILITY.md): submitted/
        acked/inflight rounds, pending side tasks, and the double-buffer
        install watermark — registered as the ``ps_round_pipeline``
        metrics view by ``round_pipeline()``."""
        submitted, acked = self._ack.counts()
        with self._tasks_cv:
            tasks_pending = self._tasks_pending
        with self._lock:
            latest, installed = self._latest[0], self._installed
        return {"rounds_submitted": submitted, "rounds_acked": acked,
                "rounds_inflight": submitted - acked,
                "tasks_pending": tasks_pending,
                "latest_pull_round": latest,
                "installed_pull_round": installed}

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Wait for every submitted round (and queued task) to finish —
        FIFO, so the flush order is deterministic. Returns False on
        timeout. Deferred errors re-raise here."""
        end = None if timeout is None else time.time() + timeout
        while not self._q.empty():
            if end is not None and time.time() > end:
                return False
            time.sleep(0.005)
        if not self._ack.wait_all(
                None if end is None else max(0.0, end - time.time())):
            return False
        with self._tasks_cv:
            while self._tasks_pending > 0:
                wait = None if end is None else end - time.time()
                if wait is not None and wait <= 0:
                    return False
                self._tasks_cv.wait(wait if wait is None
                                    else min(wait, 1.0))
        return True

    def stop(self, timeout: Optional[float] = None):
        try:
            self.drain(timeout)
        except BaseException as e:  # noqa: BLE001 — teardown must finish
            _LOG.warning("%s: error surfaced during stop-drain: %r",
                         self._name, e)
        self._q.put(("stop", -1, None, ""))
        t = self._thread
        if t is not None and t.is_alive():
            t.join(timeout=5.0)


# process-global pipeline: the ps_round op kernels have no trainer
# context, and one trainer process runs one staleness pipe (mirrors the
# install_row_cache layering in ps_rpc)
_round_pipe: Optional[RoundPipeline] = None
_round_pipe_lock = threading.Lock()
_round_pipe_view = None


def round_pipeline() -> RoundPipeline:
    global _round_pipe, _round_pipe_view
    with _round_pipe_lock:
        if _round_pipe is None:
            _round_pipe = RoundPipeline()
            from . import telemetry
            _round_pipe_view = telemetry.REGISTRY.register_view(
                "ps_round_pipeline", _round_pipe.stats)
        return _round_pipe


def active_round_pipeline() -> Optional[RoundPipeline]:
    return _round_pipe


# geo-delta WAN lane (docs/PS_DATA_PLANE.md "Compression"): geo_sgd_send
# submits its delta-merge rounds here when FLAGS_async_staleness > 0 —
# a SEPARATE pipe from the sync ps_round one (a process never runs
# both, but the stats views must not conflate them and geo rounds have
# their own install protocol: a FIFO shift queue, not the newest-pull
# double buffer).
_geo_pipe: Optional[RoundPipeline] = None
_geo_pipe_view = None


def geo_round_pipeline() -> RoundPipeline:
    global _geo_pipe, _geo_pipe_view
    with _round_pipe_lock:
        if _geo_pipe is None:
            _geo_pipe = RoundPipeline(name="ps-geo-rounds")
            from . import telemetry
            _geo_pipe_view = telemetry.REGISTRY.register_view(
                "ps_geo_pipeline", _geo_pipe.stats)
        return _geo_pipe


def active_geo_pipeline() -> Optional[RoundPipeline]:
    return _geo_pipe


def drain_async_rounds(timeout: Optional[float] = None) -> bool:
    """Flush the staleness pipes (no-op without one). Call before
    stopping pservers / comparing trainer state — in-flight rounds
    still hold unpushed grads and unconsumed pulls. Covers BOTH the
    sync ps_round pipe and the geo delta pipe."""
    ok = True
    for pipe in (_round_pipe, _geo_pipe):
        if pipe is not None:
            ok = pipe.drain(timeout) and ok
    return ok


def reset_geo_pipeline():
    global _geo_pipe, _geo_pipe_view
    with _round_pipe_lock:
        pipe, _geo_pipe = _geo_pipe, None
        view, _geo_pipe_view = _geo_pipe_view, None
    if view is not None:
        from . import telemetry
        telemetry.REGISTRY.unregister_view(view)
    if pipe is not None:
        pipe.stop(timeout=5.0)


def reset_round_pipeline():
    global _round_pipe, _round_pipe_view
    with _round_pipe_lock:
        pipe, _round_pipe = _round_pipe, None
        view, _round_pipe_view = _round_pipe_view, None
    if view is not None:
        from . import telemetry
        telemetry.REGISTRY.unregister_view(view)
    if pipe is not None:
        pipe.stop(timeout=5.0)
    reset_geo_pipeline()


class Communicator:
    """Fully-async grad plane (``sync_mode=False``; reference
    AsyncCommunicator::SendThread/RecvThread, communicator.h:237).

    Staleness is UNBOUNDED by design: pushes enqueue onto per-var
    merge queues that never gate on an AckWindow — the trainer's step
    is never blocked by the wire, and the server applies whatever
    arrives whenever it arrives (listen_and_serv distributed_mode=1
    applies on arrival). The price is the async consistency model:
    loss tracks the sync oracle's NEIGHBORHOOD, not its trajectory
    (docs/FAULT_TOLERANCE.md "Streaming online learning").

    Every background failure is typed and counted (``stats()``,
    ``ps_communicator`` metrics view): transport outages requeue under
    FLAGS_ps_failover_deadline, server rejections and deadline
    exhaustions drop with distinct counters — nothing is silently
    lost without a counter naming the reason."""

    _global: Optional["Communicator"] = None

    def __init__(self, program=None, mode=None, kwargs=None, envs=None):
        self._running = False
        self._program = program
        self._mode = mode or "async"
        envs = envs or {}
        self._max_merge = int(envs.get("communicator_max_merge_var_num", 20))
        self._wait_times = float(
            envs.get("communicator_send_wait_times", 0.005))
        # independent recv thread cadence (reference
        # independent_recv_thread): how often the background puller
        # refreshes the dense-param double buffer
        self._recv_interval = float(
            envs.get("communicator_independent_recv_interval", 0.05))
        # stop(): how long to wait per merge thread before logging a
        # warning and moving on (env wins, then the FLAG)
        jt = envs.get("communicator_send_join_timeout")
        self._join_timeout = (float(jt) if jt is not None else
                              float(core.globals_[
                                  "FLAGS_communicator_join_timeout"]))
        self._queues: Dict[Tuple[str, str], "queue.Queue"] = {}
        self._threads: list = []
        self._lock = threading.Lock()
        # per-(var, endpoint) first-transport-failure time: merged grads
        # REQUEUE during an endpoint outage (a failover promotes the
        # replica within ~2× the heartbeat timeout and the slot resolves
        # there) and only drop once FLAGS_ps_failover_deadline passed —
        # the pre-elastic behavior silently lost the round's grads
        self._fail_since: Dict[Tuple[str, str], float] = {}
        # stop() flushes queues in SUBMIT order: first-push sequence per
        # (var, endpoint) key — deterministic, matches the order the
        # trainer first produced each grad stream
        self._first_seq: Dict[Tuple[str, str], int] = {}
        self._push_seq = 0
        # typed-and-counted background outcomes; read via stats() and
        # the ps_communicator telemetry view registered on start()
        self._stats_lock = threading.Lock()
        self._stats = {
            "pushes_total": 0,            # grads enqueued by send ops
            "merged_sends_total": 0,      # flush RPCs issued
            "vars_sent_total": 0,         # vars across those flushes
            "dgc_sends_total": 0,         # vars shipped top-k on async path
            "send_ok_total": 0,
            "send_retry_total": 0,        # typed: transport/stale-view
            "requeued_grads_total": 0,    # grads put back during outage
            "dropped_rejected_total": 0,  # typed: server rejected content
            "dropped_deadline_total": 0,  # typed: failover deadline passed
            "recv_rounds_total": 0,       # background recv-thread pulls
            "recv_errors_total": 0,       # typed: recv pull failed
            "stop_flushes_total": 0,
        }
        # independent recv plane: registered pull set + double buffer
        self._recv_lock = threading.Lock()
        self._recv_set: Optional[list] = None   # [(name, ep)]
        self._recv_tid = 0
        self._recv_thread: Optional[threading.Thread] = None
        self._recv_buf = (-1, None)   # (seq, {name: ndarray})
        self._recv_installed = -1
        self._recv_primed = False     # first recv op primed synchronously
        self._view = None

    def _bump(self, key: str, n: int = 1):
        with self._stats_lock:
            self._stats[key] += n

    def stats(self) -> dict:
        with self._stats_lock:
            out = dict(self._stats)
        with self._lock:
            out["queued_now"] = sum(q.qsize()
                                    for q in self._queues.values())
        out["running"] = bool(self._running)
        return out

    # ---------------------------------------------------------- lifecycle
    def start(self):
        self._running = True
        Communicator._global = self
        if self._view is None:
            from . import telemetry
            self._view = telemetry.REGISTRY.register_view(
                "ps_communicator", self.stats)

    def stop(self):
        # a stop racing an in-flight async-overlap window must drain
        # the staleness pipe FIRST, in FIFO submit order: the pipe's
        # rounds still hold unpushed grads and barrier arrivals the
        # server is counting on, and the merge-queue flush below
        # assumes SYNC rounds (no round may land AFTER the flush, or
        # the server's round accounting sees a phantom late send).
        # Deterministic order = the single pipeline worker's FIFO; the
        # drain is bounded so a wedged round (dead pserver) degrades to
        # the same warn-and-continue contract as the merge threads.
        pipe = _round_pipe
        if pipe is not None:
            try:
                if not pipe.drain(timeout=max(self._join_timeout * 10,
                                              10.0)):
                    _LOG.warning(
                        "Communicator.stop: async round pipe still has "
                        "%d round(s) in flight after the drain timeout "
                        "— a pserver is unreachable; their grads/pulls "
                        "are dropped", pipe.inflight())
            except BaseException as e:  # noqa: BLE001 — stop() finishes
                _LOG.warning(
                    "Communicator.stop: deferred async-round error "
                    "surfaced during the pre-flush drain: %r", e)
        self._running = False
        if Communicator._global is self:
            Communicator._global = None
        rt = self._recv_thread
        if rt is not None and rt.is_alive():
            rt.join(timeout=self._join_timeout)
        for t in self._threads:
            t.join(timeout=self._join_timeout)
            if t.is_alive():
                # a leaked thread means a send is wedged (dead pserver,
                # RPC retry loop) — name it so the operator can tell
                # WHICH var/endpoint queue is stuck
                _LOG.warning(
                    "Communicator.stop: merge thread %r still running "
                    "after %.1fs join timeout — a send to its endpoint "
                    "is wedged; its queued grads may be dropped",
                    t.name, self._join_timeout)
        # flush whatever is still queued — fully, not just one merge
        # batch, in SUBMIT order (first-push sequence per queue): the
        # pserver sees the tail of the stream in the same order the
        # trainer produced it, so a final-state comparison right after
        # stop() is deterministic. Snapshot under the lock and bound
        # the loop so a misbehaving producer still pushing during
        # stop() can't spin this forever.
        with self._lock:
            snapshot = dict(self._queues)
            order = sorted(snapshot,
                           key=lambda k: self._first_seq.get(k, 0))
        for key in order:
            q = snapshot[key]
            flushes = 0
            while not q.empty() and flushes < 1000:
                self._drain(key)
                flushes += 1
                self._bump("stop_flushes_total")
        with self._lock:
            # drop queues so a later start()/push() spawns fresh merge
            # threads (the old ones exited when _running went False)
            self._queues.clear()
            self._threads.clear()
            self._first_seq.clear()
        with self._recv_lock:
            self._recv_set = None
            self._recv_thread = None
            self._recv_buf = (-1, None)
            self._recv_installed = -1
            self._recv_primed = False
        if self._view is not None:
            from . import telemetry
            telemetry.REGISTRY.unregister_view(self._view)
            self._view = None

    def is_running(self):
        return self._running

    @classmethod
    def global_instance(cls) -> Optional["Communicator"]:
        c = cls._global
        return c if c is not None and c._running else None

    # ------------------------------------------------------------- queues
    def push(self, name: str, value, endpoint: str, trainer_id: int = 0):
        """Called by the async send op: enqueue one gradient; a per-var
        daemon merges and sends (reference AsyncCommunicator::Send)."""
        key = (name, endpoint)
        with self._lock:
            q = self._queues.get(key)
            if q is None:
                q = self._queues[key] = queue.Queue()
                t = threading.Thread(
                    target=self._merge_loop, args=(key, trainer_id),
                    name=f"communicator-merge-{name}@{endpoint}",
                    daemon=True)
                t.start()
                self._threads.append(t)
            self._push_seq += 1
            self._first_seq.setdefault(key, self._push_seq)
        self._bump("pushes_total")
        q.put(np.asarray(value))

    def _send_merged(self, name, ep, merged, trainer_id) -> str:
        """Ship one merged grad; a failure warns instead of killing the
        merge thread (a dead thread would silently pin the queue and
        every later grad). Returns _send_batch's "ok"/"retry"/"drop" —
        the stop()-time flush ignores it (no requeue while stopping)."""
        return self._send_batch(ep, [(name, merged)], trainer_id)

    def _send_batch(self, ep, items, trainer_id) -> str:
        """Ship one coalesced flush: a single-var batch goes out as the
        plain ``send_var`` every server understands; multiple vars for
        the same endpoint ride ONE ``send_vars_batch`` RPC (the server
        applies the whole batch under its grad lock, and the call's
        dedup token covers all of it). An OLD server without the batch
        method falls back to per-var sends (ps_rpc.send_vars_batch —
        only on "no method", when nothing was applied; a PARTIALLY
        applied batch must not be re-sent per-var).

        Returns "ok" | "retry" (transport failure — the endpoint may be
        failing over to a promoted replica, requeue) | "drop" (the
        server REJECTED the content; re-sending the same grads would
        just be rejected again)."""
        from .ps_rpc import VarClient, send_vars_batch
        names = [n for n, _ in items]
        try:
            if len(items) == 1:
                VarClient.of(ep).send_var(names[0], items[0][1],
                                          trainer_id=trainer_id)
            else:
                send_vars_batch(VarClient.of(ep), items,
                                trainer_id=trainer_id)
            self._bump("send_ok_total")
            self._bump("merged_sends_total")
            self._bump("vars_sent_total", len(items))
            return "ok"
        except (ConnectionError, OSError) as e:
            _LOG.warning(
                "Communicator: merged grads %s for %s undeliverable — "
                "endpoint unreachable after RPC retries (%r)", names, ep, e)
            self._bump("send_retry_total")
            return "retry"
        except core.StaleClusterViewError as e:
            # the call's re-route budget ran out while membership was
            # still converging (a drain racing a failover) — NOT a
            # content rejection: the views settle moments later, so
            # requeue like a transport outage instead of silently
            # losing the round's merged grads
            _LOG.warning(
                "Communicator: merged grads %s for %s caught a "
                "stale-view convergence window (%r) — requeueing",
                names, ep, e)
            self._bump("send_retry_total")
            return "retry"
        except Exception as e:  # noqa: BLE001 — server-side rejection
            _LOG.warning(
                "Communicator: dropping merged grads %s for %s — "
                "server rejected them (%r)", names, ep, e)
            self._bump("dropped_rejected_total", len(items))
            return "drop"

    def _send_dgc(self, ep, name, merged, trainer_id):
        """Ship one merged grad top-k compressed on the async path
        (FLAGS_dgc; the same dgc_send frame the sync _push_dense_batch
        lane uses). compress() folds the grad into the error-feedback
        residual and zeroes the selection, so a transport failure must
        RESTORE the mass before requeueing — restore_dense() hands the
        full accumulator back and clears the residual, and the caller
        requeues that dense payload (re-compressed at the next flush:
        mass is conserved across the outage, momentum state resets —
        acceptable under an outage, documented contract). Returns
        (outcome, requeue_payload_or_None): "sent" | "pass" (not
        eligible / old server — caller ships dense) | "retry" |
        "drop"."""
        from .ps_rpc import VarClient
        g = np.asarray(merged)
        cli = VarClient.of(ep)
        if "dgc_send" in cli._missing_methods:
            return "pass", None
        comp = dgc_compressor()
        enc = comp.compress(name, g)
        if enc is None:
            return "pass", None
        idx, vals = enc
        try:
            cli.call("dgc_send", name=name, values=vals, indices=idx,
                     shape=list(g.shape), trainer_id=trainer_id)
            self._bump("dgc_sends_total")
            return "sent", None
        except (ConnectionError, OSError, core.StaleClusterViewError) as e:
            full = comp.restore_dense(name, idx, vals)
            _LOG.warning(
                "Communicator: dgc push %s for %s undeliverable (%r) — "
                "restored residual, requeueing dense", name, ep, e)
            return "retry", full.reshape(g.shape)
        except Exception as e:  # noqa: BLE001 — old server / rejection
            if "no method dgc_send" in str(e):
                cli._missing_methods.add("dgc_send")
                full = comp.restore_dense(name, idx, vals)
                return "pass", full.reshape(g.shape)
            _LOG.warning(
                "Communicator: dropping dgc push %s for %s — server "
                "rejected it (%r)", name, ep, e)
            return "drop", None

    def _drain(self, key, trainer_id=0):
        name, ep = key
        merged = self._drain_nowait(key)
        if merged is not None:
            self._send_merged(name, ep, merged, trainer_id)

    def _drain_nowait(self, key):
        """Merge whatever is queued for ``key`` right now (no waiting);
        None when its queue is empty."""
        q = self._queues.get(key)
        if q is None:
            return None
        merged, n = None, 0
        while n < self._max_merge:
            try:
                v = q.get_nowait()
            except queue.Empty:
                break
            merged = v if merged is None else merged + v
            n += 1
        return merged

    def _merge_loop(self, key, trainer_id):
        name, ep = key
        q = self._queues[key]
        while self._running:
            try:
                first = q.get(timeout=self._wait_times * 10)
            except queue.Empty:
                continue
            merged = np.asarray(first)
            n = 1
            # short grace window lets a burst of pending grads coalesce
            deadline = threading.Event()
            deadline.wait(self._wait_times)
            while n < self._max_merge:
                try:
                    merged = merged + q.get_nowait()
                    n += 1
                except queue.Empty:
                    break
            # coalesced flush: piggyback OTHER vars pending for the same
            # endpoint onto this send (one multi-var RPC instead of one
            # RPC per var — the reference AsyncCommunicator's batched
            # send queues). queue.get_nowait is atomic, so a concurrent
            # sibling merge thread never double-takes a grad. The legacy
            # data-plane lane (PADDLE_TPU_PS_PICKLE_WIRE=1) keeps the
            # pre-overhaul one-RPC-per-var behavior.
            from .ps_rpc import _pickle_wire_forced
            batch = [(name, merged)]
            if not _pickle_wire_forced():
                with self._lock:
                    siblings = [k for k in self._queues
                                if k[1] == ep and k != key]
                for k in siblings:
                    other = self._drain_nowait(k)
                    if other is not None:
                        batch.append((k[0], other))
            # FLAGS_dgc: eligible merged grads ship as top-k dgc_send
            # frames right here on the async path (the sync lane does
            # this in _push_dense_batch); the rest — plus any restored
            # dense fallbacks — ride the coalesced batch send below
            send_items, requeue_now = [], []
            if dgc_enabled() and not _pickle_wire_forced():
                for n, v in batch:
                    oc, payload = self._send_dgc(ep, n, v, trainer_id)
                    if oc == "sent":
                        continue
                    if oc == "pass":
                        send_items.append(
                            (n, v if payload is None else payload))
                    elif oc == "retry":
                        requeue_now.append((n, payload))
                    # "drop": counted in _send_dgc's rejection path
            else:
                send_items = batch
            outcome = "ok"
            if send_items:
                outcome = self._send_batch(ep, send_items, trainer_id)
            to_requeue = list(requeue_now)
            if outcome == "retry":
                to_requeue.extend(send_items)
            if to_requeue and self._running:
                # endpoint outage (possibly a failover in progress):
                # requeue every merged grad onto its own queue — the
                # NEXT flush re-resolves the slot and reaches the
                # promoted replica. Give up only past the failover
                # deadline; a permanently dead endpoint must not spin
                # the thread and pin stale grads forever.
                import time as _time
                now = _time.time()
                first = self._fail_since.setdefault(key, now)
                limit = float(core.globals_["FLAGS_ps_failover_deadline"])
                if now - first <= limit:
                    for n, v in to_requeue:
                        self.push(n, v, ep, trainer_id=trainer_id)
                    self._bump("requeued_grads_total", len(to_requeue))
                    # breathe: don't hot-loop against a dead endpoint
                    threading.Event().wait(self._wait_times * 10)
                else:
                    _LOG.warning(
                        "Communicator: giving up on %s after %.0fs of "
                        "transport failures — dropping %d merged "
                        "grad(s)", ep, now - first,
                        len(to_requeue))
                    self._bump("dropped_deadline_total", len(to_requeue))
                    self._fail_since.pop(key, None)
            elif outcome != "retry":
                # "ok" AND "drop" both end the outage streak ("drop" =
                # the server was reachable and rejected): a stale
                # first-failure stamp would make a later unrelated
                # outage give up on its first "retry" instead of
                # requeueing through the failover window
                self._fail_since.pop(key, None)

    # --------------------------------------------------- independent recv
    # reference AsyncCommunicator::RecvThread: in async mode the trainer
    # never blocks a step on a param pull — a background thread refreshes
    # a double buffer at _recv_interval and the recv op installs the
    # newest completed buffer at the next step boundary (same protocol
    # as RoundPipeline.take_fresh_pulls). Registration happens lazily
    # from the first recv op execution, which knows the (param, ep) set.

    def register_recv(self, pairs, trainer_id: int = 0):
        """Register the async pull set [(param_name, endpoint)] and
        start the recv thread (idempotent)."""
        with self._recv_lock:
            merged = dict(self._recv_set or [])
            merged.update(dict(pairs))
            self._recv_set = sorted(merged.items())
            self._recv_tid = int(trainer_id)
            if self._recv_thread is None or \
                    not self._recv_thread.is_alive():
                self._recv_thread = threading.Thread(
                    target=self._recv_loop, name="communicator-recv",
                    daemon=True)
                self._recv_thread.start()

    def take_fresh_recv(self):
        """Newest completed background pull, handed out exactly once
        (None when the trainer already installed it)."""
        with self._recv_lock:
            seq, buf = self._recv_buf
            if buf is None or seq <= self._recv_installed:
                return None
            self._recv_installed = seq
            return buf

    def _pull_once(self, pairs, tid) -> dict:
        """Fetch every registered param once; an unreachable endpoint
        skips its params for THIS refresh only (the trainer keeps the
        last installed values — bounded staleness, never a crash) and
        is typed + counted."""
        from .ps_rpc import VarClient
        by_ep: Dict[str, list] = {}
        for n, ep in pairs:
            by_ep.setdefault(ep, []).append(n)
        buf = {}
        for ep, names in by_ep.items():
            cli = VarClient.of(ep)
            try:
                if len(names) > 1 and \
                        "get_vars_batch" not in cli._missing_methods:
                    try:
                        got = cli.call("get_vars_batch", names=names,
                                       trainer_id=tid)
                    except RuntimeError as e:
                        if "no method get_vars_batch" not in str(e):
                            raise
                        cli._missing_methods.add("get_vars_batch")
                        got = [cli.get_var(n, trainer_id=tid)
                               for n in names]
                else:
                    got = [cli.get_var(n, trainer_id=tid) for n in names]
                for n, v in zip(names, got):
                    buf[n] = np.asarray(v)
            except Exception as e:  # noqa: BLE001 — typed + counted
                self._bump("recv_errors_total")
                _LOG.warning(
                    "Communicator: background recv from %s failed "
                    "(%r) — keeping last installed params", ep, e)
        return buf

    def _recv_loop(self):
        seq = 0
        while self._running:
            threading.Event().wait(self._recv_interval)
            if not self._running:
                return
            with self._recv_lock:
                pairs, tid = self._recv_set, self._recv_tid
            if not pairs:
                continue
            buf = self._pull_once(pairs, tid)
            if buf:
                seq += 1
                with self._recv_lock:
                    self._recv_buf = (seq, buf)
                self._bump("recv_rounds_total")

    def recv(self) -> dict:
        """One synchronous pull of the registered set (start-up priming
        / tests); returns the buffer without touching the double-buffer
        seq accounting."""
        with self._recv_lock:
            pairs, tid = self._recv_set, self._recv_tid
        return self._pull_once(pairs or [], tid)


class LargeScaleKV:
    """Host-RAM key→row store stub (reference large_scale_kv.h); the
    pserver scope already hosts whole tables in this build."""

    def __init__(self):
        self._store = {}

    def save(self, name, path):
        import numpy as np
        np.save(path, self._store.get(name))

    def size(self, name):
        v = self._store.get(name)
        return 0 if v is None else len(v)
