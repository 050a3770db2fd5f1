"""Profiler (reference: python/paddle/fluid/profiler.py over
platform/profiler.h — RecordEvent:124 RAII spans nested per op,
EnableProfiler/DisableProfiler:206 with sorted summary tables
(profiler_helper.h), CUPTI DeviceTracer → chrome://tracing via
tools/timeline.py).

TPU layering:
  * host spans — RecordEvent stack collected here; the executor wraps each
    eager op and each compiled-step dispatch (operator.cc:948-977 hook
    points). stop_profiler prints the reference-style sorted table and
    writes a chrome://tracing JSON that tools/timeline.py merges/views.
  * device timeline — every span is a `jax.profiler.TraceAnnotation`, so
    whoever starts a `jax.profiler` trace (the DeviceTracer/CUPTI
    replacement) finds the spans there, on the device events' clock;
    this module starts none.
  * the step record — the executor-stage spans that close inside an
    `exe:run` span add their seconds to that run's record
    (`telemetry.STEPS`), whether or not anything else records.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional

import jax

from . import core
from . import telemetry

__all__ = ["cuda_profiler", "reset_profiler", "profiler", "start_profiler",
           "stop_profiler", "record_event", "RecordEvent", "is_profiling",
           "record_span", "record_instant", "snapshot_events",
           "concurrent_seconds", "dropped_events"]


class _Event:
    __slots__ = ("name", "start", "end", "tid", "cat", "args",
                 "trace_id", "span_id", "parent_id")

    def __init__(self, name, start, end, tid, cat="host", args=None,
                 trace_id=None, span_id=None, parent_id=None):
        self.name = name
        self.start = start
        self.end = end
        self.tid = tid
        self.cat = cat
        self.args = args  # chrome-trace "args" payload (e.g. rpc bytes)
        # trace correlation (telemetry.trace_scope): stamped from the
        # recording thread's installed context, None outside any trace
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id


def _ring(maxlen_hint: Optional[int] = None) -> deque:
    """FLAGS_profiler_max_events-bounded event store: beyond the bound
    the OLDEST events drop (counted) instead of growing the host heap
    for a long run's lifetime. The bound is read at ring creation —
    start_profiler / reset_profiler — not per append."""
    n = maxlen_hint if maxlen_hint is not None else int(
        core.globals_["FLAGS_profiler_max_events"])
    return deque(maxlen=max(1, n))


class _ProfilerState:
    def __init__(self):
        self.enabled = False
        self.events: deque = _ring(1024)
        self.dropped = 0
        self.lock = threading.Lock()
        self.t0 = 0.0
        self.depth = 0  # nested profiler()/cuda_profiler() contexts


_prof = _ProfilerState()


def is_profiling() -> bool:
    """True when spans should be recorded: an explicit profiler session
    is on OR FLAGS_trace_dir shard streaming is active (the cluster-
    timeline mode records without start_profiler)."""
    return _prof.enabled or telemetry.shard_active()


def is_session() -> bool:
    """True ONLY during an explicit start_profiler() session — the gate
    for measurement-mode side effects (executor block_until_ready,
    numeric-guard flag readbacks). FLAGS_trace_dir shard streaming
    records spans WITHOUT them: a shard-only step span measures
    dispatch, not device completion, so always-on cluster tracing never
    re-adds the per-step host syncs PR 5 engineered away
    (docs/OBSERVABILITY.md "1-core caveats")."""
    return _prof.enabled


def dropped_events() -> int:
    """Events dropped by the FLAGS_profiler_max_events ring since the
    last start/reset."""
    with _prof.lock:
        return _prof.dropped


def start_profiler(state="All", tracer_option="Default"):
    """reference profiler.py start_profiler / EnableProfiler. ``state``
    is the reference's argument and every value records host spans: the
    device's events are `jax.profiler`'s to trace, and the spans are in
    its trace too."""
    if _prof.enabled:
        _prof.depth += 1  # nested enable: inner stop becomes a no-op pair
        return
    _prof.depth = 1
    with _prof.lock:
        _prof.events = _ring()
        _prof.dropped = 0
    _prof.enabled = True
    _prof.t0 = time.perf_counter()


def stop_profiler(sorted_key: Optional[str] = None,
                  profile_path: str = "/tmp/profile"):
    """Print the sorted summary table (reference profiler_helper.h
    PrintProfiler) and write a chrome://tracing JSON to ``profile_path``
    (consumed by tools/timeline.py)."""
    if not _prof.enabled:
        return
    _prof.depth -= 1
    if _prof.depth > 0:  # inner context of a nested session: keep going
        return
    _prof.enabled = False
    with _prof.lock:
        events = list(_prof.events)
        dropped = _prof.dropped
    if dropped:
        print(f"[profiler] {dropped} oldest event(s) dropped by the "
              f"FLAGS_profiler_max_events ring "
              f"(bound {_prof.events.maxlen})")
    _summary(events, sorted_key)
    if profile_path:
        _write_chrome_trace(events, profile_path)
        print(f"[profiler] host timeline written to {profile_path} "
              f"(tools/timeline.py or chrome://tracing)")


def reset_profiler():
    with _prof.lock:
        _prof.events = _ring()
        _prof.dropped = 0
        _prof.t0 = time.perf_counter()


def _record(name: str, start: float, end: float, cat: str = "host",
            args=None):
    tctx = telemetry.current_trace()
    tid = threading.get_ident()
    if _prof.enabled:
        if tctx is None:
            ev = _Event(name, start, end, tid, cat, args)
        else:
            ev = _Event(name, start, end, tid, cat, args,
                        tctx.trace_id, tctx.span_id, tctx.parent_id)
        with _prof.lock:
            if len(_prof.events) == _prof.events.maxlen:
                _prof.dropped += 1
            _prof.events.append(ev)
    # cluster-timeline shard (FLAGS_trace_dir): every recorded span also
    # streams to the process's chrome-trace shard — no-op when off
    telemetry.shard_record(name, start, end, tid, cat, args, tctx)


def record_span(name: str, start: float, end: float, cat: str = "host",
                args=None) -> None:
    """Record an already-timed span (perf_counter endpoints). No-op when
    profiling is off. Used by layers that time work themselves — the PS
    RPC client attaches byte/retry counts as chrome-trace args here."""
    if is_profiling():
        _record(name, start, end, cat, args)


def record_instant(name: str, cat: str = "host", args=None) -> None:
    """Zero-duration marker event. No-op when profiling is off. The
    numeric fault plane emits its trip/rollback markers here under
    cat='health' (args carry the step, the offending segment, and the
    action taken) so they land beside the cat='segment'/'window'/'rpc'
    spans in the chrome trace."""
    if is_profiling():
        t = time.perf_counter()
        _record(name, t, t, cat, args)


def snapshot_events():
    """Thread-safe copy of the recorded host events as plain dicts
    (name/start/end/tid/cat/args + trace correlation ids) — for tests
    that compute evidence from a live profile (e.g. the async-overlap
    concurrency check) without stopping the profiler."""
    with _prof.lock:
        return [{"name": e.name, "start": e.start, "end": e.end,
                 "tid": e.tid, "cat": e.cat, "args": e.args,
                 "trace_id": e.trace_id, "span_id": e.span_id,
                 "parent_id": e.parent_id}
                for e in _prof.events]


def _merge_intervals(spans):
    out = []
    for s, e in sorted(spans):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def concurrent_seconds(cat_a: str, cat_b: str, events=None) -> float:
    """Wall seconds during which a ``cat_a`` span overlaps IN TIME with
    a ``cat_b`` span recorded on a DIFFERENT thread — the async-overlap
    plane's evidence metric (docs/PS_DATA_PLANE.md "Async overlap"):
    cat='comm' spans (round pipeline / prefetch threads) concurrent
    with cat='segment'/'window' step spans on the main thread prove the
    wire ran behind the compiled step instead of taking turns with
    it. Both span sets are union-merged first so nesting never double
    counts."""
    events = snapshot_events() if events is None else events
    total = 0.0
    a_tids = {e["tid"] for e in events if e["cat"] == cat_a}
    for tid in a_tids:
        a = _merge_intervals([(e["start"], e["end"]) for e in events
                              if e["cat"] == cat_a and e["tid"] == tid])
        b = _merge_intervals([(e["start"], e["end"]) for e in events
                              if e["cat"] == cat_b and e["tid"] != tid])
        i = j = 0
        while i < len(a) and j < len(b):
            s = max(a[i][0], b[j][0])
            e = min(a[i][1], b[j][1])
            if e > s:
                total += e - s
            if a[i][1] <= b[j][1]:
                i += 1
            else:
                j += 1
    return total


# the categories of the spans a step record reads: the executor's stages
# and the window and segment dispatches
_STEP_CATS = frozenset(("executor", "window", "segment"))


class RecordEvent:
    """RAII span (reference platform/profiler.h:124). Usable as a context
    manager or decorator; when profiling is off it is a bare
    `jax.profiler.TraceAnnotation` and records nothing here, but for what
    a stage of `Executor.run` adds to the step record. ``cat`` groups
    spans in the chrome trace — the segmented executor emits its
    per-segment compile/exec and island spans under cat='segment' so the
    compiled/interpreted partition of a step is visible at a glance,
    multi-step windows emit one cat='window' span per dispatched window
    (window[K]:realdata | :broadcast | :fallback — the one-dispatch-per-
    window evidence tests/test_window_executor.py counts), the serving
    plane emits cat='serve' queue-wait/exec spans whose ``args`` carry
    bucket + batch-size chrome-trace payloads plus serve:shed /
    serve:deadline_expired / serve:degraded instants from the ingress
    overload plane (record_instant — args name the drop site:
    admission | codel | rate_gate; docs/SERVING.md "Ingress &
    overload"), and the
    async overlap plane emits cat='comm' spans from its background
    threads (ps_round[i] rounds, sparse_push tasks, prefetch[table]
    fetches, plus main-thread round:stall[pipe_full] backpressure) whose
    concurrency with the step spans ``concurrent_seconds`` measures."""

    def __init__(self, name: str, cat: str = "host", args=None):
        self.name = name
        self.cat = cat
        self.args = args
        self._start = 0.0
        self._recording = False
        self._step = None   # the open step record this span reports to
        self._opened = False  # ... which this span opened itself

    def __enter__(self):
        # always a TraceAnnotation, as JAX instruments its own dispatch
        # path: outside a jax.profiler trace it records nothing (well
        # under a microsecond), inside one — a benchmark's, an operator's
        # jax.profiler.start_server — the span is on the device events'
        # clock with no call into the program. The ring and the
        # FLAGS_trace_dir shard stay gated.
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self._recording = is_profiling()
        if self.cat in _STEP_CATS:
            # the step record (telemetry.STEPS): a span that closes
            # inside an `exe:run` span reports to that run's record; an
            # `exe:run` span with none open on the thread opens one
            self._step = telemetry.open_step()
            if self._step is None and self.name == telemetry.RUN_SPAN:
                self._step, self._opened = telemetry.begin_step(), True
        if self._opened:
            self._start = self._step.t0  # one clock read, the record's
        elif self._recording or self._step is not None:
            self._start = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc_val, exc_tb):
        self._ann.__exit__(exc_type, exc_val, exc_tb)
        # gate on the per-span state, not the global flag: a span that a
        # stop_profiler lands in is still recorded whole
        if self._start:
            end = time.perf_counter()
            if self._recording:
                _record(self.name, self._start, end, self.cat, self.args)
            step, self._step = self._step, None
            if step is not None:
                if self._opened:
                    self._opened = False
                    telemetry.end_step(step, end)
                else:
                    step.add(self.name, end - self._start, self.args)
            self._start = 0.0
        return False

    def __call__(self, fn):
        import functools

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with RecordEvent(self.name):
                return fn(*args, **kwargs)
        return wrapper


@contextlib.contextmanager
def record_event(name: str):
    with RecordEvent(name):
        yield


# ---------------------------------------------------------------- reports
_SORT_KEYS = {"total", "calls", "max", "min", "ave", None}


def _summary(events: List[_Event], sorted_key: Optional[str]):
    if sorted_key not in _SORT_KEYS:
        raise ValueError(f"sorted_key must be one of {_SORT_KEYS}")
    if not events:
        print("[profiler] no host events recorded")
        return
    agg: Dict[str, List[float]] = {}
    for e in events:
        agg.setdefault(e.name, []).append((e.end - e.start) * 1000.0)
    total_all = sum(sum(v) for v in agg.values())
    rows = []
    for name, vals in agg.items():
        tot = sum(vals)
        rows.append((name, len(vals), tot, tot / len(vals), max(vals),
                     min(vals), tot / total_all if total_all else 0.0))
    key_idx = {"calls": 1, "total": 2, "ave": 3, "max": 4, "min": 5,
               None: 2}[sorted_key]
    rows.sort(key=lambda r: -r[key_idx])
    hdr = (f"{'Event':<40}{'Calls':>8}{'Total(ms)':>12}{'Avg(ms)':>10}"
           f"{'Max(ms)':>10}{'Min(ms)':>10}{'Ratio':>8}")
    print("-------------------------     Profiling Report     "
          "-------------------------")
    print(hdr)
    for name, calls, tot, ave, mx, mn, ratio in rows:
        print(f"{name[:39]:<40}{calls:>8}{tot:>12.4f}{ave:>10.4f}"
              f"{mx:>10.4f}{mn:>10.4f}{ratio:>8.2%}")


def _write_chrome_trace(events: List[_Event], path: str):
    """chrome://tracing JSON (the format tools/timeline.py emits in the
    reference)."""
    trace = {"traceEvents": [], "displayTimeUnit": "ms"}
    for e in events:
        ev = {
            "name": e.name, "ph": "X", "pid": os.getpid(), "tid": e.tid,
            "ts": (e.start - _prof.t0) * 1e6,
            "dur": (e.end - e.start) * 1e6, "cat": e.cat}
        args = dict(e.args) if e.args else {}
        if e.trace_id is not None:
            args["trace_id"] = e.trace_id
            args["span_id"] = e.span_id
            if e.parent_id:
                args["parent_id"] = e.parent_id
        if args:
            ev["args"] = args
        trace["traceEvents"].append(ev)
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(trace, f)


@contextlib.contextmanager
def profiler(state="All", sorted_key=None, profile_path="/tmp/profile",
             tracer_option="Default"):
    """reference profiler.py profiler context manager."""
    start_profiler(state, tracer_option)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


@contextlib.contextmanager
def cuda_profiler(output_file=None, output_mode=None, config=None):
    # the reference's accelerator profiler: the same host spans here
    with profiler(state="All", profile_path=output_file or "/tmp/profile"):
        yield
