"""Unified telemetry plane (docs/OBSERVABILITY.md) — the three legs the
rest of the repo's observability hangs off:

  * **distributed trace correlation** — a Dapper-style thread-local
    trace context (trace_id / span_id / parent). ``trace_scope``
    installs one; the profiler stamps it onto every recorded span, the
    PS RPC client ships it in the ``_trace`` header (ps_rpc), the
    VarServer installs it around handler execution, and the serving
    ingress accepts/mints ``X-Trace-Id`` — so one serving request or
    one training round is followable trainer→pserver→replica end to
    end.
  * **metrics registry** — Counter/Gauge/Histogram primitives with
    labels plus *views* over the repo's existing ``stats()`` dicts,
    exposed in Prometheus text format at the serving ingress
    ``GET /metrics`` and on the opt-in ``FLAGS_metrics_port``
    sidecar server every pserver/trainer can run.
  * **merged cluster timelines** — with ``FLAGS_trace_dir`` set, every
    process streams its profiler spans into a bounded ring-buffer
    chrome-trace shard (raw ``time.perf_counter`` timestamps +
    process/role metadata + the monotonic clock offsets measured in the
    ps_rpc ``_hello`` handshake); ``tools/timeline.py merge`` aligns
    the shards into one clock-corrected timeline keyed by trace id.

This module deliberately imports only ``core`` from the package (for
the FLAGS registry) so every other layer — profiler, ps_rpc, executor,
serving — can depend on it without cycles.
"""
from __future__ import annotations

import atexit
import gc
import itertools
import json
import logging
import os
import threading
import time
import uuid
from collections import deque
from statistics import median as _median
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

from . import core

__all__ = [
    "TraceContext", "trace_scope", "current_trace", "new_trace_id",
    "new_span_id", "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "REGISTRY", "note_clock_offset", "clock_offsets", "set_process_role",
    "process_role", "shard_active", "shard_record", "flush_trace_shard",
    "start_metrics_server", "maybe_start_metrics_server",
    "metrics_server_port", "count_compile", "install_jax_compile_listener",
    "StepRecord", "STEPS", "SLOW_STEPS", "open_step", "step_summary",
]

_LOG = logging.getLogger("paddle_tpu.telemetry")


# ---------------------------------------------------------------------------
# trace context (Dapper-style propagation)
# ---------------------------------------------------------------------------
class TraceContext:
    """One logical span: every profiler event recorded while a context
    is installed carries its (trace_id, span_id, parent_id)."""

    __slots__ = ("trace_id", "span_id", "parent_id")

    def __init__(self, trace_id: str, span_id: str,
                 parent_id: Optional[str] = None):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id

    def __repr__(self):
        return (f"TraceContext(trace_id={self.trace_id!r}, "
                f"span_id={self.span_id!r}, parent_id={self.parent_id!r})")


_TRACE = threading.local()


def new_trace_id() -> str:
    return uuid.uuid4().hex[:16]


def new_span_id() -> str:
    return uuid.uuid4().hex[:16]


def current_trace() -> Optional[TraceContext]:
    """The TraceContext installed on THIS thread (None outside any
    trace_scope)."""
    return getattr(_TRACE, "ctx", None)


class trace_scope:
    """Install a trace context on this thread for the ``with`` body.

    * ``trace_scope()`` — continue the current trace with a CHILD span
      (or start a fresh root trace when none is installed).
    * ``trace_scope(trace_id=..., parent_span_id=...)`` — adopt a trace
      arriving from another process (RPC ``_trace`` header, HTTP
      ``X-Trace-Id``): same trace id, NEW span id parented on the
      caller's span — "same trace id, new span id" is the cross-process
      contract the propagation tests pin down.
    * ``trace_scope(adopt=ctx)`` — re-install an existing context
      verbatim on another thread (the sharded-RPC fan-out pool and the
      serving worker threads carry the submitting thread's context this
      way)."""

    def __init__(self, trace_id: Optional[str] = None,
                 parent_span_id: Optional[str] = None,
                 adopt: Optional[TraceContext] = None):
        self._trace_id = trace_id
        self._parent = parent_span_id
        self._adopt = adopt
        self._prev: Optional[TraceContext] = None
        self.ctx: Optional[TraceContext] = None

    def __enter__(self) -> TraceContext:
        self._prev = current_trace()
        if self._adopt is not None:
            self.ctx = self._adopt
        elif self._trace_id is not None:
            self.ctx = TraceContext(self._trace_id, new_span_id(),
                                    self._parent)
        elif self._prev is not None:
            self.ctx = TraceContext(self._prev.trace_id, new_span_id(),
                                    self._prev.span_id)
        else:
            self.ctx = TraceContext(new_trace_id(), new_span_id(), None)
        _TRACE.ctx = self.ctx
        return self.ctx

    def __exit__(self, *exc):
        _TRACE.ctx = self._prev
        return False


# ---------------------------------------------------------------------------
# metrics registry (Prometheus-style exposition)
# ---------------------------------------------------------------------------
def _sanitize(name: str) -> str:
    out = []
    for ch in str(name):
        out.append(ch if (ch.isalnum() or ch in "_:") else "_")
    s = "".join(out)
    return s or "_"


def _fmt_value(v) -> str:
    if isinstance(v, bool):
        return "1" if v else "0"
    if isinstance(v, int):
        return str(v)
    return repr(float(v))


def _fmt_labels(labels: Dict[str, str]) -> str:
    if not labels:
        return ""
    parts = []
    for k, v in sorted(labels.items()):
        v = (str(v).replace("\\", "\\\\").replace('"', '\\"')
             .replace("\n", "\\n"))
        parts.append(f'{_sanitize(k)}="{v}"')
    return "{" + ",".join(parts) + "}"


class _Child:
    """One labeled instance of a metric family."""

    def __init__(self, family: "_MetricFamily", labels: Dict[str, str]):
        self._family = family
        self.labels_dict = labels
        self._lock = threading.Lock()
        self._value = 0.0
        # histogram state
        if family.kind == "histogram":
            self._bucket_counts = [0] * len(family.buckets)
            self._sum = 0.0
            self._count = 0

    # counter / gauge -----------------------------------------------------
    def inc(self, n: float = 1) -> None:
        with self._lock:
            self._value += n

    def set(self, v: float) -> None:
        if self._family.kind != "gauge":
            raise TypeError(f"{self._family.name}: set() on a "
                            f"{self._family.kind}")
        with self._lock:
            self._value = v

    def value(self) -> float:
        with self._lock:
            v = self._value
        return int(v) if float(v).is_integer() else v

    def _reset(self) -> None:
        """Internal: zero the child (the serving engine's reset_stats
        contract predates the registry and keeps working as a view)."""
        with self._lock:
            self._value = 0.0
            if self._family.kind == "histogram":
                self._bucket_counts = [0] * len(self._family.buckets)
                self._sum = 0.0
                self._count = 0

    # histogram -----------------------------------------------------------
    def observe(self, v: float) -> None:
        if self._family.kind != "histogram":
            raise TypeError(f"{self._family.name}: observe() on a "
                            f"{self._family.kind}")
        with self._lock:
            for i, b in enumerate(self._family.buckets):
                if v <= b:
                    self._bucket_counts[i] += 1
            self._sum += v
            self._count += 1

    def histogram_state(self):
        with self._lock:
            return list(self._bucket_counts), self._sum, self._count


_DEFAULT_BUCKETS = (0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                    1.0, 2.5, 5.0, 10.0)


class _MetricFamily:
    def __init__(self, name: str, kind: str, help: str,
                 labelnames: Tuple[str, ...], buckets=None):
        self.name = _sanitize(name)
        self.kind = kind
        self.help = help
        self.labelnames = tuple(labelnames)
        self.buckets = tuple(sorted(buckets or _DEFAULT_BUCKETS)) \
            if kind == "histogram" else ()
        self._children: Dict[Tuple[str, ...], _Child] = {}
        self._lock = threading.Lock()

    def labels(self, **kv) -> _Child:
        if set(kv) != set(self.labelnames):
            raise ValueError(
                f"{self.name}: labels {sorted(kv)} != declared "
                f"{sorted(self.labelnames)}")
        key = tuple(str(kv[n]) for n in self.labelnames)
        with self._lock:
            ch = self._children.get(key)
            if ch is None:
                ch = self._children[key] = _Child(
                    self, dict(zip(self.labelnames, key)))
            return ch

    def remove(self, **kv) -> None:
        key = tuple(str(kv.get(n, "")) for n in self.labelnames)
        with self._lock:
            self._children.pop(key, None)

    # label-less convenience: family acts as its single child
    def _solo(self) -> _Child:
        if self.labelnames:
            raise ValueError(f"{self.name}: has labels "
                             f"{self.labelnames} — use .labels()")
        return self.labels()

    def inc(self, n: float = 1) -> None:
        self._solo().inc(n)

    def set(self, v: float) -> None:
        self._solo().set(v)

    def observe(self, v: float) -> None:
        self._solo().observe(v)

    def value(self, **kv) -> float:
        return (self.labels(**kv) if kv else self._solo()).value()

    def children(self) -> List[_Child]:
        with self._lock:
            return list(self._children.values())


class Counter(_MetricFamily):
    def __init__(self, name, help="", labelnames=()):
        super().__init__(name, "counter", help, labelnames)


class Gauge(_MetricFamily):
    def __init__(self, name, help="", labelnames=()):
        super().__init__(name, "gauge", help, labelnames)


class Histogram(_MetricFamily):
    def __init__(self, name, help="", labelnames=(), buckets=None):
        super().__init__(name, "histogram", help, labelnames,
                         buckets=buckets)


def _flatten_stats(prefix: str, obj, out: List[Tuple[str, float]]):
    """Flatten a stats() dict into (metric_name, value) samples: nested
    keys join with '_' (sanitized), numeric leaves only — strings,
    lists and Nones are skipped (they are labels/evidence, not
    samples). This is what keeps the dict APIs authoritative while
    /metrics exposes the same numbers."""
    if isinstance(obj, dict):
        for k, v in obj.items():
            _flatten_stats(f"{prefix}_{_sanitize(k)}", v, out)
        return
    if isinstance(obj, bool):
        out.append((prefix, int(obj)))
        return
    if isinstance(obj, (int, float)):
        out.append((prefix, obj))
        return
    # numpy scalars quack like floats without being instances
    try:
        import numpy as _np
        if isinstance(obj, _np.generic):
            out.append((prefix, obj.item()))
    except Exception:
        pass


class MetricsRegistry:
    """Process-global metric store. Two registration styles:

    * primitives — ``counter``/``gauge``/``histogram`` (get-or-create
      by name; kind conflicts raise) for NEW instrumentation;
    * views — ``register_view(prefix, fn, labels)`` bridges an
      existing ``stats()`` dict: ``fn()`` is called at scrape time and
      its numeric leaves are exposed as gauges named
      ``<prefix>_<joined keys>`` carrying ``labels``. The dict API
      stays the source of truth, so /metrics can never drift from
      ``stats()``."""

    def __init__(self):
        self._lock = threading.Lock()
        self._families: Dict[str, _MetricFamily] = {}
        self._views: List[Tuple[str, Callable[[], dict],
                                Dict[str, str], object]] = []

    # ------------------------------------------------------- primitives
    def _family(self, cls, name, help, labelnames, **kw) -> _MetricFamily:
        name = _sanitize(name)
        with self._lock:
            fam = self._families.get(name)
            if fam is None:
                fam = self._families[name] = cls(
                    name, help=help, labelnames=labelnames, **kw)
            elif not isinstance(fam, cls) \
                    or tuple(labelnames) != fam.labelnames:
                raise ValueError(
                    f"metric {name!r} already registered as "
                    f"{fam.kind} with labels {fam.labelnames}")
            return fam

    def counter(self, name, help="", labelnames=()) -> Counter:
        return self._family(Counter, name, help, labelnames)

    def gauge(self, name, help="", labelnames=()) -> Gauge:
        return self._family(Gauge, name, help, labelnames)

    def histogram(self, name, help="", labelnames=(),
                  buckets=None) -> Histogram:
        return self._family(Histogram, name, help, labelnames,
                            buckets=buckets)

    def get(self, name) -> Optional[_MetricFamily]:
        with self._lock:
            return self._families.get(_sanitize(name))

    # ------------------------------------------------------------ views
    def register_view(self, prefix: str, fn: Callable[[], dict],
                      labels: Optional[Dict[str, str]] = None) -> object:
        """Register a stats-dict view; returns a handle for
        ``unregister_view``."""
        handle = object()
        with self._lock:
            self._views.append((_sanitize(prefix), fn,
                                dict(labels or {}), handle))
        return handle

    def unregister_view(self, handle) -> None:
        with self._lock:
            self._views = [v for v in self._views if v[3] is not handle]

    # ------------------------------------------------------- exposition
    def collect(self) -> Dict[str, Dict[str, Any]]:
        """name -> {type, help, samples: [(labels, value)]} — the
        structured form ``exposition`` renders (tests assert against
        this to dodge text parsing)."""
        out: Dict[str, Dict[str, Any]] = {}
        with self._lock:
            fams = list(self._families.values())
            views = list(self._views)
        for fam in fams:
            entry = out.setdefault(fam.name, {
                "type": fam.kind, "help": fam.help, "samples": []})
            for ch in fam.children():
                if fam.kind == "histogram":
                    counts, hsum, cnt = ch.histogram_state()
                    for b, c in zip(fam.buckets, counts):
                        entry["samples"].append((
                            {**ch.labels_dict, "le": repr(float(b))}, c))
                    entry["samples"].append((
                        {**ch.labels_dict, "le": "+Inf"}, cnt))
                    out.setdefault(fam.name + "_sum", {
                        "type": "gauge", "help": "", "samples": []
                    })["samples"].append((dict(ch.labels_dict), hsum))
                    out.setdefault(fam.name + "_count", {
                        "type": "gauge", "help": "", "samples": []
                    })["samples"].append((dict(ch.labels_dict), cnt))
                else:
                    entry["samples"].append(
                        (dict(ch.labels_dict), ch.value()))
        for prefix, fn, labels, _h in views:
            try:
                stats = fn() or {}
            except Exception:
                # a broken view must not break the whole scrape
                _LOG.exception("metrics view %s failed", prefix)
                continue
            samples: List[Tuple[str, float]] = []
            _flatten_stats(prefix, stats, samples)
            for name, value in samples:
                out.setdefault(name, {
                    "type": "gauge", "help": "", "samples": []
                })["samples"].append((dict(labels), value))
        return out

    def exposition(self) -> str:
        """Prometheus text format 0.0.4."""
        lines: List[str] = []
        for name, entry in sorted(self.collect().items()):
            if entry["help"]:
                lines.append(f"# HELP {name} {entry['help']}")
            lines.append(f"# TYPE {name} {entry['type']}")
            for labels, value in entry["samples"]:
                sample_name = (name + "_bucket"
                               if entry["type"] == "histogram" else name)
                lines.append(f"{sample_name}{_fmt_labels(labels)} "
                             f"{_fmt_value(value)}")
        return "\n".join(lines) + "\n"

    def reset(self) -> None:
        """Drop every family and view (tests)."""
        with self._lock:
            self._families.clear()
            self._views.clear()


REGISTRY = MetricsRegistry()


def set_site_gauge(name: str, help: str, site: str, value: float) -> None:
    """A count of one traced op, by the layer that built it (its
    ``site``): a program is traced several times (the grad op's vjp, a
    rematerialised segment, a feed of another shape), and a gauge a
    site reads the same each time; the sites of a process sum to its
    program's step."""
    REGISTRY.gauge(name, help, labelnames=("site",)).labels(
        site=site).set(value)


# executor compile/retrace counters (docs/OBSERVABILITY.md "Step
# telemetry"): bumped at the executor's EXPLICIT jit-cache-miss sites.
# A "compile" is the first entry of a cache; a "retrace" is a later
# miss of an already-populated cache (a new bucket/LoD/program
# signature appearing after warm-up) — the scrapeable form of the
# serving plane's "steady state never recompiles" claim.
def count_compile(kind: str, retrace: bool = False) -> None:
    REGISTRY.counter(
        "executor_compiles_total",
        "jit-cache misses that triggered a trace+compile, by site",
        labelnames=("kind",)).labels(kind=kind).inc()
    if retrace:
        REGISTRY.counter(
            "executor_retraces_total",
            "cache misses AFTER the site already compiled once — new "
            "signature post-warm-up; flat in steady state",
            labelnames=("kind",)).labels(kind=kind).inc()


def count_state_placed(n: int) -> None:
    """State arrays a compiled block's `_place_inputs` had to put on its
    mesh (the start-up program's arrays on step 1, a value a checkpoint
    load or a ``set_value`` left in the scope): flat in steady state,
    where the step's own outputs already lie as the plan says."""
    REGISTRY.counter(
        "executor_state_arrays_placed_total",
        "state arrays placed onto the mesh before a dispatch; those "
        "already placed as the block's plan says pass through uncounted"
    ).inc(n)


_JAX_LISTENER_LOCK = threading.Lock()
_JAX_LISTENER_INSTALLED = False

# What jax.monitoring reports while JAX compiles (names as jax 0.9.0 has
# them, jax/_src/dispatch.py and compiler.py): event -> (counter, help,
# the cat="compile" span or instant recorded beside it).
_JAX_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": (
        "jax_trace_seconds_total",
        "seconds tracing Python into jaxprs; a jit traced inside another "
        "counts in its own event and in its caller's",
        "compile:trace"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration": (
        "jax_lower_seconds_total",
        "seconds lowering jaxprs to MLIR modules", "compile:lower"),
    "/jax/core/compile/backend_compile_duration": (
        "jax_backend_compile_seconds_total",
        "seconds in XLA backend compiles, or in loading them from the "
        "persistent cache", "compile:backend"),
}
_JAX_EVENTS = {
    "/jax/compilation_cache/cache_hits": (
        "jax_compile_cache_hits_total",
        "executables loaded from the persistent compilation cache",
        "compile:cache_hit"),
    "/jax/compilation_cache/cache_misses": (
        "jax_compile_cache_misses_total",
        "executables compiled and written to the persistent compilation "
        "cache (those under its size or time thresholds are not counted)",
        "compile:cache_miss"),
}


def install_jax_compile_listener() -> bool:
    """Register the jax.monitoring listeners ONCE per process. They
    count where JAX compiles — seconds tracing, lowering and in the
    backend, the number of backend compiles, persistent-cache hits and
    misses (the tables above) — and, when the profiler records, emit a
    cat="compile" span or instant for each: ground truth that catches
    what the executor's explicit cache counters cannot see (a second
    signature of one step, shape-driven retraces inside one jit, whether
    a "compile" was a load). Zero cost on the steady-state path: jax only
    calls listeners when it compiles."""
    global _JAX_LISTENER_INSTALLED
    with _JAX_LISTENER_LOCK:
        if _JAX_LISTENER_INSTALLED:
            return True
        import jax.monitoring as _mon

        compiles = ("jax_backend_compiles_total",
                    "XLA backend compiles observed via jax.monitoring")
        # created now, so that a scrape before the first compile reads 0
        REGISTRY.counter(*compiles)
        for name, help_, _span in (*_JAX_DURATIONS.values(),
                                   *_JAX_EVENTS.values()):
            REGISTRY.counter(name, help_)

        def _on_duration(event: str, duration: float, **kw):
            known = _JAX_DURATIONS.get(event)
            if known is None:
                return
            name, help_, span = known
            REGISTRY.counter(name, help_).inc(float(duration))
            if span == "compile:backend":
                REGISTRY.counter(*compiles).inc()
            if span != "compile:lower":
                with _JAX_TOTALS_LOCK:  # threads may compile at once
                    if span == "compile:trace":
                        _JAX_TOTALS[0] += float(duration)
                    else:
                        _JAX_TOTALS[1] += 1
            from . import profiler as _profiler
            if _profiler.is_profiling():
                now = time.perf_counter()
                _profiler.record_span(
                    span, now - float(duration), now, cat="compile",
                    args={"seconds": round(float(duration), 6)})

        def _on_event(event: str, **kw):
            known = _JAX_EVENTS.get(event)
            if known is None:
                return
            name, help_, instant = known
            REGISTRY.counter(name, help_).inc()
            from . import profiler as _profiler
            _profiler.record_instant(instant, cat="compile")

        _mon.register_event_duration_secs_listener(_on_duration)
        _mon.register_event_listener(_on_event)
        _install_gc_probe()
        _JAX_LISTENER_INSTALLED = True
        return True


# ---------------------------------------------------------------------------
# the step record (docs/OBSERVABILITY.md "Step record")
# ---------------------------------------------------------------------------
# One record an `Executor.run`, made by the `exe:run` span
# (`profiler.RecordEvent`) with no session, shard or flag on: the six
# stage spans' seconds, the caller's time before the call, the thread's
# CPU time, the collector's pauses and what JAX traced or compiled
# meanwhile. The newest STEP_RING of them are kept; a step far over its
# block's median period says where it sat (`_flag_slow`).
RUN_SPAN = "exe:run"
STEP_RING = 4096  # the fastest benchmark cell makes 365 steps a window
# a step is slow when its period (since_prev_s + run_s) is over this
# many medians of its block's periods AND over the median by this much
SLOW_STEP_FACTOR = 3.0
SLOW_STEP_EXCESS_S = 0.050
_MEDIAN_AFTER = 8    # a block's first records hold its compiles: not judged
_MEDIAN_EVERY = 64   # records between two readings of a block's median

STEPS: deque = deque(maxlen=STEP_RING)
SLOW_STEPS: deque = deque(maxlen=32)  # kept past the ring's wrap
_STEPS_LOCK = threading.Lock()
_STEP_TLS = threading.local()  # .open: the open record; .prev: a _Mark
_SEQ = itertools.count(1)
_BLOCK_IDS = itertools.count(1)
# block -> [the median period of its last _MEDIAN_EVERY records (of its
# first _MEDIAN_AFTER at first; None before that), the periods since]
_BLOCKS: Dict[int, list] = {}
_LOG_EXECUTOR = logging.getLogger("paddle_tpu.executor")

# [seconds, collections of generation 0, 1, 2]: the collector's probe is
# their one writer, and collections do not overlap
_GC_TOTALS = [0.0, 0, 0, 0]
# [seconds tracing, backend compiles] as the jax.monitoring listener
# counts them
_JAX_TOTALS = [0.0, 0]
_JAX_TOTALS_LOCK = threading.Lock()

_STAGE_FIELD = {"exe:feed": "feed_s", "exe:lookup": "lookup_s",
                "exe:place": "place_s", "compiled_step": "dispatch_s",
                "exe:write_back": "write_back_s", "exe:fetch": "fetch_s"}
_STAGES = tuple(_STAGE_FIELD.values())


class StepRecord:
    """What one `Executor.run` left (the field table is in
    docs/OBSERVABILITY.md). Seconds are `time.perf_counter` differences;
    an absent stage reads 0."""

    __slots__ = ("seq", "block", "t0", "run_s") + _STAGES + (
        "since_prev_s", "cpu_s", "gc_s", "gc_gen", "jax_trace_s",
        "compiles", "feed_bytes", "placed")

    def __init__(self, seq: int, t0: float, since_prev_s: float):
        self.seq, self.t0, self.since_prev_s = seq, t0, since_prev_s
        self.run_s = self.feed_s = self.lookup_s = self.place_s = \
            self.dispatch_s = self.write_back_s = self.fetch_s = \
            self.cpu_s = self.gc_s = self.jax_trace_s = 0.0
        self.block = self.compiles = self.feed_bytes = self.placed = 0
        self.gc_gen = -1  # no collection ended in the interval

    def add(self, name: str, seconds: float, args) -> None:
        """A span that closed inside this run: a stage adds its seconds
        to its field. The window and segment spans are the dispatch of
        their paths; `window[K]:fallback` is not, it wraps whole runs
        that joined this record."""
        field = _STAGE_FIELD.get(name)
        if field is None:
            if not name.startswith(("window[", "segment[")) \
                    or name.endswith(":fallback"):
                return
            field = "dispatch_s"
        setattr(self, field, getattr(self, field) + seconds)
        if args:
            self.feed_bytes += args.get("bytes", 0)
            self.placed += args.get("placed", 0)

    @property
    def self_s(self) -> float:
        """`run_s` less the stages: the time between them."""
        return self.run_s - sum(getattr(self, f) for f in _STAGES)

    @property
    def period_s(self) -> float:
        """From the run before's return to this one's."""
        return self.since_prev_s + self.run_s

    def as_dict(self) -> Dict[str, Any]:
        out = {name: getattr(self, name) for name in self.__slots__}
        out["self_s"] = self.self_s
        return out


# every field a summary takes a median of: all but the three that name
# the record
_SUMMARY_FIELDS = StepRecord.__slots__[3:] + ("self_s",)


def open_step() -> Optional[StepRecord]:
    """The record of the `exe:run` span open on THIS thread, or None."""
    return getattr(_STEP_TLS, "open", None)


def step_block(owner) -> None:
    """Name the open record's block: a number `owner` (the compiled
    block, or the Program where there is none) keeps for its life, so
    that a reader tells the step from the start-up program and a new
    block never inherits another's median."""
    rec = open_step()
    if rec is not None:
        block = owner.__dict__.get("_step_block")
        if block is None:
            block = owner.__dict__["_step_block"] = next(_BLOCK_IDS)
        rec.block = block


class _Mark(NamedTuple):
    """What a thread and the process had counted when a run returned: the
    next run's record holds the differences."""
    end: float          # time.perf_counter()
    cpu: float          # time.thread_time()
    gc_s: float
    collections: tuple  # of generation 0, 1, 2
    jax_trace_s: float
    compiles: int


def _mark(end: float) -> _Mark:
    return _Mark(end, time.thread_time(), _GC_TOTALS[0],
                 tuple(_GC_TOTALS[1:]), *_JAX_TOTALS)


def begin_step() -> StepRecord:
    """Open this thread's record (`RecordEvent` does, entering an
    `exe:run` span where none is open)."""
    t0 = time.perf_counter()
    prev = getattr(_STEP_TLS, "prev", None)
    if prev is None:
        # the thread's first run: no caller's time before it
        prev = _STEP_TLS.prev = _mark(t0)
    rec = _STEP_TLS.open = StepRecord(next(_SEQ), t0, t0 - prev.end)
    return rec


def end_step(rec: StepRecord, end: float) -> None:
    """Close the record at `end`, the `exe:run` span's own end: what the
    thread and the process counted since the run before returned goes
    in, the record into the ring, and a slow one is flagged."""
    _STEP_TLS.open = None
    prev = _STEP_TLS.prev
    now = _STEP_TLS.prev = _mark(end)
    rec.run_s = end - rec.t0
    rec.cpu_s = now.cpu - prev.cpu
    rec.gc_s = now.gc_s - prev.gc_s
    rec.gc_gen = max((g for g in range(3)
                      if now.collections[g] > prev.collections[g]),
                     default=-1)
    rec.jax_trace_s = now.jax_trace_s - prev.jax_trace_s
    rec.compiles = now.compiles - prev.compiles
    period = rec.period_s
    with _STEPS_LOCK:
        STEPS.append(rec)
        stats = _BLOCKS.get(rec.block)
        if stats is None:
            stats = _BLOCKS[rec.block] = [None, []]
        typical, periods = stats
        periods.append(period)
        if len(periods) == (_MEDIAN_AFTER if typical is None
                            else _MEDIAN_EVERY):
            stats[0] = _median(periods)
            periods.clear()
    if typical is not None and period > SLOW_STEP_FACTOR * typical \
            and period - typical > SLOW_STEP_EXCESS_S:
        _flag_slow(rec)


def _flag_slow(rec: StepRecord) -> None:
    """Counted, kept, an `exe:slow_step` instant where spans are
    recorded, and ONE warning line with the record beside its block's
    medians: an untraced run still leaves the finding on stderr."""
    REGISTRY.counter(*_SLOW_STEPS_TOTAL).inc()
    with _STEPS_LOCK:
        SLOW_STEPS.append(rec)
    from . import profiler as _profiler
    _profiler.record_instant("exe:slow_step", cat="executor",
                             args=rec.as_dict())
    with _STEPS_LOCK:
        siblings = [r for r in STEPS if r.block == rec.block]
    block = _summarise(siblings)
    _LOG_EXECUTOR.warning(
        "slow step: %s; medians of block %s over %d records: %s",
        json.dumps(rec.as_dict()), rec.block, block["n"],
        json.dumps(block["median"]))


def _summarise(records: List[StepRecord]) -> Dict[str, Any]:
    """Median and nearest-rank 95th percentile of every field."""
    columns = {f: sorted(getattr(r, f) for r in records)
               for f in _SUMMARY_FIELDS}
    rank = max(0, -(-95 * len(records) // 100) - 1)  # ceil(0.95 n) - 1
    return {"n": len(records),
            "median": {f: _median(v) for f, v in columns.items()},
            "p95": {f: v[rank] for f, v in columns.items()}}


def step_summary() -> Dict[str, Any]:
    """Median and nearest-rank 95th percentile of every field over the
    ring, by block, and the slow records: what `flush_trace_shard`
    writes into the shard's metadata."""
    with _STEPS_LOCK:
        records, slow = list(STEPS), list(SLOW_STEPS)
    by_block: Dict[int, List[StepRecord]] = {}
    for r in records:
        by_block.setdefault(r.block, []).append(r)
    return {"blocks": {str(block): _summarise(rs)
                       for block, rs in by_block.items()},
            "slow": [r.as_dict() for r in slow]}


_GC_SECONDS = ("python_gc_seconds_total",
               "seconds the cyclic collector held the interpreter, by the "
               "generation it collected")
_GC_COLLECTIONS = ("python_gc_collections_total",
                   "collections of the cyclic collector, by generation")
_SLOW_STEPS_TOTAL = (
    "executor_slow_steps_total",
    "Executor.run calls whose period was far over their block's median "
    "(telemetry.SLOW_STEP_FACTOR, SLOW_STEP_EXCESS_S)")


def _install_gc_probe() -> None:
    """The `gc.callbacks` entry (installed with the jax.monitoring
    listeners, once a process): times each collection into the registry
    and the step record's totals, and holds a `TraceAnnotation
    ("gc:gen<N>")` over the pause, so that a profiler trace shows it on
    the device events' clock. The probe runs INSIDE the collector, at
    whatever allocation set it off: it takes no lock another frame of
    the thread may hold (the registry's children are found here, once;
    a child's own lock guards no allocation)."""
    from jax.profiler import TraceAnnotation
    names = tuple(f"gc:gen{g}" for g in range(3))
    seconds = [REGISTRY.counter(*_GC_SECONDS, labelnames=("generation",))
               .labels(generation=g) for g in range(3)]
    collections = [
        REGISTRY.counter(*_GC_COLLECTIONS, labelnames=("generation",))
        .labels(generation=g) for g in range(3)]
    REGISTRY.counter(*_SLOW_STEPS_TOTAL)  # a scrape before the first: 0
    pause = [0.0, None]  # the collection in progress: start, annotation

    def _on_gc(phase: str, info: dict) -> None:
        gen = info["generation"]
        if phase == "start":
            pause[1] = TraceAnnotation(names[gen])
            pause[1].__enter__()
            pause[0] = time.perf_counter()
        elif pause[1] is not None:
            took = time.perf_counter() - pause[0]
            pause[1].__exit__(None, None, None)
            pause[1] = None
            _GC_TOTALS[0] += took
            _GC_TOTALS[1 + gen] += 1
            seconds[gen].inc(took)
            collections[gen].inc()

    gc.callbacks.append(_on_gc)


# ---------------------------------------------------------------------------
# process identity + clock offsets (the timeline-merge substrate)
# ---------------------------------------------------------------------------
_PROCESS = {"role": None, "endpoint": None}
_PROCESS_LOCK = threading.Lock()

# endpoint -> (offset_s, rtt_s): offset = peer perf_counter - ours, the
# NTP-style estimate from the _hello handshake. Kept at MIN rtt (the
# tightest bound is the most accurate sample).
_OFFSETS: Dict[str, Tuple[float, float]] = {}
_OFFSETS_LOCK = threading.Lock()


def set_process_role(role: str, endpoint: Optional[str] = None,
                     override: bool = False) -> None:
    """Label this process for the trace shard metadata ('trainer0',
    'pserver', ...). First caller wins unless ``override`` — the
    PADDLE_TPU_TRACE_ROLE env (read at shard creation) beats both."""
    with _PROCESS_LOCK:
        if _PROCESS["role"] is None or override:
            _PROCESS["role"] = str(role)
        if endpoint is not None and (_PROCESS["endpoint"] is None
                                     or override):
            _PROCESS["endpoint"] = str(endpoint)


def process_role() -> Optional[str]:
    return os.environ.get("PADDLE_TPU_TRACE_ROLE") or _PROCESS["role"]


def note_clock_offset(endpoint: str, offset_s: float,
                      rtt_s: float) -> None:
    """Record a peer clock-offset sample from the _hello handshake:
    ``offset_s`` = peer's time.perf_counter() minus ours at the same
    instant (estimated at rtt/2)."""
    with _OFFSETS_LOCK:
        cur = _OFFSETS.get(endpoint)
        if cur is None or rtt_s <= cur[1]:
            _OFFSETS[endpoint] = (float(offset_s), float(rtt_s))


def clock_offsets() -> Dict[str, Tuple[float, float]]:
    with _OFFSETS_LOCK:
        return dict(_OFFSETS)


def reset_clock_offsets() -> None:
    with _OFFSETS_LOCK:
        _OFFSETS.clear()


# ---------------------------------------------------------------------------
# trace shard streaming (FLAGS_trace_dir)
# ---------------------------------------------------------------------------
class _ShardWriter:
    """Bounded ring buffer of chrome-trace events, flushed atomically to
    ``<trace_dir>/trace-<pid>.json``. Timestamps are RAW
    time.perf_counter microseconds (each process's own monotonic
    clock); the shard metadata carries a (wall, perf) anchor pair and
    the measured peer offsets so ``tools/timeline.py merge`` can
    clock-correct everything into one timeline."""

    _FLUSH_INTERVAL_S = 2.0

    def __init__(self, trace_dir: str):
        self.dir = trace_dir
        os.makedirs(trace_dir, exist_ok=True)
        self.path = os.path.join(trace_dir, f"trace-{os.getpid()}.json")
        max_events = max(
            1024, int(core.globals_["FLAGS_trace_shard_max_events"]))
        self._events: deque = deque(maxlen=max_events)
        self.dropped = 0
        self._lock = threading.Lock()
        # serializes snapshot+write+replace: two concurrent flushes
        # (atexit racing the background loop) would interleave writes
        # into the SAME .tmp inode and install a corrupt shard
        self._flush_lock = threading.Lock()
        self._since_flush = 0
        self._last_flush = time.perf_counter()
        # wall/perf anchor: maps this shard's raw perf timestamps onto
        # the wall clock — the merge fallback when no measured offset
        # links two shards (same-host shards share the wall clock)
        self._anchor_wall = time.time()
        self._anchor_perf = time.perf_counter()
        # a superseded writer (FLAGS_trace_dir re-pointed,
        # reset_trace_shard) is STOPPED: its flush thread exits and its
        # registered atexit flush becomes a no-op — atexit runs LIFO,
        # so a live old flush would overwrite the current writer's
        # shard with pre-reset events when the dir is reused
        self._stopped = False
        atexit.register(self.flush)
        # background flusher: a process that goes quiet (a pserver
        # parked in serve_forever) or dies hard (chaos SIGKILL) must
        # not lose its tail — the shard on disk stays at most
        # ~_FLUSH_INTERVAL_S stale regardless of record cadence
        t = threading.Thread(target=self._flush_loop,
                             name="telemetry-shard-flush", daemon=True)
        t.start()

    def _flush_loop(self):
        while not self._stopped:
            time.sleep(self._FLUSH_INTERVAL_S)
            if self._stopped:
                return
            with self._lock:
                dirty = self._since_flush > 0
            if dirty:
                self.flush()

    def stop(self) -> None:
        """Final flush, then deactivate (flush thread exits, the
        atexit hook no-ops)."""
        if not self._stopped:
            self.flush()
            self._stopped = True

    def record(self, name: str, start: float, end: float, tid: int,
               cat: str, args, trace: Optional[TraceContext]) -> None:
        ev = {"name": name, "ph": "X", "pid": os.getpid(), "tid": tid,
              "ts": start * 1e6, "dur": (end - start) * 1e6, "cat": cat}
        a = dict(args) if args else {}
        if trace is not None:
            a["trace_id"] = trace.trace_id
            a["span_id"] = trace.span_id
            if trace.parent_id:
                a["parent_id"] = trace.parent_id
        if a:
            ev["args"] = a
        # the recording (data-path) thread only appends and marks the
        # buffer dirty — the O(ring) JSON serialization always happens
        # on the background flusher (or an explicit flush), never as a
        # periodic stall inside an RPC handler or serving worker
        with self._lock:
            if len(self._events) == self._events.maxlen:
                self.dropped += 1
            self._events.append(ev)
            self._since_flush += 1

    def flush(self) -> None:
        if self._stopped:
            return
        with self._flush_lock:
            self._flush_locked()

    def _flush_locked(self) -> None:
        with self._lock:
            events = list(self._events)
            dropped = self.dropped
            self._since_flush = 0
            self._last_flush = time.perf_counter()
        meta = {
            "pid": os.getpid(),
            "role": process_role() or f"proc{os.getpid()}",
            "endpoint": _PROCESS["endpoint"],
            "clock": "perf_counter_us",
            "anchor_wall_us": self._anchor_wall * 1e6,
            "anchor_perf_us": self._anchor_perf * 1e6,
            "dropped_events": dropped,
            "step_summary": step_summary(),
            "peer_offsets": {
                ep: {"offset_us": off * 1e6, "rtt_us": rtt * 1e6}
                for ep, (off, rtt) in clock_offsets().items()},
        }
        tmp = self.path + ".tmp"
        try:
            with open(tmp, "w") as f:
                json.dump({"traceEvents": events,
                           "displayTimeUnit": "ms",
                           "metadata": meta}, f)
            os.replace(tmp, self.path)
        except OSError:
            _LOG.exception("trace shard flush to %s failed", self.path)


_SHARD: Optional[_ShardWriter] = None
_SHARD_LOCK = threading.Lock()


def shard_active() -> bool:
    """True when FLAGS_trace_dir streaming is on — the profiler records
    spans (into the shard) even without start_profiler(). Gated on the
    FLAG alone: clearing it turns the recording overhead off even
    after a writer existed."""
    return bool(core.globals_["FLAGS_trace_dir"])


def _shard() -> Optional[_ShardWriter]:
    global _SHARD
    d = core.globals_["FLAGS_trace_dir"]
    if not d and _SHARD is not None:
        # flag cleared at runtime: final-flush and retire the writer
        # (its flush thread exits; the atexit hook no-ops)
        with _SHARD_LOCK:
            if _SHARD is not None:
                _SHARD.stop()
                _SHARD = None
        return None
    if _SHARD is not None:
        # a test that re-points FLAGS_trace_dir gets a fresh writer
        if d and _SHARD.dir != d:
            with _SHARD_LOCK:
                if _SHARD is not None and _SHARD.dir != d:
                    _SHARD.stop()
                    _SHARD = _ShardWriter(d)
        return _SHARD if d else None
    if not d:
        return None
    with _SHARD_LOCK:
        if _SHARD is None:
            _SHARD = _ShardWriter(d)
    return _SHARD


def shard_record(name: str, start: float, end: float, tid: int,
                 cat: str, args, trace=None) -> None:
    w = _shard()
    if w is not None:
        w.record(name, start, end, tid, cat, args, trace)


def flush_trace_shard() -> Optional[str]:
    """Force-write the shard now; returns its path (None when off)."""
    w = _shard()
    if w is None:
        return None
    w.flush()
    return w.path


def reset_trace_shard() -> None:
    """Drop the writer (tests that re-point FLAGS_trace_dir)."""
    global _SHARD
    with _SHARD_LOCK:
        if _SHARD is not None:
            _SHARD.stop()
        _SHARD = None


# ---------------------------------------------------------------------------
# metrics sidecar server (FLAGS_metrics_port)
# ---------------------------------------------------------------------------
_METRICS_SRV = None
_METRICS_SRV_LOCK = threading.Lock()


def start_metrics_server(port: int = 0,
                         host: str = "127.0.0.1") -> Optional[int]:
    """Start the process's lightweight /metrics HTTP sidecar (idempotent
    — the first successful start wins; returns its bound port). Serves
    ``GET /metrics`` (Prometheus text) and ``GET /healthz``. Returns
    None when the port cannot be bound (another process on a shared
    box already owns it — logged, never fatal: observability must not
    take a pserver down)."""
    global _METRICS_SRV
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    with _METRICS_SRV_LOCK:
        if _METRICS_SRV is not None:
            return _METRICS_SRV.server_address[1]

        class _Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):  # stay off stderr
                _LOG.debug("metrics %s " + fmt,
                           self.client_address[0], *args)

            def do_GET(self):
                if self.path == "/metrics":
                    body = REGISTRY.exposition().encode("utf-8")
                    ctype = "text/plain; version=0.0.4; charset=utf-8"
                elif self.path == "/healthz":
                    body = b'{"status": "ok"}'
                    ctype = "application/json"
                else:
                    self.send_response(404)
                    self.send_header("Content-Length", "0")
                    self.end_headers()
                    return
                self.send_response(200)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        try:
            srv = ThreadingHTTPServer((host, int(port)), _Handler)
        except OSError as e:
            _LOG.warning("metrics server: cannot bind %s:%s (%r) — "
                         "metrics stay scrape-able via stats()/ingress",
                         host, port, e)
            return None
        srv.daemon_threads = True
        threading.Thread(target=srv.serve_forever,
                         name="telemetry-metrics", daemon=True).start()
        _METRICS_SRV = srv
        return srv.server_address[1]


def maybe_start_metrics_server() -> Optional[int]:
    """Start the sidecar iff FLAGS_metrics_port > 0 (the opt-in hook
    pservers/trainers/ingresses call at startup). Idempotent."""
    port = int(core.globals_["FLAGS_metrics_port"])
    if port <= 0:
        return None
    return start_metrics_server(port)


def metrics_server_port() -> Optional[int]:
    srv = _METRICS_SRV
    return None if srv is None else srv.server_address[1]


def stop_metrics_server() -> None:
    global _METRICS_SRV
    with _METRICS_SRV_LOCK:
        srv, _METRICS_SRV = _METRICS_SRV, None
    if srv is not None:
        srv.shutdown()
        srv.server_close()
