"""From a profiler trace (`.xplane.pb`) to the numbers the benchmark
reports: seconds the device was busy, the traced window, the device
operations that took most time, and the idle gaps by what the host was
doing in them.

`read` opens the file with nothing but JAX (`jax.profiler.ProfileData`)
and returns plain tuples; `reduce` is arithmetic on those tuples, so it
is checked on a hand-made trace (tests/benchmark). Run as a script it
prints what a trace holds, plane by plane — look at one by hand before
trusting a number read from it:

    python benchmark/trace_reduce.py <dir or .xplane.pb>
"""
import glob
import json
import os
import sys

# A chip's plane in the trace, and the line of it that holds one event
# per executed HLO operation (fusions, custom calls, copies). The other
# lines of the plane ("XLA Modules", "Steps", ...) span whole programs
# and would count every gap inside a program as busy.
DEVICE_PLANE_PREFIX = "/device:TPU:"
DEVICE_OPS_LINE = "XLA Ops"
HOST_PLANE_PREFIX = "/host:"
NO_SPAN = "(no span)"


def short_name(text):
    """A device event's name is the whole HLO instruction; keep what
    names it, without the number that tells one layer's copy from the
    next: `%jvp__.21 = ... custom_call_target="tpu_custom_call"` becomes
    `%jvp__ [tpu_custom_call]` (a Pallas kernel, all layers together),
    `%divide_subtract_fusion.1` becomes `%divide_subtract_fusion`, and
    the fusions XLA gave no name of their own (`%fusion.38`: where the
    matmuls and convolutions are) stand together as `%fusion`."""
    name = text.split(" = ", 1)[0].strip()
    stem, dot, number = name.rpartition(".")
    if dot and number.isdigit():
        name = stem
    marker = 'custom_call_target="'
    if marker in text:
        name += " [" + text.split(marker, 1)[1].split('"', 1)[0] + "]"
    return name


def find_xplane(path):
    """`path` itself, or the newest `.xplane.pb` under the directory."""
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def read(path, span_names):
    """(device, host): `device` maps each chip's plane to its operation
    events, `host` lists the host events whose name is in `span_names`
    (the benchmark's own `TraceAnnotation`s); every event is
    `(start_s, end_s, name)` on the trace's one clock."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(find_xplane(path))
    device, host = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == DEVICE_OPS_LINE:
                    device.setdefault(plane.name, []).extend(
                        (e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9,
                         short_name(e.name))
                        for e in line.events)
        elif plane.name.startswith(HOST_PLANE_PREFIX):
            for line in plane.lines:
                host.extend(
                    (e.start_ns * 1e-9,
                     (e.start_ns + e.duration_ns) * 1e-9, e.name)
                    for e in line.events if e.name in span_names)
    return device, host


def union(intervals):
    """Disjoint, sorted `(start, end)` covering the same points."""
    merged = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def _gaps(busy, lo, hi):
    out, at = [], lo
    for start, end in busy:
        if start > at:
            out.append((at, start))
        at = max(at, end)
    if hi > at:
        out.append((at, hi))
    return out


def _top(totals, n):
    return [[name, seconds] for name, seconds in
            sorted(totals.items(), key=lambda kv: (-kv[1], kv[0]))[:n]]


def reduce(device, host, steps, top=10):
    """The trace's numbers, or None where no chip's plane holds an event.

    The window runs from the first host span's start to the last one's
    end (the traced steps, not the profiler's own start and stop); with
    no host span, from the first device event to the last. Busy is the
    union of the operation intervals inside the window, idle is the
    window less that; both are averaged over the chips' planes, and so
    are the lists. An idle gap is shared out among the host spans that
    cover it, by the seconds of overlap; what no span covers goes to
    `(no span)`."""
    device = {plane: events for plane, events in device.items() if events}
    if not device:
        return None
    if host:
        lo, hi = min(s for s, _, _ in host), max(e for _, e, _ in host)
    else:
        lo = min(s for ev in device.values() for s, _, _ in ev)
        hi = max(e for ev in device.values() for _, e, _ in ev)
    spans = {}
    for s, e, name in host:
        spans.setdefault(name, []).append((s, e))
    spans = {name: union(iv) for name, iv in spans.items()}

    busy_s, ops, gaps = 0.0, {}, {}
    for events in device.values():
        inside = [(max(s, lo), min(e, hi), name) for s, e, name in events
                  if min(e, hi) > max(s, lo)]
        busy = union((s, e) for s, e, _ in inside)
        busy_s += sum(e - s for s, e in busy)
        for s, e, name in inside:
            ops[name] = ops.get(name, 0.0) + (e - s)
        for g0, g1 in _gaps(busy, lo, hi):
            covered = 0.0
            for name, iv in spans.items():
                part = sum(e - s for s, e in _clip(iv, g0, g1))
                if part:
                    gaps[name] = gaps.get(name, 0.0) + part
                    covered += part
            if (g1 - g0) - covered > 1e-12:
                gaps[NO_SPAN] = gaps.get(NO_SPAN, 0.0) + (g1 - g0) - covered
    n = len(device)
    busy_s /= n
    return {
        "chips": n,
        "steps": steps,
        "window_s": hi - lo,
        "busy_s": busy_s,
        "idle_s": (hi - lo) - busy_s,
        "device_ops": _top({k: v / n for k, v in ops.items()}, top),
        "idle_gaps": _top({k: v / n for k, v in gaps.items()}, top),
    }


def describe(path, events_per_line=6):
    """What the trace holds: each plane, each line, its first events."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(find_xplane(path))
    out = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = list(line.events)
            lines.append({
                "line": line.name, "events": len(events),
                "first": [[e.name[:80], e.start_ns, e.duration_ns]
                          for e in events[:events_per_line]]})
        out.append({"plane": plane.name, "lines": lines})
    return out


if __name__ == "__main__":
    json.dump(describe(sys.argv[1]), sys.stdout, indent=1)
    print()
