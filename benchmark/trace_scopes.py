"""From a profiler trace to what the PROGRAM says about a step: device
time by the Fluid-op scopes the executor traces every op under
(`fwd/<op>`, `bwd/<op>_grad`, `opt/<optimizer op>`), the Pallas kernels by
the names they carry, the stage spans inside `Executor.run`
(`exe:feed`, `exe:lookup`, `exe:place`, `compiled_step`, `exe:write_back`,
`exe:fetch`) and every idle gap of the device booked to the innermost
span that covered it, or to `h2d` where it waited for an upload.

Beside `trace_reduce.py`, whose `union` and gap helpers it loads by path
and whose window, busy time and idle total it reproduces: `read` opens
the file with nothing but JAX and returns plain tuples, `reduce` is
arithmetic on those tuples and is checked on a hand-made trace
(tests/benchmark). Look at a trace by hand before trusting a number read
from it; this prints every plane and line with its first events AND
their stats:

    python benchmark/trace_scopes.py <dir or .xplane.pb>

How a device event gets its scope. On the v5e an event of the `XLA Ops`
line is named by the text of its HLO instruction and carries no
`op_name` (read in PR 24's traces: its stats are `device_offset_ps`,
`device_duration_ps`, `Time Scale Multiplier`; `trace_options()` sets
`enable_hlo_proto = False`). The compiled module does: every
instruction, fusions included, has `metadata={op_name="jit(_step)/
<phase>/<op type>/..."}`, a fusion that of its root. So the map
instruction name -> op_name is read from the text of the executables the
process has loaded, module by module; an event finds its module by the
`XLA Modules` event that contains it, and that event its loaded module
by the instructions the two share. An executable that JAX loaded from
its persistent cache carries the metadata it was COMPILED with (the
cache's key leaves metadata out): an entry written by an older program
shows that program's scopes, or none.

How the harness gets the result. `run.py` reduces its trace with
`trace_reduce.py` and deletes it; it has no line that calls this file,
and a PR of this kind may not add one. The per-layer readers that need
this reduction call `watch()` when the harness loads them (before it
starts JAX): while the running script is the `run.py` beside this file,
`jax.profiler.stop_trace` is followed by one `reduce` of the trace just
written, kept for `last()` and printed as a `{"phase": "scopes"}` line.
Anywhere else (the tests, an import) `watch()` does nothing.
"""
import bisect
import importlib.util
import json
import os
import re
import sys
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))


def _beside(name):
    spec = importlib.util.spec_from_file_location(
        "_benchmark_" + name, os.path.join(HERE, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_tr = _beside("trace_reduce")
_median = _beside("stats").median

# the benchmark's own spans (run.py SPLIT_SPANS): they bound the window
WINDOW_SPANS = ("feed", "exe.run", "fetch")
# the program's spans inside `Executor.run` (fluid/executor.py,
# cat="executor"), in the order a step passes them
STAGE_SPANS = ("exe:feed", "exe:lookup", "exe:place", "compiled_step",
               "exe:write_back", "exe:fetch")
PHASES = ("fwd", "bwd", "opt")
UNSCOPED = "unscoped"
H2D = "h2d"
MODULES_LINE = "XLA Modules"
KERNEL = 'custom_call_target="tpu_custom_call"'  # a Pallas (Mosaic) kernel
# The runtime's own host events of one host-to-device transfer, as the
# v5e's PJRT client names them (read in PR 24's trace of
# resnet50.b256_i224, chiprun_out/fc, 154 MB a step): `XlaLinearize`
# re-tiles the host array on the runtime's threads (9 ms, `Linearize`
# and the `Transpose*` events nest inside it), then
# `tpu::System::TransferToDevice` issues the DMA (stat `size`, bytes) and
# an event of the second name, with the same `size`, marks its end 11 ms
# later. An upload is in flight from the first's start to the last's end.
LINEARIZE = "XlaLinearize"
TRANSFER_ISSUE = "tpu::System::TransferToDevice"
TRANSFER_DONE = "tpu::System::TransferToDevice=>IssueEvent=>Done"
# the host sees a DMA's end a little after the device has started on
# the data (0.3 ms in that trace): a gap "ends when the upload ends" if
# the upload's end is no earlier than this before the gap's end
H2D_SLACK_S = 0.5e-3
# one linearized array is issued at once: the `XlaLinearize` that ended
# within this of an issue is the same upload
H2D_JOIN_S = 1e-3

_SCOPE = re.compile(r"(?:^|[/(])(fwd|bwd|opt)/([A-Za-z0-9_.\-]+)")
_KERNEL = re.compile(r"([A-Za-z0-9_.\-]+)\)*/pallas_call$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([^\s=]+) = ")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([^\s(]+) \(.*\{$")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([^\s,)]+)")


def instruction_name(text):
    """`%fusion.38 = (f32[64]...) fusion(...)` -> `fusion.38`."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def scope_of(op_name):
    """`jit(_step)/bwd/mul_grad/transpose(jvp())/dot_general` ->
    ("bwd", "bwd/mul_grad"); (None, None) where no phase is in it. A
    transform that wrapped the scope (`jvp(fwd/flash_attn)`) is looked
    into."""
    found = _SCOPE.search(op_name or "")
    if not found:
        return None, None
    return found.group(1), found.group(1) + "/" + found.group(2)


def kernel_of(op_name, instruction):
    """A Pallas kernel's own name: what stands before `/pallas_call` in
    its op_name, out of the transforms that wrapped it
    (`.../jvp(flash_fwd)/pallas_call` -> `flash_fwd`: the instruction is
    `%jvp_flash_fwd_.7` there); the instruction's name without its
    number where there is no such op_name."""
    found = _KERNEL.search(op_name or "")
    if found:
        return found.group(1)
    stem, dot, number = instruction.rpartition(".")
    return stem if dot and number.isdigit() else instruction


def op_names_of(hlo_text):
    """(names, mixed) of one module's text. `names` has EVERY
    instruction, {instruction name: op_name, "" where it has none};
    `mixed` names the fusions whose fused computation holds
    instructions of more than one phase, {instruction name: "bwd+opt"}:
    XLA may merge an optimizer update into the fusion that produces the
    gradient, and the fusion has one op_name."""
    names, inside, calls = {}, {}, {}
    computation = None
    for line in hlo_text.splitlines():
        found = _INSTRUCTION.match(line)
        if found:
            op_name = _OP_NAME.search(line)
            names[found.group(1)] = op_name.group(1) if op_name else ""
            phase = scope_of(names[found.group(1)])[0]
            if phase and computation:
                inside.setdefault(computation, set()).add(phase)
            called = _CALLS.search(line) if " fusion(" in line else None
            if called:
                calls[found.group(1)] = called.group(1)
            continue
        found = _COMPUTATION.match(line)
        if found:
            computation = found.group(1)
    mixed = {name: "+".join(sorted(inside[c])) for name, c in calls.items()
             if len(inside.get(c, ())) > 1}
    return names, mixed


def loaded_modules(client):
    """[(module name, names, mixed)] of every executable the client has
    loaded (`op_names_of` its text)."""
    return [(module.name, *op_names_of(module.to_string()))
            for executable in client.live_executables()
            for module in executable.hlo_modules()]


def match_modules(device, loaded):
    """{module event name: (names, mixed)}: for each module that ran in
    the trace (`jit__step(1631...)`) the loaded module of that name that
    holds most of the instructions seen under it. The name alone does
    not do: the startup program's jitted step is a `jit__step` too."""
    seen = {}
    for events in device.values():
        for _, _, text, module in events:
            seen.setdefault(module, set()).add(instruction_name(text))
    out = {}
    for module, instructions in seen.items():
        best = max((m for m in loaded
                    if m[0] == module.split("(", 1)[0]),
                   key=lambda m: len(instructions & m[1].keys()),
                   default=None)
        if best:
            out[module] = best[1:]
    return out


def _seconds(e):
    return e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9


def read(path):
    """(device, host, uploads). `device` maps each chip's plane to its
    operation events `(start_s, end_s, instruction text, module)`, the
    module the name of the `XLA Modules` event that contains the
    operation's start (`jit__step(1631...)`); `host` lists the
    benchmark's and the program's spans `(start_s, end_s, name)`;
    `uploads` the runtime's transfers `(start_s, end_s, bytes)`. One
    clock."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(_tr.find_xplane(path))
    wanted = set(WINDOW_SPANS) | set(STAGE_SPANS)
    device, host = {}, []
    linearize, issued, done = [], [], []
    for plane in data.planes:
        if plane.name.startswith(_tr.DEVICE_PLANE_PREFIX):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == _tr.DEVICE_OPS_LINE:
                    ops.extend((*_seconds(e), e.name) for e in line.events)
                elif line.name == MODULES_LINE:
                    modules.extend((*_seconds(e), e.name)
                                   for e in line.events)
            modules.sort()
            starts = [m[0] for m in modules]
            events = []
            for s, e, text in ops:
                i = bisect.bisect_right(starts, s) - 1
                inside = i >= 0 and s < modules[i][1]
                events.append((s, e, text, modules[i][2] if inside else ""))
            device.setdefault(plane.name, []).extend(events)
        elif plane.name.startswith(_tr.HOST_PLANE_PREFIX):
            for line in plane.lines:
                for e in line.events:
                    if e.name in wanted:
                        host.append((*_seconds(e), e.name))
                    elif e.name == LINEARIZE:
                        linearize.append(_seconds(e))
                    elif e.name in (TRANSFER_ISSUE, TRANSFER_DONE):
                        size = int(dict(e.stats).get("size", 0) or 0)
                        (issued if e.name == TRANSFER_ISSUE else done).append(
                            (*_seconds(e), size))
    return device, host, pair_uploads(linearize, issued, done)


def pair_uploads(linearize, issued, done):
    """One `(start_s, end_s, bytes)` for each transfer issued: from its
    issue, or from the start of the `XlaLinearize` that ended right
    before it, to the end of the first later `Done` event of the same
    size; a transfer whose end the trace does not hold is left out."""
    done = sorted(done)
    taken = [False] * len(done)
    ends = sorted((e, s) for s, e in linearize)
    out = []
    for s, e, size in sorted(issued):
        for i, (ds, de, dsize) in enumerate(done):
            if not taken[i] and dsize == size and de >= s:
                taken[i] = True
                start = s
                j = bisect.bisect_right(ends, (s + 1e-9, float("inf"))) - 1
                if j >= 0 and s - ends[j][0] <= H2D_JOIN_S:
                    start = min(start, ends[j][1])
                out.append((start, de, size))
                break
    return out


def _innermost(spans, at):
    """Name of the span that covers `at` and started last."""
    best = None
    for s, e, name in spans:
        if s <= at < e and (best is None or s >= best[0]):
            best = (s, name)
    return best[1] if best else _tr.NO_SPAN


def _segments(spans):
    """(cuts, names): time cut at every span boundary, and the innermost
    span over each piece between two cuts."""
    cuts = sorted({t for s, e, _ in spans for t in (s, e)})
    return cuts, [_innermost(spans, (a + b) / 2)
                  for a, b in zip(cuts, cuts[1:])]


def _book_gap(g0, g1, segments, uploads, out):
    """Share the idle gap [g0, g1) out: what an upload covers goes to
    `h2d` if the gap ends when that upload ends (the device waited for
    the data); every other piece to the innermost span over it."""
    cuts, names = segments
    waited = [(s, e) for s, e, _ in uploads
              if s < g1 and e > g0 and e >= g1 - H2D_SLACK_S]
    h2d = _tr._clip(_tr.union(waited), g0, g1)
    for s, e in h2d:
        out[H2D] = out.get(H2D, 0.0) + (e - s)
    for p0, p1 in _tr._gaps(h2d, g0, g1):
        i = bisect.bisect_right(cuts, p0) - 1
        at = p0
        while at < p1:
            inside = 0 <= i < len(names)
            until = min(p1, cuts[i + 1]) if i + 1 < len(cuts) else p1
            name = names[i] if inside else _tr.NO_SPAN
            out[name] = out.get(name, 0.0) + (until - at)
            at, i = until, i + 1


def _uncovered(runs, stages):
    """Median milliseconds of each `exe.run` span that no program span
    covers, by where: before the next program span, or after the last."""
    spans = sorted((s, e, n) for n, iv in stages.items() for s, e in iv)
    parts = {}
    for r0, r1 in runs:
        at, mine = r0, {}
        for s, e, n in spans:
            if r0 <= s and e <= r1:
                mine["before " + n] = mine.get("before " + n, 0.0) \
                    + max(0.0, s - at)
                at, last = max(at, e), n
        if at > r0:
            mine["after " + last] = r1 - at
        for k, v in mine.items():
            parts.setdefault(k, []).append(v)
    return {k: _median(v) * 1e3 for k, v in parts.items()}


def reduce(device, host, uploads, modules, top=10):
    """What the trace says, per traced window (`steps` is the number of
    the benchmark's `exe.run` spans in it: divide by it for a step), or
    None where the trace holds none of the benchmark's spans. `modules`
    is `match_modules`' result: for each module that ran, the op_name of
    every instruction and its mixed fusions.

    The window, the busy union and the idle total are `trace_reduce`'s:
    first benchmark span's start to the last one's end, operations
    clipped to it, everything averaged over the chips' planes. With no
    chip's plane `chips` is 0 and only the host's part is there.

    `phase_s`: seconds of operations under `fwd`, `bwd`, `opt` and
    `unscoped` (no phase in the instruction's op_name, or no op_name:
    what XLA inserted itself); they sum to `ops_total_s`, the sum of
    the operations' durations. `mixed_s`: of those seconds, the ones in
    fusions that hold instructions of several phases, by which
    (`bwd+opt`: booked to one of the two, it is both). `device_scopes`:
    the `top` of `<phase>/<op type>`. `unscoped_ops`: the `top` unscoped
    operations by `trace_reduce.short_name`. `device_ops_scopes`:
    `trace_reduce`'s `device_ops` (XLA's names), each with the three
    scopes that hold most of it: whose `%pad` it is. `kernels`: seconds and
    calls of each Pallas kernel by its own name (`flash_fwd`, ...), and
    its seconds by phase. `unknown_instructions`: operations whose
    instruction no loaded module has (then the map is not this
    executable's). `stage_ms`: median milliseconds of each program span;
    `stage_cover`: the share of the benchmark's `exe.run` spans that
    program spans cover, `uncovered_ms` where the rest is.
    `idle_by_stage`: every idle gap booked by `_book_gap`; sums to
    `idle_s`."""
    bench = [(s, e, n) for s, e, n in host if n in WINDOW_SPANS]
    if not bench:
        return None
    lo, hi = min(s for s, _, _ in bench), max(e for _, e, _ in bench)
    runs = sorted((s, e) for s, e, n in bench if n == "exe.run")
    stages = {}
    for s, e, n in host:
        if n in STAGE_SPANS and s >= lo and e <= hi:
            stages.setdefault(n, []).append((s, e))
    covered = _tr.union(iv for spans in stages.values() for iv in spans)
    in_runs = sum(e - s for r0, r1 in runs
                  for s, e in _tr._clip(covered, r0, r1))
    result = {
        "chips": 0, "steps": len(runs), "window_s": hi - lo,
        "stage_ms": {n: _median([e - s for s, e in stages[n]]) * 1e3
                     for n in STAGE_SPANS if n in stages},
        "stage_calls": {n: len(stages[n]) for n in STAGE_SPANS
                        if n in stages},
        "stage_cover": (in_runs / sum(e - s for s, e in runs)
                        if runs else None),
        "uncovered_ms": _uncovered(runs, stages),
        "uploads": len([u for u in uploads if lo <= u[0] < hi]),
        "upload_bytes": sum(u[2] for u in uploads if lo <= u[0] < hi),
    }
    device = {plane: ev for plane, ev in device.items() if ev}
    if device:
        result.update(_device_part(device, lo, hi, host, uploads, modules,
                                   top))
    return result


def _device_part(device, lo, hi, host, uploads, modules, top):
    """`reduce`'s numbers of the chips' planes, over the window
    [lo, hi)."""
    phase_s = dict.fromkeys(PHASES + (UNSCOPED,), 0.0)
    scopes, unscoped, kernels, mixed_s, idle, ops = {}, {}, {}, {}, {}, {}
    busy_s = total_s = 0.0
    unknown = 0
    segments = _segments(host)
    for events in device.values():
        inside = [(max(s, lo), min(e, hi), text, module)
                  for s, e, text, module in events
                  if min(e, hi) > max(s, lo)]
        busy = _tr.union((s, e) for s, e, _, _ in inside)
        busy_s += sum(e - s for s, e in busy)
        for s, e, text, module in inside:
            name = instruction_name(text)
            names, mixed = modules.get(module, ({}, {}))
            unknown += name not in names
            op_name = names.get(name)
            phase, scope = scope_of(op_name)
            total_s += e - s
            short = _tr.short_name(text)
            if phase is None:
                phase = scope = UNSCOPED
                unscoped[short] = unscoped.get(short, 0.0) + (e - s)
            else:
                scopes[scope] = scopes.get(scope, 0.0) + (e - s)
            phase_s[phase] += e - s
            inside_op = ops.setdefault(short, {})
            inside_op[scope] = inside_op.get(scope, 0.0) + (e - s)
            if name in mixed:
                mixed_s[mixed[name]] = mixed_s.get(mixed[name], 0.0) \
                    + (e - s)
            if KERNEL in text:
                k = kernels.setdefault(kernel_of(op_name, name),
                                       {"s": 0.0, "calls": 0})
                k["s"] += e - s
                k["calls"] += 1
                k[phase + "_s"] = k.get(phase + "_s", 0.0) + (e - s)
        for g0, g1 in _tr._gaps(busy, lo, hi):
            _book_gap(g0, g1, segments, uploads, idle)
    n = len(device)

    def mean(totals):
        return {k: v / n for k, v in totals.items()}

    return {
        "chips": n, "busy_s": busy_s / n, "idle_s": (hi - lo) - busy_s / n,
        "ops_total_s": total_s / n,
        "phase_s": mean(phase_s),
        "mixed_s": mean(dict(sorted(mixed_s.items()))),
        "device_scopes": _tr._top(mean(scopes), top),
        "unscoped_ops": _tr._top(mean(unscoped), top),
        "device_ops_scopes": [
            [short, seconds, _tr._top(mean(ops[short]), 3)]
            for short, seconds in _tr._top(
                {k: sum(v.values()) / n for k, v in ops.items()}, top)],
        "kernels": {name: mean(kernel)
                    for name, kernel in sorted(kernels.items())},
        "idle_by_stage": _tr._top(mean(idle), len(idle)),
        "unknown_instructions": unknown,
    }


# ------------------------------------------------- under the harness
_state = {"dir": None, "last": None, "watching": False}


def last():
    """The reduction of the trace the harness took, or None."""
    return _state["last"]


def _reduce_now(trace_dir):
    import jax
    device, host, uploads = read(trace_dir)
    loaded = loaded_modules(jax.devices()[0].client) if device else []
    return reduce(device, host, uploads, match_modules(device, loaded))


def watch():
    """Under the `run.py` beside this file, and only there: follow
    `jax.profiler.stop_trace` by one `reduce` of the trace it wrote
    (the harness deletes it right after its own reduction). A failure
    here must not fail the traced run: it is printed, and every reader
    then finds nothing."""
    script = os.path.abspath(sys.argv[0]) if sys.argv and sys.argv[0] else ""
    if _state["watching"] or script != os.path.join(HERE, "run.py"):
        return
    _state["watching"] = True
    import jax.profiler
    start, stop = jax.profiler.start_trace, jax.profiler.stop_trace

    def start_trace(log_dir, *args, **kwargs):
        _state["dir"] = log_dir
        return start(log_dir, *args, **kwargs)

    def stop_trace():
        stop()
        row = {"phase": "scopes", "scopes": None}
        try:
            _state["last"] = row["scopes"] = _reduce_now(_state["dir"])
        except Exception:  # the boundary: see the docstring
            row["error"] = traceback.format_exc(limit=8)
        print(json.dumps(row), flush=True)

    jax.profiler.start_trace, jax.profiler.stop_trace = start_trace, stop_trace


# ------------------------------------------------------------ by hand
def describe(path, events_per_line=4):
    """What the trace holds: each plane and its stats, each line, its
    first events with their stats."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(_tr.find_xplane(path))
    out = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = list(line.events)
            lines.append({
                "line": line.name, "events": len(events),
                "first": [{"name": e.name[:200], "start_ns": e.start_ns,
                           "duration_ns": e.duration_ns,
                           "stats": {k: str(v)[:120] for k, v in e.stats}}
                          for e in events[:events_per_line]]})
        out.append({"plane": plane.name,
                    "stats": {k: str(v)[:120] for k, v in plane.stats},
                    "lines": lines})
    return out


if __name__ == "__main__":
    json.dump(describe(sys.argv[1]), sys.stdout, indent=1)
    print()
