"""Device time of chosen Fluid-op scopes as the UNION of their device
intervals, for programs whose scopes nest on the device.

`trace_scopes.reduce` sums operation durations and keeps the ten largest
scopes. A sum counts the body of a `while` twice (the `while` event
spans its body's events; PERF.md §3, limit 4), and a scope outside the
ten is not in `last()`. A decoder whose delta rule scans its chunks in a
`while` needs neither: this file follows `jax.profiler.stop_trace` under
the harness exactly as `trace_scopes.watch()` does, reads the same trace
with `trace_scopes.read` / `match_modules` / `scope_of`, keeps every
operation's `(start, end, scope, instruction)` inside the benchmark's
window, and `ms_per_step(op_types, instructions)` is the union of the
intervals whose scope's op type is one of `op_types` (forward op or its
`_grad`) or whose instruction's name starts with one of `instructions`,
averaged over the chips' planes, a traced step. The second form is for
what XLA names itself: it expands a grouped product into kernels whose
op_name is `ragged-dot-none`, with no Fluid scope left in it.

`roofline_pct(run, required, ms)`: the least time the chip could take
for `required` ({"flop", "bytes"}, a step), the larger of flop over the
bf16 peak and bytes over the HBM peak, as a share of `ms`.
`cell_files()`: the configuration and traffic of the cell the harness
was started for, read from its command line.
"""
import importlib.util
import json
import os
import sys
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def shared():
    """`benchmark/trace_scopes.py`, the one instance the readers share."""
    name = "_benchmark_trace_scopes"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(HERE, "trace_scopes.py"))
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


def intervals(device, host, modules):
    """{"steps", "planes": [[(start_s, end_s, scope or None, instruction
    name)]]} of the window the benchmark's spans bound, or None without
    them: every operation clipped to the window with the `<phase>/<op
    type>` scope of its instruction's op_name."""
    ts = shared()
    bench = [(s, e, n) for s, e, n in host if n in ts.WINDOW_SPANS]
    if not bench:
        return None
    lo, hi = min(s for s, _, _ in bench), max(e for _, e, _ in bench)
    planes = []
    for events in device.values():
        plane = []
        for s, e, text, module in events:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                names, _ = modules.get(module, ({}, {}))
                name = ts.instruction_name(text)
                plane.append((s, e, ts.scope_of(names.get(name))[1], name))
        if plane:
            planes.append(plane)
    return {"steps": sum(n == "exe.run" for _, _, n in bench),
            "planes": planes}


def union_ms_per_step(found, op_types, instructions=()):
    """Milliseconds a step under the scopes `fwd|bwd|opt/<t>` and
    `.../<t>_grad`, t in `op_types`, and in the operations whose
    instruction's name starts with one of `instructions`: the union of
    their intervals (an operation nested in another of them counts
    once), mean of the planes; None where there is no chip's plane or
    no step."""
    if not found or not found["planes"] or not found["steps"]:
        return None
    union = shared()._tr.union
    wanted = {t + suffix for t in op_types for suffix in ("", "_grad")}
    total = 0.0
    for plane in found["planes"]:
        total += sum(e - s for s, e in union(
            (s, e) for s, e, scope, name in plane
            if (scope and scope.split("/", 1)[1] in wanted)
            or name.startswith(tuple(instructions))))
    return total / len(found["planes"]) / found["steps"] * 1e3


# ------------------------------------------------- under the harness
_state = {"dir": None, "last": None, "watching": False}


def last():
    return _state["last"]


def ms_per_step(op_types, instructions=()):
    """`union_ms_per_step` of the trace the harness took; None where
    nothing ran under those scopes (a program without them)."""
    return union_ms_per_step(_state["last"], op_types, instructions) or None


def watch():
    """Under the `run.py` beside this file, and only there: after
    `jax.profiler.stop_trace` (and `trace_scopes`' own reduction),
    keep the intervals of the trace just written; the harness deletes
    it right after. A failure here is printed and every reader then
    finds nothing: it must not fail the traced run."""
    ts = shared()
    ts.watch()
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
        try:
            import jax
            device, host, _ = ts.read(_state["dir"])
            loaded = ts.loaded_modules(jax.devices()[0].client) \
                if device else []
            _state["last"] = intervals(device, host,
                                       ts.match_modules(device, loaded))
        except Exception:  # the boundary: see the docstring
            print(json.dumps({"phase": "scope_union",
                              "error": traceback.format_exc(limit=8)}),
                  flush=True)

    jax.profiler.start_trace, jax.profiler.stop_trace = start_trace, stop_trace


def cell_files():
    """(config, traffic, the configuration's module) of the cell named
    after `--workload` on the harness's command line."""
    cell = sys.argv[sys.argv.index("--workload") + 1]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = {c["name"]: c for c in manifest["configs"]}[
        {w["name"]: w for w in manifest["workloads"]}[cell]["config"]]
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "workloads", cell + ".json")) as f:
        traffic = json.load(f)["traffic"]
    path = os.path.join(ROOT, os.path.splitext(entry["file"])[0] + ".py")
    spec = importlib.util.spec_from_file_location("_benchmark_cell_config",
                                                  path)
    model = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(model)
    return config, traffic, model


def roofline_pct(run, required, ms):
    """100 x (the larger of required flop / the bf16 peak and required
    bytes / the HBM peak) / `ms`: a step's share of the chip's roofline;
    None without a reading."""
    if not ms:
        return None
    least_s = max(
        required["flop"] / run.peak(run.device_kind, "bf16_flops_per_s"),
        required["bytes"] / run.peak(run.device_kind, "hbm_bytes_per_s"))
    return 100.0 * least_s / run.chips / (ms * 1e-3)
