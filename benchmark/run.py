#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json, on the machine it is started on.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1> [--tiny]

What is timed is the loop every Fluid example writes, and nothing
shorter: `loss, = exe.run(main, feed=batch, fetch_list=[loss])`, one call
a step, a different host batch in every step, a numpy loss out (which
ends the device's work). Set-up builds the program, runs the startup
program, makes a pool of host batches from the seed and runs the untimed
warm-up steps; then the window runs for `--seconds`.

Everything that belongs to one cell, one configuration or one metric is
a file found by the name BENCHMARK.json gives it, and this file holds
none of those names:

    workloads/<cell>.json       traffic (sizes, pool), mesh
    configs/<config>.json       the configuration's sizes, flags, checks
    configs/<config>.py         build, make_batches, flops_per_sample, tiny
    end_to_end/<metric>.py      compute(run) -> number
    layer_metrics/<metric>.py   compute(run) -> number, or None (left out)

`--trace 0` prints the cell's end-to-end metrics. `--trace 1` runs the
same window with each step split into the spans `feed`, `exe.run`
(until the call returns) and `fetch` (until the loss is on the host),
then traces a few more steps with `jax.profiler`, and prints the cell's
per-layer metrics. The last stdout line is the result; earlier lines
(one JSON object each) say where it ran and what it saw.

One process, no child. Without a TPU, or with fewer chips than the cell
asks for, it exits non-zero before any work. `--tiny` is the rehearsal:
the same code at a toy size on whatever backend JAX has; it gives no
metric a value, never reports correct, and exits non-zero.
"""
import time

T0 = time.perf_counter()  # the process's start, as near as Python sees it

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.metadata  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# JAX's persistent compilation cache, at a fixed path inside the checkout
# (the path is part of the cache's key); where JAX_COMPILATION_CACHE_DIR
# is set, `enable_compile_cache` takes that directory instead
CACHE_DIR = os.path.join(ROOT, ".xla_cache")
SPLIT_SPANS = ("feed", "exe.run", "fetch")
# step 1 compiles against the startup program's uncommitted state, step 2
# against the committed outputs of step 1 (PR 21): both before the window
WARMUP_STEPS = 2
TRACE_STEPS = 10  # traced after the window, in a run that asks for them


def load_module(path):
    """The Python file at `path`, by path: names with dots are fine, and
    nothing has to be a package."""
    name = "_benchmark_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, ROOT))
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def read_json(path):
    with open(path) as f:
        return json.load(f)


def emit(**row):
    print(json.dumps(row), flush=True)


class CompileClock:
    """Backend compiles JAX makes, counted and timed by a
    `jax.monitoring` listener (a load from the persistent cache counts as
    a short one), summed between two `take()`s."""

    def __init__(self):
        import jax.monitoring
        self.seconds, self.count = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event, duration, **kwargs):
        if event.endswith("backend_compile_duration"):
            self.seconds += duration
            self.count += 1

    def take(self):
        out = (self.seconds, self.count)
        self.seconds, self.count = 0.0, 0
        return out


class Spans:
    """The benchmark's own spans, by its own clock; while `annotate` is
    set each is also a `jax.profiler.TraceAnnotation`, so that the trace
    carries it on the device events' clock."""

    def __init__(self):
        self.by_name = {}
        self.annotate = False

    @contextlib.contextmanager
    def __call__(self, name):
        if self.annotate:
            import jax.profiler
            cm = jax.profiler.TraceAnnotation(name)
        else:
            cm = contextlib.nullcontext()
        with cm:
            start = time.perf_counter()
            try:
                yield
            finally:
                self.by_name.setdefault(name, []).append(
                    (start, time.perf_counter()))


def no_span(name):
    return contextlib.nullcontext()


class Run:
    """What one run saw: all a metric's reader may read."""

    def __init__(self, spans):
        stats = load_module(os.path.join(HERE, "stats.py"))
        self.median, self.percentile = stats.median, stats.percentile
        self.peak = load_module(os.path.join(HERE, "peaks.py")).peak
        self.spans = spans       # name -> [(start_s, end_s)], host clock
        self.counters = {}       # name -> number
        self.trace = None        # trace_reduce.reduce(...), traced runs
        self.step_s = []         # wall seconds of each step of the window
        self.losses = []         # the loss each of those steps returned
        self.window_s = 0.0      # first step's start to last step's end
        self.setup_s = 0.0       # process start to the first timed step
        self.samples_per_step = 0   # the global batch
        self.flops_per_sample = 0.0
        self.chips = 1
        self.device_kind = ""

    @property
    def rate(self):
        """Samples a second over all the steps and all the time of the
        window."""
        return len(self.step_s) * self.samples_per_step / self.window_s


def fold_seed(seed):
    """`--seed` may exceed 32 signed bits; a program's seed may not, and 0
    means "none given"."""
    return seed % (2 ** 31 - 1) or 1


def falls(losses, n):
    """Does the loss fall over the window: is the mean of its last `n`
    values below the mean of its first `n`?"""
    n = min(n, len(losses) // 2)
    return n >= 1 and sum(losses[-n:]) < sum(losses[:n])


def memory_peak_bytes(used):
    """The peak on the fullest chip. The runtime's `peak_bytes_in_use`
    counts live arrays and, on the v5e's runtime, not the temporaries an
    executable holds while it runs (PR 21: 2.0 GB read for a step the
    compiler gives 10.1 GB); so the peak is the larger of that counter and
    the largest footprint among the executables the process has loaded:
    arguments + outputs - aliased + temporaries + code, each chip's share,
    as `get_compiled_memory_stats()` of the loaded executable gives them."""
    counters = [(d.memory_stats() or {}).get("peak_bytes_in_use") or 0
                for d in used]
    footprints = [0]
    for executable in used[0].client.live_executables():
        m = executable.get_compiled_memory_stats()
        footprints.append(
            m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes
            + m.generated_code_size_in_bytes)
    emit(phase="memory", peak_bytes_in_use=counters,
         largest_executable_bytes=max(footprints),
         memory_stats=used[0].memory_stats())
    return max(counters + footprints) or None


def trace_options():
    import jax.profiler
    options = jax.profiler.ProfileOptions()
    # no Python call tracing: it slows the host inside the traced steps,
    # and the benchmark's spans are TraceAnnotations, which stay
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    return options


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="rehearsal at a toy size on any backend; no "
                         "metric gets a value, never correct")
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="leave the profiler's trace in DIR (to read by "
                         "hand) instead of deleting it")
    args = ap.parse_args(argv)

    manifest = read_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if args.workload not in cells:
        sys.exit(f"run.py: BENCHMARK.json has no workload "
                 f"{args.workload!r}; it has {sorted(cells)}")
    cell = cells[args.workload]
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    workload = read_json(os.path.join(HERE, "workloads",
                                      cell["name"] + ".json"))
    config = read_json(os.path.join(ROOT, entry["file"]))
    model = load_module(
        os.path.join(ROOT, os.path.splitext(entry["file"])[0] + ".py"))
    traffic = workload["traffic"]
    if args.tiny:
        config, traffic = model.tiny(config, traffic)
    group = "per_layer" if args.trace else "end_to_end"
    folder = "layer_metrics" if args.trace else "end_to_end"
    metrics = [m for m in manifest[group]
               if cell["name"] in m.get("workloads", [cell["name"]])]
    readers = {m["name"]: load_module(
        os.path.join(HERE, folder, m["name"] + ".py")) for m in metrics}

    import jax
    devices = jax.devices()
    chips = cell["chips"]
    if not args.tiny and devices[0].platform != "tpu":
        sys.exit(f"run.py: needs a TPU, jax.devices()[0] is "
                 f"{devices[0].platform!r} ({devices[0].device_kind})")
    if len(devices) < chips:
        sys.exit(f"run.py: {cell['name']} needs {chips} chips, "
                 f"JAX has {len(devices)}")
    used = devices[:chips]
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}

    sys.path.insert(0, ROOT)
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import core
    from paddle_tpu.inference import enable_compile_cache
    cache_dir = enable_compile_cache(CACHE_DIR)
    emit(phase="start", workload=cell["name"], seed=args.seed,
         seconds=args.seconds, trace=args.trace, rehearsal=args.tiny,
         device=device, chips=chips, jax=jax.__version__,
         jaxlib=importlib.metadata.version("jaxlib"),
         compile_cache_dir=cache_dir,
         cache_entries_before=len(os.listdir(cache_dir)))

    spans, clock = Spans(), CompileClock()
    run = Run(spans.by_name)
    if args.tiny:
        # a rehearsal has no chip and so no peak; its values are dropped
        run.peak = lambda kind, what: float("nan")
    run.chips, run.device_kind = chips, device["kind"]
    run.samples_per_step = traffic["batch"]
    run.flops_per_sample = model.flops_per_sample(config, traffic)

    # ---- set-up: program, startup, pool of host batches, warm-up steps
    mesh = None
    if workload.get("mesh") != ({"dp": chips} if chips > 1 else None):
        sys.exit(f"run.py: {cell['name']}: the workload file's mesh "
                 f"{workload.get('mesh')} is not what {chips} chip(s) get")
    if chips > 1:
        from paddle_tpu.parallel.mesh import build_mesh
        mesh = build_mesh(devices=used)  # data-parallel over the chips
    with spans("build"):
        for flag, value in config.get("flags", {}).items():
            core.set_flag(flag, value)
        program, startup, fetches = model.build(config, traffic)
        program.random_seed = startup.random_seed = fold_seed(args.seed)
        exe = fluid.Executor(fluid.TPUPlace(0))
        scope = core.Scope()
        exe.run(startup, scope=scope)
    with spans("batches"):
        pool = model.make_batches(config, traffic, args.seed,
                                  traffic["pool"])

    def step(i, split):
        """One step of the user's loop: (wall seconds, loss); `split` puts
        the benchmark's spans around its parts. Both forms call `exe.run`
        from this one line: a Pallas kernel carries the Python call stack
        it was traced under into the executable, so a second call site
        would be a second entry in the compile cache (PR 24: 80 s)."""
        span = spans if split else no_span
        start = time.perf_counter()
        with span("feed"):
            feed = pool[i % len(pool)]
        with span("exe.run"):
            out = exe.run(program, feed=feed, fetch_list=fetches, scope=scope,
                          mesh=mesh, return_numpy=not split)
        with span("fetch"):
            loss = np.asarray(out[0].array if split else out[0])
        end = time.perf_counter()
        return end - start, float(loss.ravel()[0])

    # in the form (split or not) the window will use
    with spans("warmup"):
        warm = [step(i, bool(args.trace)) for i in range(WARMUP_STEPS)]
    first_loss = warm[0][1]
    params = [p.name for p in program.global_block().all_parameters()]
    holding = set()
    for name in params:
        holding |= set(scope.find_var(name).get_tensor().array.devices())
    run.counters["setup_compile_s"], run.counters["setup_compiles"] = \
        clock.take()
    run.setup_s = time.perf_counter() - T0
    emit(phase="setup", setup_s=run.setup_s,
         build_seconds=sum(e - s for s, e in run.spans["build"]),
         batches_seconds=sum(e - s for s, e in run.spans["batches"]),
         warmup_step_s=[w[0] for w in warm],
         warmup_losses=[w[1] for w in warm],
         compile_seconds=run.counters["setup_compile_s"],
         compiles=run.counters["setup_compiles"],
         parameters=len(params), devices_holding_parameters=len(holding),
         cache_entries_after=len(os.listdir(cache_dir)))

    # ---- the window: steps until `--seconds` have passed
    n = len(warm)
    window_start = time.perf_counter()
    deadline = window_start + args.seconds
    while time.perf_counter() < deadline:
        seconds, loss = step(n, bool(args.trace))
        run.step_s.append(seconds)
        run.losses.append(loss)
        n += 1
    run.window_s = time.perf_counter() - window_start
    _, run.counters["window_compiles"] = clock.take()
    emit(phase="window", steps=len(run.step_s), window_s=run.window_s,
         step_ms=[round(s * 1e3, 3) for s in run.step_s],
         losses=[round(x, 5) for x in run.losses])

    # ---- the traced steps, after the window, in a run that asked for them
    if args.trace:
        reducer = load_module(os.path.join(HERE, "trace_reduce.py"))
        trace_dir = args.keep_trace or tempfile.mkdtemp(prefix="trace-")
        spans.annotate = True
        jax.profiler.start_trace(trace_dir, profiler_options=trace_options())
        try:
            for _ in range(TRACE_STEPS):
                seconds, loss = step(n, True)
                run.losses.append(loss)
                n += 1
        finally:
            jax.profiler.stop_trace()
            spans.annotate = False
        try:
            run.trace = reducer.reduce(
                *reducer.read(trace_dir, set(SPLIT_SPANS)), steps=TRACE_STEPS)
        finally:
            if not args.keep_trace:
                shutil.rmtree(trace_dir, ignore_errors=True)
        emit(phase="trace", trace=run.trace)

    # ---- is what ran correct?
    want = config["correct"]
    target = math.log(config["classes"])
    checks = {
        "on_a_tpu": device["platform"] == "tpu",
        "parameters_on_the_chips": holding == set(used),
        "losses_finite": bool(np.isfinite(run.losses).all()
                              and np.isfinite([w[1] for w in warm]).all()),
        "first_loss_near_ln_classes":
            abs(first_loss - target) <= want["first_loss_rel_tol"] * target,
        "loss_falls": falls(run.losses, want["falling_n"]),
        "no_compile_in_window": run.counters["window_compiles"] == 0,
        "not_a_rehearsal": not args.tiny,
    }
    emit(phase="checks", first_loss=first_loss, ln_classes=target, **checks)

    values = {}
    for m in metrics:
        value = readers[m["name"]].compute(run)
        if value is not None:
            # a rehearsal's numbers are a CPU's: they get no value under
            # the name of a device metric
            values[m["name"]] = {"value": None if args.tiny else value,
                                 "unit": m["unit"]}
    device["memory_peak_bytes"] = memory_peak_bytes(used)
    result = {"correct": all(checks.values()),
              "attempted": len(run.step_s),
              "failed": int(sum(not math.isfinite(x)
                                for x in run.losses[:len(run.step_s)])),
              "metrics": values, "device": device}
    if run.trace:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = {"device_ops": run.trace["device_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    emit(**result)
    if args.tiny:
        sys.exit("run.py: --tiny is a rehearsal, not a chip run")


if __name__ == "__main__":
    main()
