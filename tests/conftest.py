"""Test config: run on a virtual 8-device CPU mesh so sharding/collective
tests work without TPU hardware (same strategy as the reference's
multiprocess-on-localhost distributed tests — SURVEY.md §4).

The suite is CPU-only whatever the machine holds: the platform is pinned
through jax.config before any backend initializes, so a test never takes
a chip (tests/test_chip_compile.py asks the chip's COMPILER, which needs
no chip)."""
import os
import sys

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(params=["ragged_dot", "kernels"])
def expert_lowering(request):
    """Both lowerings of a pass of `moe_expert_ffn` (ops/decoder_ops.
    _window): `lax.ragged_dot` between masks, what the CPU runs, and the
    Pallas kernels of ops/pallas/grouped_matmul.py through the
    interpreter, at a row tile of 8 and whatever the toy widths are."""
    if request.param == "ragged_dot":
        yield request.param
        return
    from paddle_tpu.ops.pallas import flash_attention, grouped_matmul
    with flash_attention.interpret_guard(), grouped_matmul.block_override(8):
        yield request.param


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running model tests")
    config.addinivalue_line(
        "markers", "full: full-tier-only tests (skipped by the quick "
        "per-commit tier: pytest -m 'not full')")
    config.addinivalue_line(
        "markers", "faults: fault-injection suite (tests/faultinject.py "
        "— killed/paused processes, corrupted checkpoints). Fast "
        "injections (<10s) stay in the tier-1 non-slow set; the heavier "
        "multiprocess ones also carry 'slow'. All injections run "
        "JAX_PLATFORMS=cpu subprocesses, so PADDLE_TPU_TEST_SHARD "
        "file-level sharding applies unchanged.")
    config.addinivalue_line(
        "markers", "chaos: PS-membership chaos suite (tools/chaos_ps.py "
        "+ tests/test_ps_membership.py — live pserver drains, SIGKILL "
        "replica failover, corrupted shard handoffs). The in-process "
        "protocol tests run fast heartbeat/deadline settings and stay "
        "in the tier-1 non-slow set; the multiprocess scenario drivers "
        "also carry 'slow'. Subprocesses run JAX_PLATFORMS=cpu, so "
        "PADDLE_TPU_TEST_SHARD file-level sharding applies unchanged.")
    config.addinivalue_line(
        "markers", "streaming: streaming online-learning suite "
        "(fully-async Communicator plane + resumable StreamLoader + "
        "train-and-serve composition; tests/test_streaming.py, "
        "tools/chaos_ps.py --scenario streaming). In-process units — "
        "stream-offset resume bit-parity, typed async-failure "
        "counters, freshness histogram, ingress auth — stay tier-1; "
        "the multiprocess chaos twin also carries 'slow'.")
    config.addinivalue_line(
        "markers", "serving: online-serving plane suite "
        "(paddle_tpu/serving/ — continuous batcher, predictor pool, "
        "serving-time embedding fetch; tests/test_serving.py). "
        "In-process tests (incl. the thread-harness pserver ones) stay "
        "in the tier-1 non-slow set; the multiprocess ones (cross-"
        "process compile-cache cold start, loadgen subprocess drivers) "
        "also carry 'slow'. Subprocesses run JAX_PLATFORMS=cpu, so "
        "PADDLE_TPU_TEST_SHARD file-level sharding applies unchanged.")
    config.addinivalue_line(
        "markers", "obs: unified-telemetry-plane suite "
        "(fluid/telemetry.py + tools/timeline.py merge — trace "
        "propagation, metrics registry/exposition, trace shards; "
        "tests/test_telemetry.py). In-process tests stay in the tier-1 "
        "non-slow set; the multiprocess timeline-merge acceptance also "
        "carries 'slow'. Subprocesses run JAX_PLATFORMS=cpu, so "
        "PADDLE_TPU_TEST_SHARD file-level sharding applies unchanged.")
    config.addinivalue_line(
        "markers", "wan: compressed PS data-plane / WAN-emulation suite "
        "(docs/PS_DATA_PLANE.md 'Compression' — wire v3 quantized "
        "frames, DGC top-k grads, geo-delta rounds under injected "
        "RTT/jitter/bandwidth; tests/test_ps_compression.py). Units and "
        "in-process thread-harness tests stay tier-1 non-slow; the "
        "multiprocess 2-region 50ms-RTT scenario also carries 'slow'. "
        "Subprocesses run JAX_PLATFORMS=cpu, so PADDLE_TPU_TEST_SHARD "
        "file-level sharding applies unchanged.")
    config.addinivalue_line(
        "markers", "capacity: PS capacity-tier suite (fluid/"
        "slab_spill.py + LazyEmbeddingTable disk tier — slab spill/"
        "promotion, at-rest quantized rows, entry gating, decay "
        "shrink, corrupt-spill rejection, streaming handoff/"
        "checkpoint; tests/test_ps_capacity.py). In-process tier "
        "tests stay tier-1 non-slow; multiprocess spill lanes also "
        "carry 'slow'. Subprocesses run JAX_PLATFORMS=cpu, so "
        "PADDLE_TPU_TEST_SHARD file-level sharding applies unchanged.")
    config.addinivalue_line(
        "markers", "rpcbench: PS-RPC data-plane microbench smoke "
        "(tools/rpc_microbench.py loopback sweep at tiny sizes — the "
        "full 4KB..64MB run is a manual tool invocation). In-process "
        "and fast, stays in the tier-1 non-slow set.")
    config.addinivalue_line(
        "markers", "analysis: static-analysis plane suite "
        "(fluid/analysis.py program verifier + tools/lockcheck.py "
        "concurrency lint; tests/test_analysis.py — per-rule units, the "
        "seeded-mutation corpus, the repo-wide lockcheck run, CLI "
        "smokes; docs/ANALYSIS.md). All in-process and tier-1 non-slow. "
        "The opt-in PADDLE_TPU_VERIFY=1 sweep additionally verifies "
        "every Program the whole suite builds (conftest "
        "_verify_programs fixture + tests/verify_allowlist.py).")
    config.addinivalue_line(
        "markers", "fleet: self-healing serving-fleet suite "
        "(serving/fleet.py — trainer→serving invalidation pub/sub over "
        "the binary wire, epoch-stamped fleet membership with heartbeat "
        "eviction and zero-lost rolling drain, SLO autopilot; "
        "tests/test_fleet.py). In-process protocol/unit tests (thread-"
        "harness publishers/directories) stay in the tier-1 non-slow "
        "set; the multiprocess chaos acceptance (tools/chaos_ps.py "
        "--scenario serving_fleet) also carries 'slow'. Subprocesses "
        "run JAX_PLATFORMS=cpu, so PADDLE_TPU_TEST_SHARD file-level "
        "sharding applies unchanged.")
    config.addinivalue_line(
        "markers", "parallel3d: composed 3D-parallel lane suite "
        "(parallel/lm3d.py dp×pp×sp+MoE on the virtual 8-device mesh, "
        "gpipe/MoE composition units, executor window×pipeline "
        "parity — docs/ci.md). Small-shape units stay in the tier-1 "
        "non-slow set; the full bench-scale composition acceptance "
        "also carries 'slow'.")


import pytest as _pytest


@_pytest.fixture(autouse=True)
def _verify_programs(request):
    """Opt-in (PADDLE_TPU_VERIFY=1) program-verify sweep: run the
    static-analysis plane in warn mode over every Program this test
    compiles/interprets (the Executor/transpiler choke points fire
    behind FLAGS_program_verify) and fail on any diagnostic
    tests/verify_allowlist.py does not explain. Off by default so the
    tier-1 gate's time budget is untouched."""
    if not os.environ.get("PADDLE_TPU_VERIFY"):
        yield
        return
    if "analysis" in request.node.keywords:
        # the analysis suite exercises the verifier itself — its tests
        # emit diagnostics on purpose
        yield
        return
    from paddle_tpu.fluid import analysis, core as _core
    collected = []
    hook = analysis.install_collector(collected.append)
    old = _core.globals_["FLAGS_program_verify"]
    _core.set_flag("FLAGS_program_verify", "warn")
    try:
        yield
    finally:
        _core.set_flag("FLAGS_program_verify", old)
        analysis.remove_collector(hook)
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "verify_allowlist",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "verify_allowlist.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    bad = mod.unexplained(collected, request.node.nodeid.replace(
        os.sep, "/"))
    assert not bad, (
        "program verifier surfaced unexplained diagnostics — fix the "
        "program or add a rationale entry to tests/verify_allowlist.py:"
        "\n" + "\n".join(d.format() for d in bad))


def pytest_collection_modifyitems(config, items):
    """Two suite tiers (VERDICT r03 item 9): the quick per-commit tier
    (`pytest -m "not full"`, target < 5 min) skips tests listed in
    tests/full_tier.txt — one nodeid prefix per line, maintained from
    `pytest --durations` output. The full tier (plain `pytest tests/`)
    runs everything and stays the round-end gate.

    Sharding (VERDICT r5 next-round item 7): PADDLE_TPU_TEST_SHARD=i/n
    deterministically keeps every test whose nodeid CRC lands in shard i
    (1-based) of n — run n pytest processes with i=1..n on a multi-core
    box and the full tier splits near-evenly with zero coordination
    (docs/ci.md). Unset (the 1-core fallback) nothing changes. Sharding
    at FILE granularity keeps per-file fixtures/session state together,
    matching how pytest-xdist --dist=loadfile would split."""
    import pytest
    shard = os.environ.get("PADDLE_TPU_TEST_SHARD")
    if shard:
        import zlib
        try:
            idx, n = (int(p) for p in shard.split("/"))
        except ValueError:
            raise pytest.UsageError(
                f"PADDLE_TPU_TEST_SHARD must look like '2/4', got "
                f"{shard!r}")
        if not 1 <= idx <= n:
            raise pytest.UsageError(
                f"shard index {idx} out of range 1..{n}")
        kept, dropped = [], []
        for item in items:
            fname = item.nodeid.split("::", 1)[0].replace(os.sep, "/")
            (kept if zlib.crc32(fname.encode()) % n == idx - 1
             else dropped).append(item)
        if dropped:
            config.hook.pytest_deselected(items=dropped)
            items[:] = kept
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "full_tier.txt")
    if not os.path.exists(path):
        return
    prefixes = tuple(
        ln.strip() for ln in open(path)
        if ln.strip() and not ln.strip().startswith("#"))
    if not prefixes:
        return
    mark = pytest.mark.full
    for item in items:
        nid = item.nodeid.replace(os.sep, "/")
        if nid.startswith(prefixes):
            item.add_marker(mark)
