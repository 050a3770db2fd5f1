"""Multi-process launcher test (reference: launch.py sets PADDLE_TRAINER_*
env per spawned worker and watches them — multi_process test pattern)."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = """
import json, os, sys
out = sys.argv[1]
rec = {k: os.environ.get(k) for k in (
    "PADDLE_TRAINER_ID", "PADDLE_TRAINERS_NUM",
    "PADDLE_TRAINER_ENDPOINTS", "PADDLE_CURRENT_ENDPOINT")}
with open(os.path.join(out, "r%s.json" % rec["PADDLE_TRAINER_ID"]), "w") as f:
    json.dump(rec, f)
"""


def test_launch_spawns_workers_with_env(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    # the launcher process itself imports no JAX and touches no device:
    # on a TPU host the chip must stay free for the worker it starts
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS",)}
    env["PYTHONPATH"] = REPO
    res = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc=2", "--start_port=7701", str(script), str(tmp_path)],
        env=env, capture_output=True, timeout=120)
    assert res.returncode == 0, res.stderr.decode()[-2000:]
    recs = []
    for r in range(2):
        p = tmp_path / f"r{r}.json"
        assert p.exists(), (r, res.stderr.decode()[-2000:])
        recs.append(json.load(open(p)))
    assert [r["PADDLE_TRAINER_ID"] for r in recs] == ["0", "1"]
    assert all(r["PADDLE_TRAINERS_NUM"] == "2" for r in recs)
    eps = recs[0]["PADDLE_TRAINER_ENDPOINTS"].split(",")
    assert len(eps) == 2
    assert recs[0]["PADDLE_CURRENT_ENDPOINT"] == eps[0]
    assert recs[1]["PADDLE_CURRENT_ENDPOINT"] == eps[1]


def test_launch_propagates_worker_failure(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import sys; sys.exit(3)\n")
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS",)}
    env["PYTHONPATH"] = REPO
    res = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc=2", "--start_port=7711", str(bad)],
        env=env, capture_output=True, timeout=120)
    assert res.returncode != 0


# r19 fleet-PR buyback: now that the gloo collectives fix (parallel/
# env.py) makes multi-proc launch WORK, this is a ~12s multiprocess
# subprocess driver — those carry `slow` by the docs/ci.md convention.
# Tier-1 keeps test_launch_spawns_workers_with_env + the failure-
# propagation test as the per-commit launch coverage.
@pytest.mark.slow
def test_two_process_dp_matches_single_process(tmp_path):
    """The reference's N-vs-1 oracle (test_dist_base.py:933): the same
    model trained on a 2-process 4-device jax.distributed CPU mesh through
    the launcher must produce the same per-step losses as one process."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["PYTHONPATH"] = REPO
    workload = os.path.join(REPO, "tests", "dist_dp_workload.py")

    multi_out = tmp_path / "multi.json"
    res = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc=2", "--start_port=7821", workload, str(multi_out)],
        env=env, capture_output=True, timeout=420)
    assert res.returncode == 0, res.stderr.decode()[-3000:]
    assert multi_out.exists(), res.stderr.decode()[-3000:]

    single_out = tmp_path / "single.json"
    res1 = subprocess.run(
        [sys.executable, workload, str(single_out)],
        env=env, capture_output=True, timeout=420)
    assert res1.returncode == 0, res1.stderr.decode()[-3000:]

    multi = json.load(open(multi_out))
    single = json.load(open(single_out))
    assert len(multi) == len(single) == 5
    for a, b in zip(multi, single):
        assert abs(a - b) < 1e-4, (multi, single)


@pytest.mark.slow  # demoted r13 (suite-time buyback): 19s, 5 processes;
# the DP half stays tier-1 via the 2/4-process parity tests and the PS
# lazy-table half via test_dist_ps — this case only composes the two
def test_combined_dp_trainers_with_ps_lazy_tables(tmp_path):
    """VERDICT r2 #5 — the BASELINE.md Wide&Deep shape in one job:
    launcher-driven 2-process trainers (jax.distributed bring-up) that
    are data-parallel through a 2-pserver sync plane hosting a
    beyond-threshold LAZY sparse table; per-step losses must match the
    single-process full-batch oracle (reference test_dist_base.py:933 +
    fleet_wrapper.h:86-190)."""
    import socket
    import subprocess as sp
    import time

    workload = os.path.join(REPO, "tests", "dist_dp_ps_workload.py")
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["PYTHONPATH"] = REPO

    def free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        p = s.getsockname()[1]
        s.close()
        return p

    def start_pservers(trainers):
        eps = ",".join(f"127.0.0.1:{free_port()}" for _ in range(2))
        procs, logs = [], []
        for i in range(2):
            log = open(tmp_path / f"ps{trainers}_{i}.log", "wb+")
            logs.append(log)
            procs.append(sp.Popen(
                [sys.executable, workload, "pserver", eps, str(i),
                 str(trainers)],
                env=env, stdout=log, stderr=sp.STDOUT))
        deadline = time.time() + 240
        for p, log in zip(procs, logs):
            while True:
                log.flush()
                data = open(log.name, "rb").read()
                if b"PSERVER_READY" in data:
                    assert b"lazy=True" in data, data[-500:]
                    break
                if p.poll() is not None:
                    raise RuntimeError(
                        f"pserver died rc={p.returncode}: "
                        + data[-1500:].decode(errors="replace"))
                if time.time() > deadline:
                    raise TimeoutError("pserver not ready")
                time.sleep(0.3)
        return eps, procs

    def stop_pservers(eps, procs):
        try:
            sys.path.insert(0, REPO)
            from paddle_tpu.fluid.ps_rpc import VarClient
            for ep in eps.split(","):
                try:
                    VarClient.of(ep).stop()
                except Exception:
                    pass
            VarClient.reset_pool()
        finally:
            for p in procs:
                if p.poll() is None:
                    p.terminate()
                try:
                    p.wait(timeout=10)
                except Exception:
                    p.kill()

    # --- multi: 2 launcher-spawned DP trainers x 2 pservers ----------
    eps, procs = start_pservers(trainers=2)
    multi_out = tmp_path / "multi.json"
    try:
        res = sp.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc=2", "--start_port=7931", workload, "trainer",
             eps, str(multi_out)],
            env=env, capture_output=True, timeout=420)
        assert res.returncode == 0, res.stderr.decode()[-3000:]
        for r in (0, 1):
            assert (tmp_path / f"multi.json.r{r}").exists(), \
                res.stderr.decode()[-3000:]
    finally:
        stop_pservers(eps, procs)

    # --- oracle: single process, full batch, fresh pserver pair ------
    eps1, procs1 = start_pservers(trainers=1)
    single_out = tmp_path / "single.json"
    try:
        env1 = dict(env, PADDLE_TRAINERS_NUM="1", PADDLE_TRAINER_ID="0")
        res1 = sp.run([sys.executable, workload, "trainer", eps1,
                       str(single_out)],
                      env=env1, capture_output=True, timeout=420)
        assert res1.returncode == 0, res1.stderr.decode()[-3000:]
    finally:
        stop_pservers(eps1, procs1)

    r0 = json.load(open(str(multi_out) + ".r0"))
    r1 = json.load(open(str(multi_out) + ".r1"))
    single = json.load(open(str(single_out) + ".r0"))
    assert r0["trainers"] == 2 and single["trainers"] == 1
    # each trainer's loss covers its half of the global batch — the
    # cross-rank mean is the oracle's full-batch loss
    merged = [(a + b) / 2 for a, b in zip(r0["losses"], r1["losses"])]
    assert len(merged) == len(single["losses"]) == 5
    for a, b in zip(merged, single["losses"]):
        assert abs(a - b) < 1e-4, (merged, single["losses"])
    assert r0["samples_per_sec"] > 0


# r19 fleet-PR buyback: ~18s 4-proc subprocess driver; slow per the
# docs/ci.md multiprocess-drivers-carry-slow convention (the 2-proc
# twin above covers the same parity contract in the full tier).
@pytest.mark.slow
def test_four_process_dp_matches_single_process(tmp_path):
    """VERDICT r03 #8 — scale the multi-process proof past 2: a
    4-process 8-device jax.distributed CPU mesh through the launcher
    must reproduce the single-process per-step losses (reference
    test_dist_base.py:847 N-vs-1 oracle)."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["PYTHONPATH"] = REPO
    workload = os.path.join(REPO, "tests", "dist_dp_workload.py")

    multi_out = tmp_path / "multi4.json"
    res = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc=4", "--start_port=7841", workload, str(multi_out)],
        env=env, capture_output=True, timeout=600)
    assert res.returncode == 0, res.stderr.decode()[-3000:]
    assert multi_out.exists(), res.stderr.decode()[-3000:]

    single_out = tmp_path / "single4.json"
    res1 = subprocess.run(
        [sys.executable, workload, str(single_out)],
        env=env, capture_output=True, timeout=600)
    assert res1.returncode == 0, res1.stderr.decode()[-3000:]

    multi = json.load(open(multi_out))
    single = json.load(open(single_out))
    assert len(multi) == len(single) == 5
    for a, b in zip(multi, single):
        assert abs(a - b) < 1e-4, (multi, single)
