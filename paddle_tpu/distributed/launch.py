"""Multi-process launcher (reference: python/paddle/distributed/launch.py:193
— builds the cluster from args/PaddleCloud env, spawns one worker per
device group with PADDLE_TRAINER_ID / PADDLE_TRAINERS_NUM /
PADDLE_TRAINER_ENDPOINTS / PADDLE_CURRENT_ENDPOINT set, watches children).

TPU inversion: ONE process per host (jax owns all local chips); multi-host
scale-out sets one worker per host and jax.distributed handles DCN. A chip
belongs to one process at a time, so on a TPU host this launcher starts ONE
worker and itself imports no JAX (it must never hold the chip its worker
needs); --nproc > 1 is for CPU devices. Usage:
    python -m paddle_tpu.distributed.launch --ips=h1,h2 train.py ...
Local multi-process testing (CPU devices):
    python -m paddle_tpu.distributed.launch --nproc=2 --devices_per_proc=4 train.py
"""
from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time


def _parse_args():
    p = argparse.ArgumentParser("paddle_tpu.distributed.launch")
    p.add_argument("--ips", default="127.0.0.1",
                   help="comma-separated host list (one worker per host)")
    p.add_argument("--nproc", type=int, default=None,
                   help="local processes to spawn (testing on CPU)")
    p.add_argument("--devices_per_proc", type=int, default=1)
    p.add_argument("--start_port", type=int, default=6170)
    p.add_argument("--log_dir", default=None)
    p.add_argument("training_script")
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args()


def _local_addrs():
    import socket
    names = {"127.0.0.1", "localhost", socket.gethostname()}
    try:
        names.add(socket.gethostbyname(socket.gethostname()))
    except OSError:
        pass
    return names


def launch():
    args = _parse_args()
    hosts = [h for h in args.ips.split(",") if h]
    nproc = args.nproc if args.nproc is not None else len(hosts)
    endpoints = [f"{hosts[i % len(hosts)]}:{args.start_port + i}"
                 for i in range(nproc)]
    # one worker per host: only spawn the ranks whose endpoint names THIS
    # machine (reference launch.py filters by node IP the same way); local
    # --nproc testing spawns everything.
    local = _local_addrs()
    if len(hosts) > 1:
        ranks = [r for r in range(nproc)
                 if endpoints[r].rsplit(":", 1)[0] in local]
        if not ranks:
            raise SystemExit(
                f"none of --ips={args.ips} matches this host "
                f"({sorted(local)}); run the launcher on each host")
    else:
        ranks = list(range(nproc))
    procs = []
    for rank in ranks:
        env = dict(os.environ)
        env.update({
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": str(nproc),
            "PADDLE_TRAINER_ENDPOINTS": ",".join(endpoints),
            "PADDLE_CURRENT_ENDPOINT": endpoints[rank],
        })
        if args.nproc is not None:
            # local testing: carve virtual CPU devices per process
            env.setdefault("JAX_PLATFORMS", "cpu")
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                                " --xla_force_host_platform_device_count="
                                + str(args.devices_per_proc))
        cmd = [sys.executable, "-u", args.training_script] \
            + args.training_script_args
        log = None
        if args.log_dir:
            os.makedirs(args.log_dir, exist_ok=True)
            log = open(os.path.join(args.log_dir,
                                    f"workerlog.{rank}"), "w")
        procs.append((subprocess.Popen(cmd, env=env, stdout=log,
                                       stderr=log), log))
    # watch loop (reference launch.py:219): tear the pod down on failure
    try:
        alive = True
        while alive:
            alive = False
            for proc, _ in procs:
                ret = proc.poll()
                if ret is None:
                    alive = True
                elif ret != 0:
                    for q, _ in procs:
                        if q.poll() is None:
                            q.send_signal(signal.SIGTERM)
                    sys.exit(ret)
            time.sleep(1)
    finally:
        for _, log in procs:
            if log:
                log.close()


if __name__ == "__main__":
    launch()
