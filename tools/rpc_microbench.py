"""Loopback PS-RPC data-plane microbench: pickle wire vs binary frames.

Starts a VarServer with an echo handler on 127.0.0.1 and sweeps payload
sizes through one VarClient per wire generation, printing MB/s for the
round trip (send + echo receive). This isolates the framing cost a PS
trainer pays per tensor: the legacy wire pickles every
ndarray into the message blob (two full copies plus pickle overhead per
direction); the binary wire ships a small pickled header plus the raw
buffer via sendall(memoryview)/recv_into (docs/PS_DATA_PLANE.md).

Usage:
    python tools/rpc_microbench.py                 # 4KB..64MB sweep
    python tools/rpc_microbench.py --smoke         # tiny fast sweep (CI)

The smoke invocation is also exercised by the tier-1 suite
(tests/test_ps_data_plane.py, marker ``rpcbench``).
"""
import argparse
import os
import socket
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

DEFAULT_SIZES = [1 << 12, 1 << 14, 1 << 16, 1 << 18, 1 << 20, 1 << 22,
                 1 << 24, 1 << 26]
SMOKE_SIZES = [1 << 12, 1 << 16, 1 << 20]
# quantized-frame sweep (docs/PS_DATA_PLANE.md "Compression"): the
# payload range where the data path is bandwidth-bound and quantization
# pays — 64KB..16MB
QUANT_SIZES = [1 << 16, 1 << 18, 1 << 20, 1 << 22, 1 << 24]


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def run(sizes=None, repeats=5, warmup=1):
    """Returns a list of rows: {"bytes", "pickle_mb_s", "binary_mb_s",
    "speedup"} — each the round-trip goodput of an echo RPC carrying a
    float32 payload of that size."""
    from paddle_tpu.fluid.ps_rpc import VarClient, VarServer

    sizes = list(sizes or DEFAULT_SIZES)
    srv = VarServer(f"127.0.0.1:{_free_port()}",
                    {"echo": lambda value, trainer_id=0: value}).start()
    ep = f"127.0.0.1:{srv.port}"
    rows = []
    try:
        clients = {}
        old_env = os.environ.get("PADDLE_TPU_PS_PICKLE_WIRE")
        try:
            os.environ["PADDLE_TPU_PS_PICKLE_WIRE"] = "1"
            clients["pickle"] = VarClient(ep, channels=1)
            os.environ.pop("PADDLE_TPU_PS_PICKLE_WIRE", None)
            clients["binary"] = VarClient(ep, channels=1)
        finally:
            if old_env is None:
                os.environ.pop("PADDLE_TPU_PS_PICKLE_WIRE", None)
            else:
                os.environ["PADDLE_TPU_PS_PICKLE_WIRE"] = old_env
        for size in sizes:
            payload = np.arange(size // 4, dtype=np.float32)
            row = {"bytes": int(size)}
            for wire, cli in clients.items():
                for _ in range(warmup):
                    cli.call("echo", value=payload)
                t0 = time.perf_counter()
                for _ in range(repeats):
                    out = cli.call("echo", value=payload)
                dt = time.perf_counter() - t0
                assert np.asarray(out).nbytes == payload.nbytes
                # bytes cross the loopback twice per echo (there + back)
                row[f"{wire}_mb_s"] = round(
                    2 * payload.nbytes * repeats / dt / 1e6, 1)
            row["speedup"] = round(row["binary_mb_s"]
                                   / max(row["pickle_mb_s"], 1e-9), 2)
            rows.append(row)
        for cli in clients.values():
            cli.close()
    finally:
        srv.shutdown()
    return rows


def run_quant(sizes=None, repeats=5, warmup=1, bandwidth_mbps=None):
    """Wire v3 quantized-frame sweep: raw (exact f32) vs fp16 vs int8
    frames through ONE loopback echo server, both directions quantized
    (request by the client flag, response by the server's — one
    process, one flag). Rows report EFFECTIVE MB/s: logical f32
    payload bytes per second, regardless of how few bytes crossed the
    wire — the number a training round actually experiences — plus the
    on-wire compression ratio from ps_rpc's byte counters.

    ``bandwidth_mbps`` emulates a thin pipe via the
    PADDLE_TPU_PS_RPC_BANDWIDTH_MBPS send throttle — the regime the
    compression claims are about. Raw loopback is CPU/syscall-bound at
    GB/s, so there quantization's codec cost can exceed the bytes it
    saves (the 1-core caveat; CPU, builder-run, not recorded)."""
    from paddle_tpu.fluid import core
    from paddle_tpu.fluid import ps_rpc
    from paddle_tpu.fluid.ps_rpc import VarClient, VarServer

    sizes = list(sizes or QUANT_SIZES)
    old_bw = os.environ.get("PADDLE_TPU_PS_RPC_BANDWIDTH_MBPS")
    if bandwidth_mbps:
        os.environ["PADDLE_TPU_PS_RPC_BANDWIDTH_MBPS"] = \
            str(float(bandwidth_mbps))
    # the echo method must ride the data-plane quant allowlist for the
    # duration of the sweep (restored in the finally — tests call this
    # in-process and must not leak a widened allowlist)
    old_methods = ps_rpc._QUANT_METHODS
    ps_rpc._QUANT_METHODS = old_methods | {"echo"}
    srv = VarServer(f"127.0.0.1:{_free_port()}",
                    {"echo": lambda value, trainer_id=0: value}).start()
    ep = f"127.0.0.1:{srv.port}"
    rows = []
    cli = None
    old_flag = core.globals_["FLAGS_ps_wire_quant"]
    try:
        cli = VarClient(ep, channels=1)
        for size in sizes:
            rng = np.random.RandomState(0)
            payload = rng.randn(max(1, size // 256), 64).astype(
                np.float32)  # row-shaped, like embedding pulls
            row = {"bytes": int(payload.nbytes),
                   "bandwidth_mbps": (float(bandwidth_mbps)
                                      if bandwidth_mbps else None)}
            for mode in ("", "fp16", "int8"):
                core.set_flag("FLAGS_ps_wire_quant", mode)
                for _ in range(warmup):
                    cli.call("echo", value=payload)
                ps_rpc.reset_quant_wire_stats()
                t0 = time.perf_counter()
                for _ in range(repeats):
                    out = cli.call("echo", value=payload)
                dt = time.perf_counter() - t0
                assert np.asarray(out).shape == payload.shape
                key = mode or "raw"
                row[f"{key}_mb_s"] = round(
                    2 * payload.nbytes * repeats / dt / 1e6, 1)
                if mode:
                    qs = ps_rpc.quant_wire_stats()
                    row[f"{key}_wire_ratio"] = round(
                        qs["bytes_raw_total"]
                        / max(1, qs["bytes_sent_total"]), 2)
            row["fp16_speedup"] = round(
                row["fp16_mb_s"] / max(row["raw_mb_s"], 1e-9), 2)
            row["int8_speedup"] = round(
                row["int8_mb_s"] / max(row["raw_mb_s"], 1e-9), 2)
            rows.append(row)
    finally:
        ps_rpc._QUANT_METHODS = old_methods
        core.set_flag("FLAGS_ps_wire_quant", old_flag)
        if old_bw is None:
            os.environ.pop("PADDLE_TPU_PS_RPC_BANDWIDTH_MBPS", None)
        else:
            os.environ["PADDLE_TPU_PS_RPC_BANDWIDTH_MBPS"] = old_bw
        if cli is not None:
            cli.close()
        srv.shutdown()
    return rows


# spill-tier sweep (docs/PS_DATA_PLANE.md "Capacity tier"): the resident
# fractions a production hot set actually runs at
SPILL_FRACS = [1.0, 0.5, 0.25, 0.1]


def run_spill(n_rows=20000, dim=64, fracs=None, batch=2048, repeats=10,
              warmup=2, quant=""):
    """Spill-tier pull sweep: ONE in-process VarServer serving
    ``prefetch_rows`` over a LazyEmbeddingTable whose hot set is capped
    at ``frac * n_rows`` — rows-resident fraction vs effective pull
    MB/s (logical f32 row bytes per second through the served path,
    cold promotes + write-back evictions included). frac=1.0 is the
    all-in-RAM oracle lane the spilled rows are judged against.

    Uniform-random ids over the whole working set are the WORST case
    for a hot set (no skew to pin); real CTR traffic is zipfian and
    does better. On this 1-core box the loopback RPC dominates small
    batches — the sweep reports the tier's relative cost, not disk
    bandwidth."""
    import tempfile
    import threading
    from paddle_tpu.fluid import core
    from paddle_tpu.fluid.ps_rpc import VarClient, VarServer

    fracs = list(fracs or SPILL_FRACS)
    rows_bytes = batch * dim * 4
    rows_out = []
    for frac in fracs:
        hot = max(1, int(n_rows * frac))
        # the frac=1.0 oracle lane is tier-free: no tempdir to mint
        d = tempfile.mkdtemp(prefix="pt-spillbench-") \
            if frac < 1.0 else None
        tbl = core.LazyEmbeddingTable(
            height=max(n_rows, 1) * 10, dim=dim, seed=0,
            spill_path=os.path.join(d, "t.slab") if frac < 1.0 else None,
            hot_rows=hot if frac < 1.0 else None,
            at_rest_quant=quant if frac < 1.0 else "",
            spill_seg_rows=max(256, batch))
        rng = np.random.RandomState(0)
        # materialize the whole working set (spills the cold tail)
        for lo in range(0, n_rows, batch):
            tbl.get_rows(np.arange(lo, min(lo + batch, n_rows)))
        lock = threading.Lock()

        def h_prefetch(name, rows, prefetch=False, tbl=tbl, lock=lock):
            with lock:
                return tbl.get_rows(rows)

        srv = VarServer(f"127.0.0.1:{_free_port()}",
                        {"prefetch_rows": h_prefetch}).start()
        cli = VarClient(f"127.0.0.1:{srv.port}", channels=1)
        try:
            for _ in range(warmup):
                cli.call("prefetch_rows", name="t",
                         rows=rng.randint(0, n_rows, batch))
            t0 = time.perf_counter()
            for _ in range(repeats):
                out = cli.call("prefetch_rows", name="t",
                               rows=rng.randint(0, n_rows, batch))
            dt = time.perf_counter() - t0
            assert np.asarray(out).shape == (batch, dim)
            st = tbl.tier_stats()
            rows_out.append({
                "resident_frac": frac, "hot_rows": hot,
                "n_rows": n_rows, "dim": dim, "batch": batch,
                "quant": quant if frac < 1.0 else "",
                "pull_mb_s": round(rows_bytes * repeats / dt / 1e6, 1),
                "hit_rate": st.get("hit_rate", 1.0),
                "store_reads": st.get("store_reads", 0),
                "density_x": st.get("density_x", 0.0),
            })
        finally:
            cli.close()
            srv.shutdown()
            tbl.close_spill(unlink=True)
    base = rows_out[0]["pull_mb_s"] if rows_out else 1.0
    for r in rows_out:
        r["vs_resident"] = round(r["pull_mb_s"] / max(base, 1e-9), 2)
    return rows_out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny fast sweep (CI smoke)")
    ap.add_argument("--quant", action="store_true",
                    help="wire v3 quantized-frame sweep (raw vs fp16 "
                         "vs int8 effective MB/s)")
    ap.add_argument("--spill", action="store_true",
                    help="spill-tier sweep (rows-resident fraction vs "
                         "effective pull MB/s)")
    ap.add_argument("--at-rest-quant", default="",
                    help="spill sweep at-rest encoding: '' | fp16 | "
                         "int8")
    ap.add_argument("--bandwidth-mbps", type=float, default=None,
                    help="emulate a thin pipe at this many MB/s "
                         "(PADDLE_TPU_PS_RPC_BANDWIDTH_MBPS throttle)")
    ap.add_argument("--repeats", type=int, default=None)
    args = ap.parse_args(argv)
    repeats = args.repeats or (2 if args.smoke else 5)
    if args.spill:
        rows = run_spill(
            n_rows=2000 if args.smoke else 20000,
            batch=256 if args.smoke else 2048,
            repeats=repeats if args.repeats else (2 if args.smoke
                                                  else 10),
            quant=args.at_rest_quant)
        print(f"{'resident':>9} {'pull MB/s':>10} {'vs 1.0':>7} "
              f"{'hit rate':>9} {'reads':>7} {'density':>8}")
        for r in rows:
            print(f"{r['resident_frac']:>9} {r['pull_mb_s']:>10} "
                  f"{r['vs_resident']:>7} {r['hit_rate']:>9} "
                  f"{r['store_reads']:>7} {r['density_x']:>8}")
        return rows
    if args.quant:
        rows = run_quant(sizes=SMOKE_SIZES if args.smoke
                         else QUANT_SIZES, repeats=repeats,
                         bandwidth_mbps=args.bandwidth_mbps)
        print(f"{'payload':>10} {'raw MB/s':>10} {'fp16 MB/s':>10} "
              f"{'int8 MB/s':>10} {'fp16 x':>7} {'int8 x':>7}")
        for r in rows:
            print(f"{r['bytes']:>10} {r['raw_mb_s']:>10} "
                  f"{r['fp16_mb_s']:>10} {r['int8_mb_s']:>10} "
                  f"{r['fp16_speedup']:>7} {r['int8_speedup']:>7}")
        return rows
    rows = run(sizes=SMOKE_SIZES if args.smoke else DEFAULT_SIZES,
               repeats=repeats)
    print(f"{'payload':>10} {'pickle MB/s':>12} {'binary MB/s':>12} "
          f"{'speedup':>8}")
    for r in rows:
        print(f"{r['bytes']:>10} {r['pickle_mb_s']:>12} "
              f"{r['binary_mb_s']:>12} {r['speedup']:>8}")
    return rows


if __name__ == "__main__":
    main()
