"""Mesh construction + feed sharding for data/model parallel execution.

Replaces the reference's multi-device machinery (reference:
framework/parallel_executor.cc:442 — per-device graph clones, NCCL comms,
allreduce op-handles) with sharding metadata: ONE jitted step function whose
feed batch is sharded over the "dp" mesh axis and whose parameters are
replicated; XLA's sharding propagation inserts the gradient all-reduces over
ICI. Multi-host: the same code with jax.distributed initialized — each host
provides its local shard via make_array_from_process_local_data (DCN/ICI
handled by XLA).
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

import numpy as np
import jax
from jax import shard_map  # noqa: F401  (re-exported for parallel/*)
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "dp"
MODEL_AXIS = "mp"


def axis_mesh(n: int, axis_name: str, devices=None) -> Mesh:
    """1-D named-axis mesh over the first n devices (pp/sp helpers)."""
    devs = list(devices if devices is not None else jax.devices())[:n]
    if len(devs) != n:
        raise ValueError(f"need {n} devices, have {len(devs)}")
    return Mesh(np.asarray(devs), (axis_name,))


def mesh3d(dp: int = 2, pp: int = 2, sp: int = 2, devices=None) -> Mesh:
    """The composed 3D-parallel mesh ("dp", "pp", "sp") — data ×
    pipeline × sequence over dp·pp·sp devices (the 8-device virtual
    mesh at 2×2×2). Expert parallelism reuses one of these axes as the
    all-to-all group (parallel/lm3d.py dispatches experts over "dp"),
    so a 4th axis is never materialized."""
    n = dp * pp * sp
    devs = list(devices if devices is not None else jax.devices())[:n]
    if len(devs) != n:
        raise ValueError(
            f"mesh3d(dp={dp}, pp={pp}, sp={sp}) needs {n} devices, "
            f"have {len(devs)}")
    return Mesh(np.asarray(devs).reshape(dp, pp, sp), ("dp", "pp", "sp"))


def build_mesh(num_devices: Optional[int] = None, model_parallel: int = 1,
               devices=None) -> Mesh:
    devs = list(devices if devices is not None else jax.devices())
    if num_devices is not None:
        if len(devs) < num_devices:
            raise ValueError(
                f"build_mesh needs {num_devices} devices, have {len(devs)}")
        devs = devs[:num_devices]
    n = len(devs)
    mp = max(1, model_parallel)
    if n % mp != 0:
        raise ValueError(
            f"device count {n} is not divisible by model_parallel={mp}")
    dp = n // mp
    arr = np.asarray(devs).reshape(dp, mp)
    return Mesh(arr, (DATA_AXIS, MODEL_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharded(mesh: Mesh, ndim: int) -> NamedSharding:
    return NamedSharding(mesh, P(DATA_AXIS, *([None] * (ndim - 1))))


def shard_feed(mesh: Mesh, name: str, array, window: bool = False) -> jax.Array:
    """Place a host batch onto the mesh, sharded on dim 0. In multi-process
    mode the given array is this process's LOCAL shard. Meshes without a
    data axis (e.g. a pure "pp" pipeline mesh) replicate the feed.

    ``window=True``: the array is a [K, batch, ...] WINDOW STACK of K
    distinct batches (docs/INPUT_PIPELINE.md) — the window dim stays
    unsharded (it is the executor's scan axis) and the BATCH dim (dim 1)
    shards over "dp", so ONE device_put places the whole window and the
    per-step slices come out batch-sharded on-device. Window stacks too
    flat to carry a batch dim (ndim < 2) replicate."""
    arr = np.asarray(array)
    bdim = 1 if window else 0
    if DATA_AXIS not in mesh.shape or (window and arr.ndim < 2):
        repl = replicated(mesh)
        if jax.process_count() > 1:
            # device_put can't target non-addressable devices; every
            # process holds the identical full value
            return jax.make_array_from_process_local_data(
                repl, arr, global_shape=arr.shape)
        return jax.device_put(arr, repl)
    dp = mesh.shape[DATA_AXIS]
    if window:
        sharding = NamedSharding(mesh, P(
            None, DATA_AXIS, *([None] * (arr.ndim - 2))))
    else:
        sharding = batch_sharded(mesh, max(arr.ndim, 1))
    if jax.process_count() > 1:
        return jax.make_array_from_process_local_data(sharding, arr)
    if arr.shape[bdim] % dp != 0:
        raise ValueError(
            f"feed '{name}' batch {arr.shape[bdim]} not divisible by "
            f"data-parallel degree {dp}")
    return jax.device_put(arr, sharding)
