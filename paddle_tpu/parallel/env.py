"""Distributed environment: mesh registry (ring_id → axis) + PADDLE_* env
contract (reference: launch env in python/paddle/distributed/launch.py:193
and role_maker.py:442)."""
from __future__ import annotations

import os
from typing import Optional

import jax

_mesh = None


def set_mesh(mesh):
    global _mesh
    _mesh = mesh


def get_mesh():
    return _mesh


def world_size() -> int:
    return int(os.getenv("PADDLE_TRAINERS_NUM", "1"))


def rank() -> int:
    return int(os.getenv("PADDLE_TRAINER_ID", "0"))


def local_device_count() -> int:
    return len(jax.local_devices())


def init_distributed(coordinator_address: Optional[str] = None) -> bool:
    """Bring up the cross-host runtime from the PADDLE_* env contract
    (reference: the NCCL-id bootstrap c_gen_nccl_id + NCCLCommContext init,
    collective/c_gen_nccl_id_op.cc — here one jax.distributed.initialize
    makes every host's chips visible as one global mesh over ICI/DCN).

    Coordinator: `JAX_COORDINATOR_ADDRESS` env if set, else trainer 0's
    endpoint from PADDLE_TRAINER_ENDPOINTS (free in this build's collective
    mode — no server binds it). Returns True if a multi-host init ran;
    single-process jobs are a no-op."""
    n = world_size()
    if n <= 1 or jax.distributed.is_initialized():
        return False
    addr = (coordinator_address
            or os.getenv("JAX_COORDINATOR_ADDRESS")
            or os.getenv("PADDLE_TRAINER_ENDPOINTS", "").split(",")[0])
    if not addr:
        raise RuntimeError(
            "init_distributed needs PADDLE_TRAINER_ENDPOINTS or "
            "JAX_COORDINATOR_ADDRESS to locate the coordinator")
    # The CPU backend refuses cross-process computations ("Multiprocess
    # computations aren't implemented on the CPU backend") unless a CPU
    # collectives implementation is selected BEFORE the backend is
    # created; this jaxlib ships gloo, so multi-process CPU meshes (the
    # launch-parity lanes) need it switched on here, not at step time.
    if os.getenv("JAX_PLATFORMS", "").startswith("cpu"):
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=addr,
                               num_processes=n, process_id=rank())
    return True
