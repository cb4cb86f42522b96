"""Production mesh builders and the multi-process cluster join.

Functions, not module-level constants — importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax init)."""
from __future__ import annotations

import math
import os
from typing import Sequence

import jax


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices=None):
    """``jax.make_mesh`` with every axis in Auto mode (the mode the
    shard_map programs and sharding constraints here are written for)."""
    names = tuple(axis_names)
    return jax.make_mesh(tuple(axis_shapes), names, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(names))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; the multi-pod mesh adds a leading 2-pod
    axis (512 chips). Axes: ("pod",) "data", "model"."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    devices = jax.devices()[:math.prod(shape)]
    return make_mesh(shape, axes, devices=devices)


def make_worker_mesh(workers: int | None = None, axis_name: str = "workers"):
    """1-D mesh for the MR-HAP clustering runtime: the first ``workers``
    devices, all local devices when None."""
    n = workers or len(jax.devices())
    return make_mesh((n,), (axis_name,), devices=jax.devices()[:n])


def maybe_init_distributed() -> bool:
    """Join a multi-process jax cluster iff the environment describes one.

    A multi-process launch (one process per host, each seeing its local
    devices) must call ``jax.distributed.initialize`` before any mesh is
    built so ``jax.devices()`` spans the whole cluster. Launchers say so
    through the standard variables ``JAX_COORDINATOR_ADDRESS`` /
    ``JAX_NUM_PROCESSES`` / ``JAX_PROCESS_ID`` (``COORDINATOR_ADDRESS``
    etc. accepted as fallbacks, matching jax's own env lookup).

    Single-process runs — no coordinator advertised, or an advertised
    process count of 1 — are a strict no-op: nothing is initialized and
    the function returns False, so calling this unconditionally from the
    engine is always safe. Returns True when a cluster is (or already
    was) initialized; repeated calls are idempotent.
    """
    if jax.distributed.is_initialized():
        return True
    addr = (os.environ.get("JAX_COORDINATOR_ADDRESS")
            or os.environ.get("COORDINATOR_ADDRESS"))
    nproc = (os.environ.get("JAX_NUM_PROCESSES")
             or os.environ.get("NUM_PROCESSES"))
    if not addr or not nproc or int(nproc) < 2:
        return False
    pid = (os.environ.get("JAX_PROCESS_ID")
           or os.environ.get("PROCESS_ID") or "0")
    jax.distributed.initialize(coordinator_address=addr,
                               num_processes=int(nproc),
                               process_id=int(pid))
    return True
