"""Multi-host (DCN) runtime: jax.distributed bring-up and cross-host
weight broadcast.

Plays the multi-host roles of the reference's L1 stack the TPU way:
the heavy lifting (device enumeration across hosts, ICI+DCN collective
routing) belongs to ``jax.distributed.initialize`` + XLA; this module
supplies the bring-up around it (who is the coordinator, gloo switch
for CPU harnesses, broadcast/barrier wrappers).

The KV/rendezvous control plane (KVServer/KVClient, pubsub,
heartbeats) moved to :mod:`ray_tpu.fleet.kv` in PR 17 — it belongs to
the fleet subsystem that owns the membership protocol. The names are
re-exported here for back-compat.
"""

from __future__ import annotations

import os
from typing import Optional

from ray_tpu.fleet.kv import (  # noqa: F401  (back-compat re-exports)
    HeartbeatReporter,
    KVClient,
    KVServer,
    Subscriber,
    _body_digest,
    _body_ok,
    _channel_match,
    _request_hmac,
)

# ---------------------------------------------------------------------------
# jax.distributed bring-up
# ---------------------------------------------------------------------------

_initialized = False


def initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    local_device_ids=None,
) -> None:
    """Join the multi-controller jax runtime (DCN). Reads
    RAY_TPU_COORDINATOR / RAY_TPU_NUM_PROCESSES / RAY_TPU_PROCESS_ID
    when args are omitted, so every host runs the same script.

    Replaces the reference's NCCL/gloo rendezvous
    (``util/collective/collective.py:120`` init_collective_group): after
    this, a global Mesh over ``jax.devices()`` spans all hosts and XLA
    routes collectives over ICI within a host/pod slice and DCN across.
    """
    global _initialized
    if _initialized:
        return
    import jax

    coordinator_address = coordinator_address or os.environ.get(
        "RAY_TPU_COORDINATOR"
    )
    if coordinator_address is None:
        return  # single-host: nothing to do
    # CPU backend: XLA's default CPU client cannot run cross-process
    # computations ("Multiprocess computations aren't implemented on
    # the CPU backend") — switch its collectives to gloo BEFORE the
    # backend initializes, so the simulated multi-host tests (and any
    # CPU-only DCN bring-up) get working psum/broadcast. TPU ignores
    # this path entirely.
    platforms = str(
        getattr(jax.config, "jax_platforms", None)
        or os.environ.get("JAX_PLATFORMS", "")
    ).lower()
    if "cpu" in platforms:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    num_processes = int(
        num_processes
        if num_processes is not None
        else os.environ.get("RAY_TPU_NUM_PROCESSES", 1)
    )
    process_id = int(
        process_id
        if process_id is not None
        else os.environ.get("RAY_TPU_PROCESS_ID", 0)
    )
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        local_device_ids=local_device_ids,
    )
    _initialized = True


def process_index() -> int:
    import jax

    return jax.process_index()


def process_count() -> int:
    import jax

    return jax.process_count()


def global_mesh():
    """Mesh over ALL devices of ALL processes (DCN+ICI) — the same
    construction Algorithm.setup uses, so the axis naming cannot
    drift between the two paths."""
    import jax

    from ray_tpu import sharding as sharding_lib

    return sharding_lib.get_mesh(devices=jax.devices())


def broadcast_weights(tree, is_source: Optional[bool] = None):
    """Cross-host weight broadcast: every process returns process 0's
    pytree (reference WorkerSet.sync_weights across nodes / NCCL
    broadcast ``collective.py:373``). Rides XLA collectives over DCN via
    multihost_utils."""
    from jax.experimental import multihost_utils

    return multihost_utils.broadcast_one_to_all(
        tree, is_source=is_source
    )


def sync_global(name: str = "barrier") -> None:
    """Cross-host barrier (reference collective barrier)."""
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices(name)
