"""ray_tpu.fleet — the elastic multi-host learner fleet (PR 17).

The learner mesh becomes a fleet the way the reference's cluster is
one (GCS node table, heartbeats, resource-change pubsub): hosts
rendezvous through a KV control plane, membership lives with a
single-writer coordinator, every mesh (re)construction is a
generation-numbered epoch, and a preemption-driven resize is a
restart at the new geometry — the PR-10 reshard contract moves the
state, and the survivor's programs compile through jax's persistent
compilation cache where ``utils/platform.ensure_compile_cache()``
placed one (a retrieval instead of a backend compile; the trace and
the lowering are paid either way).

Modules (docs/fleet.md):

- :mod:`~ray_tpu.fleet.kv`          KV/rendezvous service (promoted
  from ``parallel.distributed``; blocking gets, pubsub, heartbeats);
- :mod:`~ray_tpu.fleet.coordinator` membership, mesh epochs, drain
  protocol, epoch-scoped barriers;
- :mod:`~ray_tpu.fleet.elastic`     resize primitives over the
  reshard contract.

Crash tolerance (PR 19): the coordinator's authority is a fenced KV
lease (``LEASE_NAME``) — standbys acquire it on expiry and rebuild
from the durable KV table, stale-term writes are rejected at the
store (:class:`StaleTermError`), the KV transport retries with
backoff, and partitioned hosts self-fence at their epoch barrier
(docs/fleet.md "Failure model & leadership").
"""

from ray_tpu.fleet.coordinator import (
    BARRIER_TIMEOUT_ENV,
    CH_JOIN,
    CH_LEAVE,
    CH_NOTICE,
    EPOCH_TIMEOUT_ENV,
    HEARTBEAT_ENV,
    HORIZON_ENV,
    LEASE_NAME,
    LEASE_TTL_ENV,
    FleetCoordinator,
    HostAgent,
    K_EPOCH_PTR,
    K_MEMBERS,
    K_READY,
    MeshEpoch,
    barrier_key,
    drain_key,
    epoch_key,
)
from ray_tpu.fleet.elastic import (
    epoch_mesh,
    resize_policy,
    resync_epoch,
    shadow_policy,
)
from ray_tpu.fleet.kv import (
    KV_RETRY_ENV,
    HeartbeatReporter,
    KVClient,
    KVServer,
    StaleTermError,
    Subscriber,
)

__all__ = [
    "BARRIER_TIMEOUT_ENV",
    "CH_JOIN",
    "CH_LEAVE",
    "CH_NOTICE",
    "EPOCH_TIMEOUT_ENV",
    "FleetCoordinator",
    "HEARTBEAT_ENV",
    "HORIZON_ENV",
    "HeartbeatReporter",
    "HostAgent",
    "KVClient",
    "KVServer",
    "KV_RETRY_ENV",
    "K_EPOCH_PTR",
    "K_MEMBERS",
    "K_READY",
    "LEASE_NAME",
    "LEASE_TTL_ENV",
    "MeshEpoch",
    "StaleTermError",
    "Subscriber",
    "barrier_key",
    "drain_key",
    "epoch_key",
    "epoch_mesh",
    "resize_policy",
    "resync_epoch",
    "shadow_policy",
]
