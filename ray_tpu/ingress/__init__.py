"""``ray_tpu.ingress``: the internet-scale serving front door.

Three layers between a TCP socket and a mesh forward
(docs/serving.md "the front door"):

- :mod:`~ray_tpu.ingress.http` — the asyncio HTTP/ASGI ingress
  (``POST /v1/policy/<name>/actions``, ``/healthz``, ``/metrics``);
- :mod:`~ray_tpu.ingress.router` — cross-replica batch coalescing
  into full power-of-two buckets with deadlines and dead-replica
  rerouting;
- :mod:`~ray_tpu.ingress.admission` — bounded in-flight budget,
  per-policy quotas + queue-wait shedding (429/503 + Retry-After) so
  overload sheds instead of queueing;
- :mod:`~ray_tpu.ingress.supervisor` — horizontal scale-out: N
  ingress worker PROCESSES accepting on ONE port (SO_REUSEPORT or an
  inherited listener), with crash respawn, forwarded membership,
  whole-bank drain, and one merged ``/metrics`` exposition.

A replica's cold start (``BatchedPolicyServer.warmup``) compiles each
bucket through jax's persistent compilation cache, placed by
``utils/platform.ensure_compile_cache()`` (``JAX_COMPILATION_CACHE_DIR``
to share one across replicas): a later replica still traces and
lowers, and retrieves the executable instead of compiling it.
"""

from ray_tpu.ingress.admission import (  # noqa: F401
    AdmissionController,
    AdmissionDecision,
)
from ray_tpu.ingress.http import PolicyIngress  # noqa: F401
from ray_tpu.ingress.router import (  # noqa: F401
    ActorReplica,
    CoalescingRouter,
    DeadlineExpired,
    LocalReplica,
    NoReplicasAvailable,
    wrap_replica,
)
from ray_tpu.ingress.supervisor import (  # noqa: F401
    ForwardedFeed,
    IngressSupervisor,
    WorkerContext,
)

__all__ = [
    "AdmissionController",
    "AdmissionDecision",
    "PolicyIngress",
    "CoalescingRouter",
    "LocalReplica",
    "ActorReplica",
    "DeadlineExpired",
    "NoReplicasAvailable",
    "wrap_replica",
    "IngressSupervisor",
    "ForwardedFeed",
    "WorkerContext",
]
