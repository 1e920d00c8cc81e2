"""ray_tpu.fleet.elastic — live mesh resize as a restart at the new
geometry.

``resize_policy`` is the **PR-10 reshard contract** applied to a mesh
change: ``Policy.set_state`` re-places any host state tree per the
ACTIVE sharding rules, bitwise across mesh geometries — so moving a
learner to a new mesh is "build a twin on the new mesh, hand it the
state". The twin's learn program compiles at its first step like any
other program: where ``utils/platform.ensure_compile_cache()`` placed
jax's persistent compilation cache (``JAX_COMPILATION_CACHE_DIR`` to
share one across the fleet) and an earlier process ran the same
program on the same geometry, the backend compile is a retrieval; the
trace and the lowering are paid either way (docs/fleet.md).
"""

from __future__ import annotations

from ray_tpu.fleet.coordinator import MeshEpoch


def shadow_policy(policy, mesh):
    """A twin of ``policy`` on ``mesh``: same class, same config, same
    seed — only the mesh injection differs, so its learn program is
    exactly the one a post-resize survivor would build."""
    cfg = dict(policy.config)
    cfg["_mesh"] = mesh
    return type(policy)(
        policy.observation_space, policy.action_space, cfg
    )


def resize_policy(policy, new_mesh):
    """The live-resize primitive: re-home a learner onto a new mesh
    geometry under the PR-10 reshard contract. Builds the twin on
    ``new_mesh`` and hands it the full state (params, opt_state,
    coefficient schedule, step counters) — ``set_state``'s
    ``_tree_to_device`` re-places every leaf per the twin's sharding
    rules, so the transfer is bitwise and training continues exactly
    where the old geometry stopped."""
    import time

    from ray_tpu.telemetry import fleetview
    from ray_tpu.util import tracing

    # collective drain point + recovery-lane span: every survivor
    # resizes in lockstep, so the fleet aggregator can name the host
    # that finished re-homing last (telemetry/fleetview.py)
    t0 = time.time()
    twin = shadow_policy(policy, new_mesh)
    twin.set_state(policy.get_state())
    fleetview.record_arrival("resize")
    tracing.record_span(
        "recovery:resize",
        t0,
        time.time(),
        devices=int(
            getattr(
                getattr(new_mesh, "devices", None), "size", 0
            )
        ),
    )
    return twin


def resync_epoch(kv, current_gen: int, timeout: float = 30.0) -> MeshEpoch:
    """Catch up with the fleet after an absence (a parked partition, a
    coordinator failover window): follow the epoch pointer to the
    LATEST generation ≥ ``current_gen`` and return its record. The
    pointer is written after the record (coordinator invariant), so a
    readable pointer always resolves. A host that finds the returned
    generation differs from ``current_gen`` must rebuild via
    ``resize_policy``/``epoch_mesh`` before stepping — its old epoch's
    barriers are dead keys that can never complete."""
    from ray_tpu.fleet.coordinator import K_EPOCH_PTR, epoch_key

    gen = int(kv.get(K_EPOCH_PTR, timeout=timeout))
    if gen < current_gen:
        # a fresh KV (post-crash, unpersisted) can point backwards;
        # our generation knowledge wins — wait for the fleet to catch
        # up to where we already were
        gen = current_gen
    return MeshEpoch.from_dict(kv.get(epoch_key(gen), timeout=timeout))


def epoch_mesh(epoch: MeshEpoch):
    """The mesh for one :class:`MeshEpoch`. A single-host epoch builds
    over this process's local devices (the survivor path of a shrink —
    no cross-host collectives, no jax.distributed dependency). A
    multi-host epoch builds over the global device view, which
    requires the jax.distributed runtime to already span exactly the
    epoch's hosts: growing or re-pairing live processes is a process
    restart (the persistent compilation cache serves its backend
    compiles), not an in-process rewire."""
    import jax

    from ray_tpu import sharding as sharding_lib

    if epoch.num_processes == 1:
        return sharding_lib.get_mesh(devices=jax.local_devices())
    if jax.process_count() != epoch.num_processes:
        raise RuntimeError(
            f"epoch gen={epoch.gen} names {epoch.num_processes} "
            f"hosts but this jax runtime spans "
            f"{jax.process_count()} processes — restart the fleet "
            "at the new geometry"
        )
    return sharding_lib.get_mesh(devices=jax.devices())
