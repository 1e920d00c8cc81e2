"""Subprocess entry for the 2-process DCN test (launched by
test_multihost.py with JAX_PLATFORMS=cpu and a 2-device virtual host).

Since PR 17 this is a thin driver over ray_tpu.fleet: rank 0 runs the
FleetCoordinator (single-writer membership + epoch authority), every
rank runs a HostAgent (join/heartbeat/epoch observation/barriers), and
the elastic half is the real drain choreography — provider notice →
coordinator cuts epoch gen+1 → lockstep drain step → barrier → the
survivor rebuilds via fleet.resize_policy on fleet.epoch_mesh, with
bitwise post-reshard params and a learn step on the new mesh.

Since PR 19 a chaos stage runs between the observability rung and the
drain: rank 0's coordinator "crashes" without releasing its lease, a
standby on rank 1 wins the fenced takeover at term 2 once the TTL
runs out, training resumes on the same mesh (bitwise params, zero
fresh compiles), and the revived ex-coordinator's stale-term write is
rejected at the store — so the later drain/resize runs under a
control plane that has already failed over twice.

Exercises: jax.distributed bring-up, a global mesh psum across hosts,
cross-host weight broadcast, put_global batch placement, fleet
rendezvous + epochs + drain + barrier, fenced coordinator failover,
live resize as a restart at the new geometry.
"""

import os
import sys


def main() -> None:
    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu import fleet
    from ray_tpu.parallel import distributed as dist

    rank = int(os.environ["RAY_TPU_PROCESS_ID"])
    dist.initialize()
    assert dist.process_count() == 2, dist.process_count()
    assert dist.process_index() == rank
    assert jax.local_device_count() == 2
    assert jax.device_count() == 4

    # ---- fleet rendezvous: HostAgents announce, the coordinator
    # (rank 0 only — single writer) registers them and cuts epoch 1 ----
    kv = fleet.KVClient(os.environ["RAY_TPU_KV_ADDRESS"])
    coord = fleet.FleetCoordinator(kv) if rank == 0 else None
    agent = fleet.HostAgent(
        kv, f"host{rank}", rank_hint=rank, heartbeat_interval=1.0
    )
    agent.join()  # blocks on the coordinator's readiness flag
    if rank == 0:
        members = coord.wait_for_members(2, timeout=60.0)
        assert sorted(members) == ["host0", "host1"], members
        coord.propose_epoch(reason="bootstrap")
    epoch1 = agent.wait_for_epoch(1)
    assert epoch1.hosts == ("host0", "host1"), epoch1
    assert epoch1.rank_of(f"host{rank}") == rank

    # ---- data plane: the epoch's mesh is the global (DCN) mesh ----
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu import sharding as sharding_lib

    mesh = fleet.epoch_mesh(epoch1)
    assert len(mesh.devices.flat) == 4
    axis = sharding_lib.data_axis(mesh)

    x = jnp.ones((4,), jnp.float32)  # one row per global device
    sharded = jax.device_put(x, NamedSharding(mesh, P(axis)))
    out = jax.jit(
        jax.shard_map(
            lambda v: jax.lax.psum(v, axis),
            mesh=mesh,
            in_specs=P(axis),
            out_specs=P(),
        )
    )(sharded)
    total = float(np.asarray(out)[0])
    assert total == 4.0, total

    # ---- cross-host weight broadcast ----
    weights = {
        "w": jnp.full((3,), float(rank + 1)),
        "b": jnp.asarray(float(rank * 10)),
    }
    synced = dist.broadcast_weights(weights)
    np.testing.assert_allclose(np.asarray(synced["w"]), 1.0)
    assert float(synced["b"]) == 0.0  # process 0's values everywhere

    # ---- multi-controller learner: PPO SGD nest over the GLOBAL mesh,
    # batch placed via sharding.put_global (each process ships its
    # local box); gradient pmean spans hosts (DCN) ----
    import gymnasium as gym

    from ray_tpu.algorithms.ppo.ppo import PPOJaxPolicy
    from ray_tpu.data.sample_batch import SampleBatch

    obs_space = gym.spaces.Box(-1.0, 1.0, (8,), np.float32)
    act_space = gym.spaces.Discrete(4)
    B = 8  # global rows; 2 per device
    config = {
        "_mesh": mesh,
        "model": {"fcnet_hiddens": [16]},
        "train_batch_size": B,
        "sgd_minibatch_size": B,
        "num_sgd_iter": 1,
        "lr": 1e-3,
        "seed": 0,  # identical init on every process
    }
    policy = PPOJaxPolicy(obs_space, act_space, config)
    data_rng = np.random.default_rng(42)  # same stream on all hosts
    host_batch = {
        SampleBatch.OBS: data_rng.standard_normal((B, 8)).astype(
            np.float32
        ),
        SampleBatch.ACTIONS: data_rng.integers(0, 4, B).astype(
            np.int64
        ),
        SampleBatch.ACTION_LOGP: np.full(B, -1.4, np.float32),
        SampleBatch.ACTION_DIST_INPUTS: data_rng.standard_normal(
            (B, 4)
        ).astype(np.float32),
        SampleBatch.ADVANTAGES: data_rng.standard_normal(B).astype(
            np.float32
        ),
        SampleBatch.VALUE_TARGETS: data_rng.standard_normal(B).astype(
            np.float32
        ),
    }
    tree, bsize = policy.prepare_batch(SampleBatch(host_batch))
    # every process passes the same global host value; put_global
    # ships each process's addressable box (the lockstep contract)
    global_batch = {
        k: sharding_lib.put_global(v, policy.data_sharding)
        for k, v in tree.items()
    }
    stats = policy.learn_on_device_batch(global_batch, bsize)
    assert np.isfinite(stats["total_loss"]), stats
    # identical data + params + lockstep pmean => identical loss
    kv.put(f"fleet_test/loss_{rank}", stats["total_loss"])
    other_loss = kv.get(f"fleet_test/loss_{1 - rank}", timeout=60.0)
    assert abs(other_loss - stats["total_loss"]) < 1e-5

    # ---- fleet observability rung (PR 18): every rank runs a
    # HostExporter, rank 0 the subscribing FleetAggregator; rank 1
    # arrives late at an epoch barrier ON PURPOSE, and the aggregator
    # must attribute it by name from the KV arrival records ----
    import time as _time

    from ray_tpu.telemetry import fleetview

    aggregator = (
        fleetview.FleetAggregator(kv=kv, publish_aggregate=False)
        if rank == 0
        else None
    )
    exporter = fleetview.HostExporter(kv, f"host{rank}", interval=0)
    exporter.flush()  # snapshot (clock handshake included) pre-barrier
    if rank == 0:
        # pubsub drops messages published before the subscription
        # registers: re-flush until our own snapshot round-trips, so
        # the subscriber is provably live before any barrier publish
        deadline = _time.monotonic() + 30.0
        while "host0" not in aggregator.hosts():
            if _time.monotonic() >= deadline:
                raise TimeoutError("fleetview subscription not live")
            exporter.flush()
            _time.sleep(0.05)
    if rank == 1:
        _time.sleep(0.4)  # the deliberate straggler
    agent.barrier("fleetobs", epoch1)
    if rank == 0:
        deadline = _time.monotonic() + 30.0
        while True:
            recs = [
                r
                for r in aggregator.barrier_history
                if r["name"] == "fleetobs"
            ]
            if recs:
                break
            if _time.monotonic() >= deadline:
                raise TimeoutError("barrier never attributed")
            _time.sleep(0.05)
        rec = recs[0]
        assert rec["straggler"] == "host1", rec
        assert rec["waits"]["host0"] >= 0.2, rec
        assert rec["waits"]["host1"] == 0.0, rec
        print(f"FLEETOBS_STRAGGLER {rec['straggler']}")
        if len(aggregator.hosts()) < 2:
            # host1's publish may have raced the subscription start;
            # its durable per-host key (written by the same flush) is
            # the late-joiner path
            aggregator.ingest(
                kv.get(fleetview.snapshot_key("host1"), timeout=30.0)
            )
        text = aggregator.merged_exposition()
        assert 'host="host0"' in text and 'host="host1"' in text
        assert (
            'ray_tpu_fleet_straggler_total{host="host1"} 1.0' in text
        )
        print("FLEETOBS_MERGED 2 hosts")
        aggregator.stop()
    exporter.stop()

    # ---- chaos stage (PR 19): the coordinator dies mid-training and
    # a fenced standby takes over. rank 0's coordinator "crashes"
    # (renew loop stops, lease NOT released — exactly a SIGKILL, the
    # TTL has to run out); rank 1's standby wins the lease at term 2,
    # rebuilds the member/epoch mirror from the durable KV table, and
    # cuts the failover epoch over the SAME hosts. Training resumes on
    # the unchanged mesh — params untouched, zero fresh compiles —
    # because the coordinator was never on the data path. The revived
    # ex-coordinator then proves the fence: its stale-term write is
    # rejected at the store (split-brain counter-proof). ----
    import hashlib

    lease_ttl = float(os.environ.get(fleet.LEASE_TTL_ENV, "10.0"))
    fn_before = policy.learn_fn(bsize)
    traces_before = fn_before.traces
    if rank == 0:
        info = kv.lease_info(fleet.LEASE_NAME)
        assert info["term"] == 1 and info["holder"], info
        coord.stop(release_lease=False)  # crash: lease left to expire
        kv.put("fleet_test/coord_killed", _time.time())
    standby = None
    if rank == 1:
        kv.get("fleet_test/coord_killed", timeout=60.0)
        t0 = _time.monotonic()
        standby = fleet.FleetCoordinator(
            kv, standby=True, lease_ttl=lease_ttl, holder="host1-standby"
        )
        term = standby.acquire_leadership(timeout=60.0)
        failover_wall = _time.monotonic() - t0
        assert term == 2 and standby.is_leader, (term, standby.is_leader)
        # warm-cache restart of the control plane: the mirror came
        # back from the persisted KV table, not from re-rendezvous
        assert sorted(standby.members()) == ["host0", "host1"]
        assert standby.current_epoch().gen == 1, standby.current_epoch()
        # failover wall is bounded by the dead incumbent's TTL plus
        # the acquire poll cadence (the --fleet-chaos contract)
        assert failover_wall < 2.0 * lease_ttl + 1.0, failover_wall
        epoch2 = standby.propose_epoch(reason="failover")
        assert epoch2.hosts == ("host0", "host1"), epoch2
        print(f"FAILOVER_OK term={term} wall={failover_wall:.2f}s")
    epoch2 = agent.wait_for_epoch(2)
    assert epoch2.gen == 2 and epoch2.hosts == ("host0", "host1")
    assert epoch2.reason == "failover", epoch2
    # training resumes in lockstep under the new leader: same mesh,
    # same compiled program, identical loss on both hosts
    chaos_stats = policy.learn_on_device_batch(global_batch, bsize)
    assert np.isfinite(chaos_stats["total_loss"]), chaos_stats
    kv.put(f"fleet_test/chaos_loss_{rank}", chaos_stats["total_loss"])
    other_chaos = kv.get(
        f"fleet_test/chaos_loss_{1 - rank}", timeout=60.0
    )
    assert abs(other_chaos - chaos_stats["total_loss"]) < 1e-5
    # zero fresh compiles across the failover window
    assert policy.learn_fn(bsize) is fn_before
    assert fn_before.traces == traces_before, (
        fn_before.traces,
        traces_before,
    )
    # post-resume params bitwise identical across hosts (lockstep
    # held through the leadership change)
    digest = hashlib.sha256()
    for k in sorted(policy.get_weights()):
        for leaf in jax.tree_util.tree_leaves(policy.get_weights()[k]):
            digest.update(np.asarray(leaf).tobytes())
    kv.put(f"fleet_test/chaos_digest_{rank}", digest.hexdigest())
    assert (
        kv.get(f"fleet_test/chaos_digest_{1 - rank}", timeout=60.0)
        == digest.hexdigest()
    )
    print("CHAOS_BITWISE_OK params identical, zero fresh compiles")
    if rank == 0:
        # the revived ex-coordinator acts at its dead term — the store
        # must fence it, and the fenced write flips is_leader off
        try:
            coord._put(
                "fleet/members", {"zombie": {"rank_hint": None}}
            )
            raise AssertionError("stale-term write was accepted")
        except fleet.StaleTermError:
            pass
        assert not coord.is_leader
        info = kv.lease_info(fleet.LEASE_NAME)
        assert info["term"] == 2, info
        assert info["fenced_writes"] >= 1, info
        print("FENCED_OK stale term rejected")
        kv.put("fleet_test/fence_proved", True)
    if rank == 1:
        # failback: the clean-stop path releases the lease, so rank
        # 0's re-acquire is immediate (no TTL wait) at term 3 — the
        # drain stage below runs under a twice-failed-over control
        # plane
        kv.get("fleet_test/fence_proved", timeout=60.0)
        standby.stop(release_lease=True)
        kv.put("fleet_test/failback", True)
    if rank == 0:
        kv.get("fleet_test/failback", timeout=60.0)
        coord = fleet.FleetCoordinator(kv, lease_ttl=lease_ttl)
        assert coord.term == 3 and coord.is_leader
        assert sorted(coord.members()) == ["host0", "host1"]
        assert coord.current_epoch().gen == 2, coord.current_epoch()
        kv.put("fleet_test/failback_done", True)
    # pubsub only reaches live subscribers: host1 must not announce
    # its notice until the failed-back coordinator's subscriber is
    # provably registered
    kv.get("fleet_test/failback_done", timeout=60.0)

    # ---- elastic resize: provider notice for host1 → coordinator
    # drains epoch 2 and cuts epoch 3 → one final lockstep superstep →
    # barrier → host0 rebuilds at the surviving geometry ----
    if rank == 1:
        # the "eviction notice" lands as a provider file (the DIR
        # source of resilience/provider_notice.py), the agent forwards
        # it to the coordinator
        from ray_tpu.resilience import provider_notice

        notice_dir = os.environ.get(
            provider_notice.NOTICE_DIR_ENV, ""
        )
        if notice_dir:
            with open(
                os.path.join(notice_dir, "host1"), "w"
            ) as f:
                f.write("60.0")  # grace seconds
            grace = provider_notice.probe(host="host1")
            assert grace == 60.0, grace
        agent.announce_notice(reason="preempted")
    if rank == 0:
        # driver loop: apply the notice event; handle_notice posts the
        # drain record and cuts epoch 2
        import time as _time

        deadline = _time.monotonic() + 60.0
        while agent.poll_drain(2) is None:
            coord.reconcile()
            if _time.monotonic() >= deadline:
                raise TimeoutError("drain record never posted")
            _time.sleep(0.05)
    # the lockstep anchor: every host observes the same drain record
    # before its next superstep
    drain = agent.await_drain(2)
    assert drain["victims"] == ["host1"], drain
    # the drain step: one last lockstep update over the global mesh so
    # the departing host's in-flight contribution is not lost
    drain_stats = policy.learn_on_device_batch(global_batch, bsize)
    assert np.isfinite(drain_stats["total_loss"]), drain_stats
    kv.put(f"fleet_test/drain_loss_{rank}", drain_stats["total_loss"])
    other_drain = kv.get(
        f"fleet_test/drain_loss_{1 - rank}", timeout=60.0
    )
    assert abs(other_drain - drain_stats["total_loss"]) < 1e-5
    agent.barrier("drained", epoch2)

    if rank == 1:
        # the victim idles out its grace period (no more collectives),
        # staying up until the survivor finishes so jax.distributed
        # teardown is orderly
        agent.leave()
        kv.get("fleet_test/solo_done", timeout=120.0)
        agent.stop()
        print(f"MULTIHOST_OK rank={rank}")
        return

    # ---- host0 survives the shrink: epoch 3 names it alone; the
    # resize is a restart at the new geometry (PR-10 reshard) --
    epoch3 = agent.wait_for_epoch(3)
    assert epoch3.gen == 3 and epoch3.hosts == ("host0",), epoch3
    new_mesh = fleet.epoch_mesh(epoch3)  # local devices, no DCN
    assert len(new_mesh.devices.flat) == 2
    survivor = fleet.resize_policy(policy, new_mesh)
    # params bitwise across the reshard (replicated => addressable)
    w_old, w_new = policy.get_weights(), survivor.get_weights()
    for k in w_old:
        for a, b in zip(
            jax.tree_util.tree_leaves(w_old[k]),
            jax.tree_util.tree_leaves(w_new[k]),
        ):
            assert (
                np.asarray(a).tobytes() == np.asarray(b).tobytes()
            ), f"reshard not bitwise: {k}"
    print("RESHARD_BITWISE_OK")
    solo_stats = survivor.learn_on_batch(SampleBatch(host_batch))
    assert np.isfinite(solo_stats["total_loss"]), solo_stats
    print("ELASTIC_OK survivor continued on local mesh")
    kv.put("fleet_test/solo_done", True)
    coord.stop()
    agent.stop()
    print(f"MULTIHOST_OK rank={rank}")


if __name__ == "__main__":
    main()
