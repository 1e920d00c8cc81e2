"""ray_tpu.fleet tests: membership/epoch/drain units against a real
in-process KV server (no meshes needed — the coordinator is driver
logic over KV records), the elastic resize primitives, and the
per-host provider-notice source.

Tier-1 keeps the coordinator protocol units and the fake-policy resize
sibling; the full PPO resize rungs live in the slow tier
(test_resize_single_process here, and the 2-process
test_two_process_dcn_cluster in test_multihost.py) per the PR-1 test
budget rule.
"""

import os
import threading
import time

import numpy as np
import pytest

from ray_tpu import fleet
from ray_tpu.fleet.coordinator import (
    K_EPOCH_PTR,
    K_MEMBERS,
    drain_key,
    epoch_key,
)


@pytest.fixture()
def kv():
    server = fleet.KVServer(host="127.0.0.1")
    client = fleet.KVClient(f"127.0.0.1:{server.port}")
    yield client
    server.shutdown()


# ---------------------------------------------------------------------------
# MeshEpoch
# ---------------------------------------------------------------------------


def test_mesh_epoch_roundtrip():
    epoch = fleet.MeshEpoch(
        gen=3, hosts=("a", "b"), reason="resize", created_at=1.0
    )
    assert epoch.num_processes == 2
    assert epoch.rank_of("b") == 1
    again = fleet.MeshEpoch.from_dict(epoch.to_dict())
    assert again == epoch


# ---------------------------------------------------------------------------
# FleetCoordinator: driver-injected events (no pubsub, no meshes)
# ---------------------------------------------------------------------------


def test_coordinator_register_and_epoch(kv):
    coord = fleet.FleetCoordinator(kv, subscribe=False)
    coord.register_host("host1", rank_hint=1)
    coord.register_host("host0", rank_hint=0)
    epoch = coord.propose_epoch(reason="bootstrap")
    # rank order is (rank_hint, host), not registration order
    assert epoch.gen == 1
    assert epoch.hosts == ("host0", "host1")
    # the KV mirror a late-joining reader would see
    assert sorted(kv.get(K_MEMBERS)) == ["host0", "host1"]
    assert kv.get(K_EPOCH_PTR) == 1
    assert fleet.MeshEpoch.from_dict(kv.get(epoch_key(1))) == epoch


def test_coordinator_recovers_from_kv(kv):
    first = fleet.FleetCoordinator(kv, subscribe=False)
    first.register_host("host0", rank_hint=0)
    first.propose_epoch()
    # a restarted coordinator resumes members AND generation
    second = fleet.FleetCoordinator(kv, subscribe=False)
    assert sorted(second.members()) == ["host0"]
    assert second.current_epoch().gen == 1
    assert second.propose_epoch().gen == 2


def test_notice_drains_and_cuts_next_epoch(kv):
    coord = fleet.FleetCoordinator(kv, subscribe=False)
    coord.register_host("host0", rank_hint=0)
    coord.register_host("host1", rank_hint=1)
    coord.propose_epoch(reason="bootstrap")
    epoch2 = coord.handle_notice("host1", reason="preempted")
    # drain record posted against the generation being torn down
    drain = kv.get(drain_key(1))
    assert drain["victims"] == ["host1"]
    assert drain["reason"] == "preempted"
    assert epoch2.gen == 2 and epoch2.hosts == ("host0",)
    # idempotent per victim: a duplicate notice is a no-op
    assert coord.handle_notice("host1") is None
    assert kv.get(K_EPOCH_PTR) == 2


def test_heartbeat_expiry_is_a_kill_notice(kv):
    coord = fleet.FleetCoordinator(kv, subscribe=False)
    coord.register_host("alive", rank_hint=0)
    coord.register_host("ghost", rank_hint=1)
    coord.propose_epoch()
    hb = fleet.HeartbeatReporter(kv, "alive", interval=0.1)
    time.sleep(0.3)  # let a heartbeat land; "ghost" never reports
    dead = coord.expire_dead(horizon=10.0)
    hb.stop()
    assert dead == ["ghost"]
    assert sorted(coord.members()) == ["alive"]
    assert kv.get(drain_key(1))["reason"] == "heartbeat-expired"
    assert coord.current_epoch().hosts == ("alive",)


# ---------------------------------------------------------------------------
# The pubsub path: HostAgents rendezvous through a live coordinator
# ---------------------------------------------------------------------------


def test_agents_rendezvous_epoch_and_barrier(kv):
    coord = fleet.FleetCoordinator(kv)  # subscriber + readiness flag
    agents = [
        fleet.HostAgent(
            kv, f"host{i}", rank_hint=i, heartbeat_interval=0.2
        )
        for i in range(2)
    ]
    try:
        for a in agents:
            a.join()  # blocks on fleet/ready, so no publish is lost
        members = coord.wait_for_members(2, timeout=10.0)
        assert sorted(members) == ["host0", "host1"]
        coord.propose_epoch(reason="bootstrap")
        epoch = agents[0].wait_for_epoch(1, timeout=10.0)
        assert epoch.hosts == ("host0", "host1")
        # epoch-scoped barrier: both hosts must arrive
        errs = []

        def arrive(agent):
            try:
                agent.barrier("ready", epoch, timeout=10.0)
            except Exception as e:  # pragma: no cover
                errs.append(e)

        t = threading.Thread(target=arrive, args=(agents[1],))
        t.start()
        agents[0].barrier("ready", epoch, timeout=10.0)
        t.join(timeout=10.0)
        assert not errs
        # notice flows pubsub -> reconcile -> drain + next epoch
        agents[1].announce_notice(reason="preempted")
        deadline = time.monotonic() + 10.0
        while agents[0].poll_drain(1) is None:
            coord.reconcile()
            assert time.monotonic() < deadline, "drain never posted"
            time.sleep(0.02)
        assert agents[0].await_drain(1)["victims"] == ["host1"]
        assert agents[0].wait_for_epoch(2).hosts == ("host0",)
    finally:
        for a in agents:
            a.stop()
        coord.stop()


def test_barrier_timeout_names_missing_host(kv):
    coord = fleet.FleetCoordinator(kv, subscribe=False)
    coord.register_host("host0", rank_hint=0)
    coord.register_host("host1", rank_hint=1)
    epoch = coord.propose_epoch()
    agent = fleet.HostAgent(kv, "host0", heartbeat_interval=5.0)
    try:
        with pytest.raises(TimeoutError, match="host1"):
            agent.barrier("drained", epoch, timeout=0.3)
    finally:
        agent.stop()


# ---------------------------------------------------------------------------
# Elastic primitives (tier-1 siblings of the slow PPO resize rungs)
# ---------------------------------------------------------------------------


class _FakePolicy:
    """Minimal policy satisfying the resize_policy contract: rebuild
    from (spaces, config) and carry state through get/set_state."""

    def __init__(self, observation_space, action_space, config):
        self.observation_space = observation_space
        self.action_space = action_space
        self.config = config
        self._state = {"params": np.zeros(3, np.float32)}

    def get_state(self):
        return {k: np.copy(v) for k, v in self._state.items()}

    def set_state(self, state):
        self._state = {k: np.copy(v) for k, v in state.items()}


def test_resize_policy_carries_state_bitwise():
    pol = _FakePolicy("obs", "act", {"_mesh": "mesh8", "lr": 1e-3})
    pol._state["params"] = np.arange(3, dtype=np.float32) * 0.1
    twin = fleet.resize_policy(pol, "mesh4")
    assert twin.config["_mesh"] == "mesh4"
    assert twin.config["lr"] == 1e-3
    assert pol.config["_mesh"] == "mesh8"  # source untouched
    assert (
        twin._state["params"].tobytes()
        == pol._state["params"].tobytes()
    )


def test_epoch_mesh_single_host_is_local():
    import jax

    epoch = fleet.MeshEpoch(gen=2, hosts=("host0",))
    mesh = fleet.epoch_mesh(epoch)
    assert len(mesh.devices.flat) == len(jax.local_devices())
    # an epoch naming more hosts than the runtime spans is a restart
    wide = fleet.MeshEpoch(gen=3, hosts=("host0", "host1"))
    with pytest.raises(RuntimeError, match="restart"):
        fleet.epoch_mesh(wide)


def test_provider_notice_dir_scopes_per_host(tmp_path, monkeypatch):
    from ray_tpu.resilience import provider_notice

    monkeypatch.delenv(provider_notice.NOTICE_ENV, raising=False)
    monkeypatch.delenv(provider_notice.NOTICE_FILE_ENV, raising=False)
    monkeypatch.setenv(
        provider_notice.NOTICE_DIR_ENV, str(tmp_path)
    )
    # no file, no notice; host-agnostic probes ignore the DIR source
    assert provider_notice.probe(host="host1") is None
    assert provider_notice.probe() is None
    (tmp_path / "host1").write_text("45.5")
    assert provider_notice.probe(host="host1") == 45.5
    assert provider_notice.probe(host="host0") is None
    # unparseable content arms an evict-NOW notice
    (tmp_path / "host0").write_text("not-a-float")
    assert provider_notice.probe(host="host0") == 0.0


# ---------------------------------------------------------------------------
# Slow rung: the full resize on one process (tier-1 sibling of
# test_two_process_dcn_cluster's survivor path)
# ---------------------------------------------------------------------------


@pytest.mark.slow  # ~30 s: two PPO policy builds + their compiles; the
# protocol/primitive units above are the tier-1 siblings (PR-1 rule)
def test_resize_single_process():
    """resize_policy: params bitwise across the reshard, and the twin
    learns on the new mesh."""
    import gymnasium as gym
    import jax

    from ray_tpu import sharding as sharding_lib
    from ray_tpu.algorithms.ppo.ppo import PPOJaxPolicy
    from ray_tpu.data.sample_batch import SampleBatch

    obs_space = gym.spaces.Box(-1.0, 1.0, (8,), np.float32)
    act_space = gym.spaces.Discrete(4)
    B = 8
    mesh8 = sharding_lib.get_mesh(devices=jax.devices())
    mesh4 = sharding_lib.get_mesh(devices=jax.devices()[:4])
    policy = PPOJaxPolicy(
        obs_space,
        act_space,
        {
            "_mesh": mesh8,
            "model": {"fcnet_hiddens": [16]},
            "train_batch_size": B,
            "sgd_minibatch_size": B,
            "num_sgd_iter": 1,
            "lr": 1e-3,
            "seed": 0,
        },
    )
    rng = np.random.default_rng(42)
    host = {
        SampleBatch.OBS: rng.standard_normal((B, 8)).astype(
            np.float32
        ),
        SampleBatch.ACTIONS: rng.integers(0, 4, B).astype(np.int64),
        SampleBatch.ACTION_LOGP: np.full(B, -1.4, np.float32),
        SampleBatch.ACTION_DIST_INPUTS: rng.standard_normal(
            (B, 4)
        ).astype(np.float32),
        SampleBatch.ADVANTAGES: rng.standard_normal(B).astype(
            np.float32
        ),
        SampleBatch.VALUE_TARGETS: rng.standard_normal(B).astype(
            np.float32
        ),
    }
    policy.learn_on_batch(SampleBatch(host))
    reference = policy.get_weights()
    survivor = fleet.resize_policy(policy, mesh4)
    for k in reference:
        for a, b in zip(
            jax.tree_util.tree_leaves(reference[k]),
            jax.tree_util.tree_leaves(survivor.get_weights()[k]),
        ):
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    stats = survivor.learn_on_batch(SampleBatch(host))
    assert np.isfinite(stats["total_loss"])
