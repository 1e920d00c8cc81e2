"""Device rollout lane: JaxVectorEnv API, lane parity, fused superstep.

Covers the docs/pipeline.md "two rollout lanes" contract:

- auto-reset terminal-observation semantics (final obs vs reset obs)
  on both lanes;
- fixed-seed lane parity: the jax lane and the CPU-actor lane produce
  IDENTICAL trajectory streams (obs/actions/rewards/dones bitwise) and
  matching post-GAE train batches on the same env (the ROADMAP
  contract);
- fused rollout+learn superstep ≡ rollout-then-learn dispatches;
- zero recompiles across iterations for the fused program;
- device-side replay insert keeps the host generator / sum-tree
  streams bit-exact;
- telemetry: ray_tpu_env_steps_on_device_total + the per-iteration
  rollout_lane roll-up.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from ray_tpu.algorithms.ppo.ppo import PPOConfig, PPOJaxPolicy
from ray_tpu.data.sample_batch import SampleBatch
from ray_tpu.env.jax_control import CartPoleJax, GridRoomsJax
from ray_tpu.env.jax_env import JaxVectorEnvAdapter
from ray_tpu.env.jax_pong import PongLiteJax
from ray_tpu.evaluation.rollout_worker import RolloutWorker
from ray_tpu.execution.jax_rollout import JaxRolloutEngine


def _one_shard_mesh():
    """Lane parity is asserted on a 1-shard mesh: on multi-shard
    meshes the device lane's per-shard action forward runs at a
    different matmul shape than the host lane's full-batch forward,
    and the last ulp can flip a sampled action (the same XLA property
    test_superstep documents for cross-program collective lowering).
    Same-device streams are bitwise — docs/data_plane.md."""
    from ray_tpu import sharding as sharding_lib

    return sharding_lib.get_mesh(devices=jax.devices()[:1])


def _ppo_cfg(one_shard=False, **over):
    cfg = PPOConfig().to_dict()
    cfg.update(
        seed=5,
        num_workers=0,
        num_envs_per_worker=8,
        rollout_fragment_length=8,
        train_batch_size=64,
        sgd_minibatch_size=32,
        num_sgd_iter=2,
        lr=3e-4,
        model={"fcnet_hiddens": [32, 32]},
    )
    cfg["lambda"] = 0.95
    if one_shard:
        cfg["_mesh"] = _one_shard_mesh()
    cfg.update(over)
    return cfg


def _policy(env, cfg):
    return PPOJaxPolicy(env.observation_space, env.action_space, cfg)


# -- env API / auto-reset contract -------------------------------------


def test_adapter_steps_without_autoreset():
    """The env itself never auto-resets: past a truncation the host
    lane sees the FINAL observation until the sampler calls
    reset_at (the terminal-observation contract of env/jax_env.py)."""
    ad = JaxVectorEnvAdapter(CartPoleJax({"max_steps": 3}), 2, seed=1)
    ad.vector_reset()
    for i in range(3):
        obs, rew, term, trunc, _ = ad.vector_step(
            [np.int32(0), np.int32(1)]
        )
    assert trunc == [True, True]
    final = np.asarray(obs[0])
    reset_obs, _ = ad.reset_at(0)
    # reset draws a fresh ±0.05 state from the carried key stream
    assert not np.array_equal(final, reset_obs)
    assert np.all(np.abs(reset_obs) <= 0.05)


def test_device_lane_autoreset_contract():
    """Device lane rows around an episode boundary: NEXT_OBS is the
    final (pre-reset) obs, the successor row's OBS the reset obs, and
    the per-episode step counter restarts."""
    env = CartPoleJax({"max_steps": 3})
    pol = _policy(env, _ppo_cfg())
    eng = JaxRolloutEngine(
        pol, env, 8, 7, seed=5, standardize_advantages=False
    )
    batch, _ = eng.rollout()
    host = jax.device_get(batch)
    t = host["t"].reshape(8, 7)
    dones = (host["dones"] | host["truncateds"]).reshape(8, 7)
    obs = host["obs"].reshape(8, 7, 4)
    new_obs = host["new_obs"].reshape(8, 7, 4)
    assert np.array_equal(t[0], [0, 1, 2, 0, 1, 2, 0])
    assert dones[:, 2].all() and dones[:, 5].all()
    for i in range(8):
        # successor OBS is the reset draw, not the terminal obs
        assert not np.array_equal(new_obs[i, 2], obs[i, 3])
        assert np.all(np.abs(obs[i, 3]) <= 0.05)
        # non-boundary rows chain: NEXT_OBS[t] == OBS[t+1]
        assert np.array_equal(new_obs[i, 0], obs[i, 1])


def test_pong_lite_jax_smoke():
    ad = JaxVectorEnvAdapter(
        PongLiteJax({"rallies": 2, "max_steps": 80}), 2, seed=3
    )
    obs, _ = ad.vector_reset()
    assert obs[0].shape == (84, 84, 1) and obs[0].dtype == np.uint8
    assert obs[0].max() == 255  # ball rendered
    rewards, done_seen = set(), False
    for _ in range(80):
        obs, rew, term, trunc, _ = ad.vector_step(
            [np.int32(1), np.int32(2)]
        )
        rewards.update(rew)
        for i in range(2):
            if term[i] or trunc[i]:
                done_seen = True
                ad.reset_at(i)
    assert done_seen
    assert rewards <= {-1.0, 0.0, 1.0} and len(rewards) > 1


# -- fixed-seed lane parity --------------------------------------------


def test_lane_parity_trajectories_and_gae():
    """The ROADMAP contract: jax lane ≡ CPU-actor lane at small scale.
    Trajectory streams (obs/actions/rewards/done/logp/dist-inputs)
    match BITWISE; the GAE columns match to float tolerance (the value
    tower's last ulp moves when XLA fuses it with the in-program
    bootstrap forward — documented in docs/data_plane.md)."""
    cfg = _ppo_cfg(one_shard=True)
    rw = RolloutWorker(
        env_creator=lambda c: CartPoleJax(dict(c)),
        policy_cls=PPOJaxPolicy,
        config=cfg,
        worker_index=0,
        num_workers=0,
    )
    host_batch = rw.sampler.sample()

    env = CartPoleJax({})
    pol = _policy(env, dict(cfg))
    eng = JaxRolloutEngine(
        pol, env, 8, 8, seed=5, standardize_advantages=False
    )
    dev = jax.device_get(eng.rollout()[0])

    assert host_batch.count == 64 == len(dev["obs"])
    # align host rows env-major (stable sort keeps time order per env)
    order = np.argsort(
        np.asarray(host_batch["agent_index"]), kind="stable"
    )

    def col(name):
        return np.asarray(host_batch[name])[order]

    for name in (
        "obs",
        "actions",
        "rewards",
        "dones",
        "truncateds",
        "new_obs",
        "t",
        "agent_index",
        "action_logp",
        "action_dist_inputs",
    ):
        h, d = col(name), np.asarray(dev[name])
        assert np.array_equal(h.astype(d.dtype), d), name
    np.testing.assert_allclose(
        col("vf_preds"), dev["vf_preds"], atol=1e-6
    )
    for name in ("advantages", "value_targets"):
        np.testing.assert_allclose(
            col(name), dev[name], atol=1e-5, err_msg=name
        )

    # post-standardize train batch (what the nest consumes): a fresh
    # identically-seeded policy+engine with in-program standardization
    adv = np.asarray(host_batch["advantages"], np.float32)
    host_std = (adv - adv.mean()) / max(1e-4, adv.std())
    pol2 = _policy(env, dict(cfg))
    eng2 = JaxRolloutEngine(
        pol2, env, 8, 8, seed=5, standardize_advantages=True
    )
    dev2 = jax.device_get(eng2.rollout()[0])
    np.testing.assert_allclose(
        host_std[order], dev2["advantages"], atol=2e-5
    )


def test_fused_superstep_matches_unfused_dispatches():
    """rollout+learn fused into one program ≡ rollout dispatch then
    learn dispatch, on the same seed (params to ~last-ulp — the
    scan-vs-standalone property documented for the superstep)."""

    def run(fused):
        env = CartPoleJax({})
        pol = _policy(env, _ppo_cfg())
        eng = JaxRolloutEngine(pol, env, 8, 8, seed=5)
        if fused:
            feed = eng.superstep_feed()
            infos, carry, metrics, _ = pol.learn_rollout_superstep(
                1, 64, feed, k_max=1
            )
            eng.advance(carry, metrics)
        else:
            batch, bsize = eng.rollout()
            pol.learn_on_device_batch(
                eng.learn_batch(batch), bsize
            )
        return pol.get_weights()

    wa, wb = run(True), run(False)
    for a, b in zip(
        jax.tree_util.tree_leaves(wa), jax.tree_util.tree_leaves(wb)
    ):
        np.testing.assert_allclose(a, b, atol=1e-7)


# -- algorithm integration ---------------------------------------------


def _build_ppo(backend, fused=True, env_config=None, **over):
    cfg = (
        PPOConfig()
        .environment(
            "CartPoleJax-v0",
            env_config=env_config or {},
            env_backend=backend,
            jax_fused_rollout=fused,
        )
        .rollouts(
            num_rollout_workers=0,
            num_envs_per_worker=8,
            rollout_fragment_length=8,
        )
        .training(
            train_batch_size=64,
            sgd_minibatch_size=32,
            num_sgd_iter=2,
            lr=3e-4,
            model={"fcnet_hiddens": [32, 32]},
        )
        .debugging(seed=5)
    )
    cfg.lambda_ = 0.95
    for k, v in over.items():
        setattr(cfg, k, v)
    return cfg.build()


def test_ppo_jax_lane_lifecycle():
    """One jax-lane PPO through the full Algorithm: counters, episode
    metrics via the device readback, ZERO recompiles across
    iterations (the fused program's acceptance criterion), and the
    telemetry roll-up — one build, one compile."""
    from ray_tpu.sharding.compile import compile_stats
    from ray_tpu.util import tracing

    # short episodes so completions land within a few iterations
    algo = _build_ppo("jax", env_config={"max_steps": 10})
    algo.config["telemetry_config"] = {"trace": True}
    tracing.enable()
    try:
        algo.train()  # warmup: traces the fused program
        before = compile_stats()["traces"]
        for _ in range(3):
            r = algo.train()
        assert compile_stats()["traces"] == before  # zero recompiles
        assert r["num_env_steps_sampled"] == 256
        info = r["info"]["learner"]["default_policy"]
        assert np.isfinite(info["total_loss"])
        # episode metrics came back through the device readback
        assert r["episodes_total"] > 0
        lane = r["info"]["telemetry"]["rollout_lane"]
        assert lane["backend"] == "jax"
        assert lane["env_steps"] == 64
        # the lane's H2D is key stacks only — a few hundred bytes vs
        # the >10 KB an actor-lane train batch moves at this geometry
        assert 0 < lane["h2d_bytes"] < 4096
        from ray_tpu.telemetry.metrics import (
            ENV_STEPS_ON_DEVICE_TOTAL,
            counter_total,
        )

        assert counter_total(ENV_STEPS_ON_DEVICE_TOTAL) >= 256
    finally:
        tracing.disable()
        algo.cleanup()


@pytest.mark.slow  # ~6 s; fails at seed under jax 0.9.0, passes since
# PR 21 — moved out of tier-1 by that PR's budget rule (the newly
# passing tests compile and run where they used to fail at trace
# time; only newly passing ones may leave); tier-1 keeps the
# fused-vs-unfused and trajectory/GAE lane parity pins
def test_ppo_lane_episode_parity_e2e():
    """Both lanes through the full Algorithm: identical episode
    stream (same env seeds, same action stream) on one iteration."""
    a = _build_ppo(
        "actor", env_config={"max_steps": 6}, learner_devices=1
    )
    b = _build_ppo(
        "jax", env_config={"max_steps": 6}, learner_devices=1
    )
    try:
        ra, rb = a.train(), b.train()
        assert (
            ra["episodes_this_iter"] == rb["episodes_this_iter"] > 0
        )
        assert ra["episode_reward_mean"] == rb["episode_reward_mean"]
        assert ra["num_env_steps_sampled"] == rb[
            "num_env_steps_sampled"
        ]
    finally:
        a.cleanup()
        b.cleanup()


# -- device-side replay insert -----------------------------------------


def _rows(n, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "obs": rng.standard_normal((n, 4)).astype(np.float32),
        "new_obs": rng.standard_normal((n, 4)).astype(np.float32),
        "actions": rng.integers(0, 2, n).astype(np.int32),
        "rewards": rng.standard_normal(n).astype(np.float32),
        "dones": rng.random(n) < 0.1,
    }


def test_device_insert_bit_exact_vs_host_insert():
    """add_device_tree(rows already on device) ≡ add_tree(host rows):
    stored rings, ring bookkeeping, and the subsequent host index-draw
    stream are bit-identical — the carried-forward data-plane
    contract (host generator untouched by inserts)."""
    from ray_tpu.execution.replay_buffer import DeviceReplayBuffer

    rows = _rows(24, seed=1)
    b1 = DeviceReplayBuffer(capacity=32, seed=9)
    b2 = DeviceReplayBuffer(capacity=32, seed=9)
    b1.add_tree(dict(rows))
    b2.add_device_tree(jax.device_put(dict(rows)))
    s1, s2 = b1.get_state(), b2.get_state()
    assert s1["idx"] == s2["idx"] and s1["size"] == s2["size"]
    for k in s1["cols"]:
        assert np.array_equal(s1["cols"][k], s2["cols"][k]), k
    for _ in range(3):
        g1, g2 = b1.sample(8), b2.sample(8)
        assert np.array_equal(g1.indices, g2.indices)
        for k in g1.tree:
            assert np.array_equal(
                np.asarray(g1.tree[k]), np.asarray(g2.tree[k])
            ), k


def test_device_insert_prioritized_streams_bit_exact():
    from ray_tpu.execution.replay_buffer import (
        DevicePrioritizedReplayBuffer,
    )

    rows = _rows(16, seed=2)
    b1 = DevicePrioritizedReplayBuffer(capacity=32, seed=4)
    b2 = DevicePrioritizedReplayBuffer(capacity=32, seed=4)
    b1.add_tree(dict(rows))
    b2.add_device_tree(jax.device_put(dict(rows)))
    idx = np.arange(16)
    assert np.array_equal(b1._sum_tree[idx], b2._sum_tree[idx])
    assert b1._max_priority == b2._max_priority
    # same draw + IS-weight stream, priorities updated identically
    s1, s2 = b1.sample(8, beta=0.4), b2.sample(8, beta=0.4)
    assert np.array_equal(s1.indices, s2.indices)
    assert np.array_equal(
        np.asarray(s1.tree["weights"]), np.asarray(s2.tree["weights"])
    )
    pri = np.abs(np.random.default_rng(0).standard_normal(8)) + 1e-3
    b1.update_priorities(s1.indices, pri)
    b2.update_priorities(s2.indices, pri)
    assert np.array_equal(b1._sum_tree[idx], b2._sum_tree[idx])


def test_dqn_jax_lane_fills_device_rings():
    from ray_tpu.algorithms.dqn.dqn import DQNConfig

    cfg = (
        DQNConfig()
        .environment("GridRoomsJax-v0", env_backend="jax")
        .rollouts(
            num_rollout_workers=0,
            num_envs_per_worker=8,
            rollout_fragment_length=8,
        )
        .training(
            train_batch_size=32,
            lr=1e-3,
            replay_device_resident=True,
            model={"fcnet_hiddens": [32, 32]},
        )
        .debugging(seed=3)
    )
    # fill-path test: learning never starts, so only the rollout
    # program compiles (learning from device rings is covered by
    # tests/test_device_replay.py)
    cfg.num_steps_sampled_before_learning_starts = 10 ** 9
    algo = cfg.build()
    try:
        for _ in range(2):
            r = algo.train()
        assert r["num_env_steps_sampled"] == 128
        buf = algo.local_replay_buffer.buffers["default_policy"]
        assert buf.stats()["device_resident"]
        assert len(buf) == 128
    finally:
        algo.cleanup()
