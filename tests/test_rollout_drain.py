"""The device lane's host half reads nothing back that the round is
not waiting for (docs/pipeline.md "what the host reads back"):

- ``JaxRolloutEngine.rollout`` returns with the episode metrics' read
  PENDING; the read is finished behind the next rollout's dispatch, or
  by whoever asks for what it holds, and changes no result;
- ``WorkerSet.sync_weights`` pulls the acting weights off the device
  only when a worker takes them.
"""

import types

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from ray_tpu.algorithms.ppo.ppo import PPOConfig, PPOJaxPolicy
from ray_tpu.env.jax_tokens import TokenStreamJax
from ray_tpu.evaluation.worker_set import WorkerSet
from ray_tpu.execution.jax_rollout import JaxRolloutEngine
from ray_tpu.telemetry import metrics as tm
from ray_tpu.util import tracing


def _engine(seed=5):
    from ray_tpu import sharding as sharding_lib

    cfg = PPOConfig().to_dict()
    cfg.update(
        seed=seed,
        num_workers=0,
        num_envs_per_worker=8,
        rollout_fragment_length=8,
        train_batch_size=64,
        sgd_minibatch_size=32,
        model={"fcnet_hiddens": [16]},
        _mesh=sharding_lib.get_mesh(devices=jax.devices()[:1]),
    )
    # episodes of 6 tokens, stream i one token further into its own:
    # some end in every rollout, and the env's actions are its product
    # (``report_actions``), so ``last_actions`` holds them
    env = TokenStreamJax(
        {"vocab_size": 16, "episode_length": 6, "phase_stride": 1}
    )
    pol = PPOJaxPolicy(env.observation_space, env.action_space, cfg)
    return JaxRolloutEngine(pol, env, 8, 8, seed=seed)


def _bits(tree):
    return [
        np.asarray(x).tobytes()
        for x in jax.tree_util.tree_leaves(jax.device_get(tree))
    ]


def _drains():
    d = tm.rollout_drains()
    return int(d.get("deferred", 0)), int(d.get("blocking", 0))


def test_late_reads_change_nothing():
    """(a) Eight rollouts with episodes ending in every one: an engine
    whose reads resolve late and a twin that forces ``get_metrics()``
    after every call record the same episodes in the same order and
    hold the same actions, batches and carry, bit for bit."""
    late, eager = _engine(), _engine()
    late_eps, eager_eps = [], []
    for i in range(8):
        b_late, n_late = late.rollout()
        b_eager, n_eager = eager.rollout()
        eager_eps.extend(eager.get_metrics())
        assert eager._pending is None
        assert n_late == n_eager == 64
        assert _bits(b_late) == _bits(b_eager)
        if i % 2:
            # a read of the actions is a read of THIS call's actions
            assert late._pending is not None
            assert np.array_equal(late.last_actions, eager.last_actions)
            assert late._pending is None
        assert _bits(late._carry) == _bits(eager._carry)
    late_eps.extend(late.get_metrics())
    assert late.last_actions.shape == (8, 8)
    assert np.array_equal(late.last_actions, eager.last_actions)
    assert len(eager_eps) >= 6
    assert [(m.episode_length, m.episode_reward) for m in late_eps] == [
        (m.episode_length, m.episode_reward) for m in eager_eps
    ]


def test_one_read_outstanding_and_counted():
    """(b) ``rollout()`` returns with its read pending; the next one
    finishes it behind its own dispatch (``deferred``) and leaves its
    own: never two. A read forced with nothing dispatched since is
    ``blocking``."""
    eng = _engine(seed=9)
    assert eng.last_actions is None and eng._pending is None
    d0, b0 = _drains()
    n = 4
    for i in range(n):
        eng.rollout()
        assert eng._pending is not None
        assert _drains() == (d0 + i, b0)
    # nothing was dispatched since the last rollout: this read waits
    # for it
    actions = eng.last_actions
    assert actions.shape == (8, 8)
    assert eng._pending is None
    assert _drains() == (d0 + n - 1, b0 + 1)
    assert eng.get_metrics() is not None
    assert _drains() == (d0 + n - 1, b0 + 1)


# -- the off-policy round ------------------------------------------------


def _per_dqn():
    from ray_tpu import sharding as sharding_lib
    from ray_tpu.algorithms.dqn.dqn import DQNConfig

    cfg = (
        DQNConfig()
        .environment("CartPoleJax-v0", env_backend="jax")
        .rollouts(
            num_rollout_workers=0,
            num_envs_per_worker=8,
            rollout_fragment_length=8,
        )
        .training(
            train_batch_size=32,
            num_steps_sampled_before_learning_starts=64,
            replay_buffer_config={
                "prioritized_replay": True,
                "capacity": 512,
            },
            training_intensity=2.0,
            superstep=4,
            replay_device_resident=True,
            replay_device_tree=True,
            target_network_update_freq=128,
            model={"fcnet_hiddens": [16, 16]},
        )
        .debugging(seed=0)
    )
    cfg._mesh = sharding_lib.get_mesh(devices=jax.devices()[:1])
    return cfg.build()


_EPISODE_KEYS = (
    "episode_reward_mean", "episode_reward_max", "episode_reward_min",
    "episode_len_mean", "episodes_this_iter", "episodes_total",
)


def _run_per_dqn(force_early: bool):
    algo = _per_dqn()
    try:
        eng = algo._jax_rollout_engine_get()
        if force_early:
            late_rollout = eng.rollout

            def rollout():
                out = late_rollout()
                eng.last_actions  # finishes the read before the insert
                return out

            eng.rollout = rollout
        results = []
        for _ in range(4):
            r = algo.train()
            results.append({k: r.get(k) for k in _EPISODE_KEYS})
        buf = algo.local_replay_buffer.buffers["default_policy"]
        assert buf._dtree is not None
        pol = algo.get_policy()
        state = jax.device_get(
            {
                "params": pol.params,
                "opt": pol.opt_state,
                "ring": buf._store,
                "sum": buf._dtree.sum_value,
                "min": buf._dtree.min_value,
                "key": pol._rng,
                "carry": eng._carry,
            }
        )
        return (
            _bits(state),
            buf._max_priority,
            buf._rng.bit_generator.state,
            dict(algo._counters),
            results,
        )
    finally:
        algo.cleanup()


def test_per_dqn_round_is_the_same_round():
    """(c) A tiny PER DQN on the jax lane with the device tree, 4
    ``train()`` calls with the reads left late and forced early:
    params, Adam state, ring, every tree node, the watermark, both key
    streams, the counters and every result's episode statistics are
    equal."""
    d0, b0 = _drains()
    skipped = tm.counter_total(tm.WEIGHT_PULLS_SKIPPED_TOTAL)
    late = _run_per_dqn(force_early=False)
    d1, b1 = _drains()
    # each iteration's read was finished under train:result, behind
    # the round's insert (and learn) dispatches; no worker, no pull
    assert (d1 - d0, b1 - b0) == (4, 0)
    assert tm.counter_total(tm.WEIGHT_PULLS_SKIPPED_TOTAL) - skipped == 4
    early = _run_per_dqn(force_early=True)
    assert _drains() == (d1, b1 + 4)
    assert late[3]["num_env_steps_trained"] > 0
    assert late[4][-1]["episodes_total"] > 0
    assert late == early


# -- sync_weights ----------------------------------------------------------


class _Local:
    policy_map = {"default_policy": None}

    def __init__(self):
        self.pulls = []
        self.global_vars = None

    def get_weights(self, policies=None, inference_only=False):
        self.pulls.append((policies, inference_only))
        return {"default_policy": {"w": np.ones(3, np.float32)}}

    def set_global_vars(self, global_vars):
        self.global_vars = global_vars


class _Remote:
    def __init__(self):
        self.got = []
        self.set_weights = types.SimpleNamespace(
            remote=lambda ref, global_vars=None: self.got.append(
                (ref, global_vars)
            )
        )


def _worker_set(n_remote):
    ws = WorkerSet.__new__(WorkerSet)
    ws._local_worker = _Local()
    ws._remote_workers = [_Remote() for _ in range(n_remote)]
    return ws


def _sync_spans(ws, **kw):
    tracing.clear()
    tracing.enable()
    try:
        ws.sync_weights(global_vars={"timestep": 7}, **kw)
        return [
            s for s in tracing.get_spans()
            if s["name"] == "rollout:sync_weights"
        ]
    finally:
        tracing.disable()
        tracing.clear()


@pytest.mark.parametrize(
    "n_remote, indices, pulls, takers",
    [
        (0, None, 0, []),
        (1, None, 1, [0]),
        (2, [2], 1, [1]),
        (2, [], 0, []),
    ],
)
def test_sync_weights_pulls_only_for_a_taker(
    n_remote, indices, pulls, takers, monkeypatch
):
    """(d) No taker, no ``get_weights`` call; the global vars are set
    and the span is opened either way."""
    import ray_tpu as ray

    monkeypatch.setattr(ray, "put", lambda x: ("ref", x))
    ws = _worker_set(n_remote)
    skipped = tm.counter_total(tm.WEIGHT_PULLS_SKIPPED_TOTAL)
    spans = _sync_spans(
        ws, to_worker_indices=indices, inference_only=True
    )
    local = ws._local_worker
    assert local.pulls == [(None, True)] * pulls
    assert local.global_vars == {"timestep": 7}
    assert len(spans) == 1
    assert spans[0]["attributes"]["workers"] == len(takers)
    for i, w in enumerate(ws._remote_workers):
        assert len(w.got) == (1 if i in takers else 0)
        for ref, gv in w.got:
            assert ref[0] == "ref" and gv == {"timestep": 7}
    assert tm.counter_total(tm.WEIGHT_PULLS_SKIPPED_TOTAL) - skipped == (
        0 if pulls else 1
    )


# -- the spans of a traced iteration ------------------------------------


def test_traced_iteration_keeps_its_spans():
    """(e) A traced ``train()`` still holds an iteration's span names
    (perf/tests/test_program_trace.py lists them), ``rollout:drain``
    inside ``train:result`` now, with ``deferred`` and ``bytes``."""
    algo = _per_dqn()
    try:
        for _ in range(2):  # past learning start, programs compiled
            algo.train()
        tracing.clear()
        tracing.enable()
        try:
            algo.train()
            spans = tracing.get_spans()
        finally:
            tracing.disable()
            tracing.clear()
    finally:
        algo.cleanup()
    names = {s["name"] for s in spans}
    assert {
        "train:iteration", "rollout:keys", "rollout:device",
        "rollout:drain", "replay:insert", "replay:draw", "learn:keys",
        "learn:superstep", "learn:drain", "replay:refresh",
        "rollout:sync_weights", "train:result",
    } <= names, names
    by_id = {s["span_id"]: s for s in spans}
    (drain,) = [s for s in spans if s["name"] == "rollout:drain"]
    assert drain["attributes"]["deferred"] is True
    assert drain["attributes"]["bytes"] > 0
    parents = []
    s = drain
    while s.get("parent_id") in by_id:
        s = by_id[s["parent_id"]]
        parents.append(s["name"])
    assert "train:result" in parents and "rollout:device" not in parents
    (sync,) = [s for s in spans if s["name"] == "rollout:sync_weights"]
    assert sync["attributes"]["workers"] == 0
