"""One superstep driver (``JaxPolicy._drive_superstep``).

``learn_superstep`` (feeds ``stacked`` and ``rings``) and
``learn_rollout_superstep`` (feed ``rollout``) say what is their own
and hand the rest to one driver, so whatever the feed, the host side
of a dispatch reads the same: ``learn:keys``, then
``learn:superstep`` with ``learn:drain`` inside it, the same counters
moved by k, the same timer keys, one dispatch of the fused program and
exactly ONE ``jax.device_get``. And the option that selected a second
mesh backend is refused by every spelling of a config.
"""

import gymnasium as gym
import numpy as np
import pytest

jax = pytest.importorskip("jax")

from ray_tpu import sharding as sharding_lib
from ray_tpu.algorithms.ppo.ppo import PPOConfig, PPOJaxPolicy
from ray_tpu.data.sample_batch import SampleBatch as SB
from ray_tpu.env.jax_control import CartPoleJax
from ray_tpu.execution.jax_rollout import JaxRolloutEngine
from ray_tpu.execution.replay_buffer import DeviceReplayBuffer
from ray_tpu.policy import jax_policy as jax_policy_mod
from ray_tpu.telemetry import metrics as telemetry_metrics
from ray_tpu.util import tracing

K, K_MAX, BS = 2, 3, 16


def _policy():
    env = CartPoleJax({"max_steps": 10})
    cfg = PPOConfig().to_dict()
    cfg.update(
        seed=5,
        num_workers=0,
        train_batch_size=BS,
        sgd_minibatch_size=BS,
        num_sgd_iter=1,
        model={"fcnet_hiddens": [16]},
        _mesh=sharding_lib.get_mesh(devices=jax.devices()[:1]),
    )
    return env, PPOJaxPolicy(env.observation_space, env.action_space, cfg)


def _rows(n):
    rng = np.random.default_rng(11)
    return {
        SB.OBS: rng.standard_normal((n, 4)).astype(np.float32),
        SB.ACTIONS: rng.integers(0, 2, n).astype(np.int64),
        SB.ACTION_LOGP: np.full(n, -0.7, np.float32),
        SB.ACTION_DIST_INPUTS: rng.standard_normal((n, 2)).astype(
            np.float32
        ),
        SB.ADVANTAGES: rng.standard_normal(n).astype(np.float32),
        SB.VALUE_TARGETS: rng.standard_normal(n).astype(np.float32),
    }


def _stacked(pol, env):
    rows = _rows(K_MAX * BS)
    stacked = {c: v.reshape(K_MAX, BS, *v.shape[1:]) for c, v in rows.items()}
    return lambda: pol.learn_superstep(K, BS, stacked=stacked, k_max=K_MAX)


def _rings(pol, env):
    buf = DeviceReplayBuffer(capacity=4 * BS, seed=7, mesh=pol.mesh)
    buf.add_tree(_rows(4 * BS))
    return lambda: pol.learn_superstep(
        K,
        BS,
        rings=buf.superstep_feed(buf.draw_index_sets(K_MAX, BS)),
        k_max=K_MAX,
    )


def _rollout(pol, env):
    eng = JaxRolloutEngine(pol, env, 8, BS // 8, seed=5)

    def dispatch():
        infos, carry, metrics, skipped = pol.learn_rollout_superstep(
            K, BS, eng.superstep_feed(), k_max=K_MAX
        )
        eng.advance(carry, metrics)
        return infos, metrics, skipped

    return dispatch


@pytest.mark.parametrize(
    "feed,family",
    [
        (_stacked, "superstep"),
        (_rings, "superstep"),
        (_rollout, "rollout_superstep"),
    ],
    ids=["stacked", "rings", "rollout"],
)
def test_every_feed_runs_the_one_driver(feed, family, monkeypatch):
    env, pol = _policy()
    dispatch = feed(pol, env)
    dispatch()  # warm-up: the fused program and its key chain trace here
    (fn,) = pol._superstep_fns.values()
    assert fn.label == f"{family}[PPOJaxPolicy:{BS}x{K_MAX}]"

    gets = []
    real_get = jax.device_get
    monkeypatch.setattr(
        jax_policy_mod.jax,
        "device_get",
        lambda tree: gets.append(tree) or real_get(tree),
    )
    totals = {
        name: telemetry_metrics.counter_total(name)
        for name in (
            telemetry_metrics.LEARN_STEPS_TOTAL,
            telemetry_metrics.SUPERSTEP_UPDATES_TOTAL,
        )
    }
    updates, calls = pol.num_grad_updates, fn.calls
    pol.last_learn_timers.clear()
    tracing.clear()
    tracing.enable()
    try:
        infos, _, skipped = dispatch()
        spans = sorted(
            (
                sp
                for sp in tracing.get_spans()
                if sp["name"].startswith("learn:")
            ),
            key=lambda sp: sp["start"],
        )
    finally:
        tracing.disable()
        tracing.clear()

    assert [sp["name"] for sp in spans] == [
        "learn:keys",
        "learn:superstep",
        "learn:drain",
    ]
    keys, superstep, drain = spans
    assert keys["end"] <= superstep["start"]
    assert drain["parent_id"] == superstep["span_id"]
    assert keys["attributes"]["k"] == K
    attrs = superstep["attributes"]
    assert (attrs["k"], attrs["batch_size"], attrs["recompiles"]) == (K, BS, 0)
    rollout = family == "rollout_superstep"
    assert keys["attributes"].get("rollout", False) is rollout
    assert attrs.get("rollout", False) is rollout

    assert len(gets) == 1
    assert (fn.calls - calls, fn.traces) == (1, 1)
    for name, before in totals.items():
        assert telemetry_metrics.counter_total(name) - before == K, name
    assert pol.num_grad_updates - updates == K
    assert set(pol.last_learn_timers) == {
        "learn_superstep_s",
        "learn_recompiles",
    }
    assert pol.last_learn_timers["learn_recompiles"] == 0.0
    assert len(infos) == K and skipped == [False] * K
    assert all(np.isfinite(i["total_loss"]) for i in infos)


@pytest.mark.parametrize(
    "spell",
    [
        lambda: PPOJaxPolicy(
            gym.spaces.Box(-1.0, 1.0, (4,), np.float32),
            gym.spaces.Discrete(2),
            {"sharding_backend": "mesh"},
        ),
        lambda: PPOConfig().sharding(sharding_backend="pmap"),
        lambda: PPOConfig().resources(sharding_backend="pmap"),
    ],
    ids=["policy_config", "sharding_setter", "resources_setter"],
)
def test_removed_backend_option_is_refused(spell):
    with pytest.raises(ValueError, match="removed in PR 30"):
        spell()
