"""Tune layer tests (reference ray/tune/tests/test_trial_runner*.py,
test_trial_scheduler.py)."""

import numpy as np
import pytest

from ray_tpu.tune import (
    AsyncHyperBandScheduler,
    PopulationBasedTraining,
    Trainable,
    grid_search,
    run,
    uniform,
)
from ray_tpu.tune.search import generate_variants


class _Quadratic(Trainable):
    """Toy trainable: reward approaches -(x-3)^2 + noise-free."""

    def setup(self, config):
        self.x = config.get("x", 0.0)
        self.lr = config.get("lr", 0.1)

    def step(self):
        self.x = self.x + self.lr * 2 * (3.0 - self.x)
        return {"episode_reward_mean": -((self.x - 3.0) ** 2)}

    def save_checkpoint(self, d):
        import json, os

        with open(os.path.join(d, "state.json"), "w") as f:
            json.dump({"x": self.x}, f)
        return d

    def load_checkpoint(self, path):
        import json, os

        with open(os.path.join(path, "state.json")) as f:
            self.x = json.load(f)["x"]


def test_generate_variants_grid():
    variants = generate_variants(
        {"a": grid_search([1, 2, 3]), "b": {"c": grid_search([4, 5])}}
    )
    assert len(variants) == 6
    assert {v["a"] for v in variants} == {1, 2, 3}


def test_generate_variants_distributions():
    variants = generate_variants(
        {"lr": uniform(0.0, 1.0)}, num_samples=5
    )
    assert len(variants) == 5
    assert all(0.0 <= v["lr"] <= 1.0 for v in variants)


def test_tune_run_fifo():
    analysis = run(
        _Quadratic,
        config={"x": grid_search([0.0, 10.0]), "lr": 0.3},
        stop={"training_iteration": 10},
        verbose=0,
    )
    assert len(analysis.trials) == 2
    best = analysis.get_best_trial()
    assert best.last_result["episode_reward_mean"] > -1.0


def test_tune_run_stop_on_reward():
    analysis = run(
        _Quadratic,
        config={"x": 0.0, "lr": 0.5},
        stop={
            "episode_reward_mean": -0.01,
            "training_iteration": 50,
        },
        verbose=0,
    )
    t = analysis.trials[0]
    assert t.last_result["episode_reward_mean"] >= -0.01
    assert t.last_result["training_iteration"] < 50


# ASHA judges a report against what its rung has recorded SO FAR, so
# who is cut follows the order results arrive in. Both executors fix
# that order (every trial one iteration a step; one trial actor at a
# time), and either way the two far trials reach rung 2 after the two
# near ones and fall under its median.
@pytest.mark.parametrize(
    "executor",
    [{"parallel": False}, {"max_concurrent_trials": 1}],
    ids=["in_step", "one_actor_at_a_time"],
)
def test_asha_stops_bad_trials(executor):
    scheduler = AsyncHyperBandScheduler(
        max_t=20, grace_period=2, reduction_factor=2
    )
    analysis = run(
        _Quadratic,
        config={"x": grid_search([0.0, 1.0, 9.0, 30.0]), "lr": 0.05},
        stop={"training_iteration": 20},
        scheduler=scheduler,
        verbose=0,
        **executor,
    )
    ids = [t.trial_id for t in analysis.trials]
    assert [
        t.last_result["training_iteration"] for t in analysis.trials
    ] == [20, 20, 2, 2]
    # the scheduler's own record: all four at rung 2, the two near
    # trials alone at every rung above it
    assert {
        r["milestone"]: sorted(r["recorded"])
        for r in scheduler._bracket.rungs
    } == {2: ids, 4: ids[:2], 8: ids[:2], 16: ids[:2]}


def test_pbt_perturbs():
    scheduler = PopulationBasedTraining(
        perturbation_interval=2,
        hyperparam_mutations={"lr": [0.05, 0.1, 0.3]},
    )
    analysis = run(
        _Quadratic,
        config={"x": grid_search([0.0, 20.0, -10.0, 40.0]), "lr": 0.1},
        stop={"training_iteration": 12},
        scheduler=scheduler,
        verbose=0,
    )
    assert scheduler.num_perturbations > 0


@pytest.mark.slow  # ~14 s: tune+PPO e2e (moved out of tier-1 with
# PR 7, budget rule; tune scheduling/PBT mechanics keep tier-1
# coverage in this file)
def test_tune_with_ppo():
    analysis = run(
        "PPO",
        config={
            "env": "CartPole-v1",
            "num_workers": 0,
            "rollout_fragment_length": 64,
            "train_batch_size": 128,
            "sgd_minibatch_size": 64,
            "num_sgd_iter": 2,
            "lr": grid_search([1e-4, 3e-4]),
        },
        stop={"training_iteration": 2},
        verbose=0,
    )
    assert len(analysis.trials) == 2
    for t in analysis.trials:
        assert t.status == "TERMINATED", t.error
        assert "episode_reward_mean" in t.last_result


def test_pbt_mutation_reaches_live_policy():
    """ADVICE r1: PBT explore must actually change training — rebuild
    schedules and drop compiled learn programs — not just write into
    dicts that the next learn call overwrites."""
    import gymnasium as gym
    import numpy as np

    from ray_tpu.algorithms.ppo.ppo import PPOJaxPolicy
    from ray_tpu.data.sample_batch import SampleBatch

    pol = PPOJaxPolicy(
        gym.spaces.Box(-1, 1, (4,), np.float32),
        gym.spaces.Discrete(2),
        {"train_batch_size": 64, "sgd_minibatch_size": 32,
         "num_sgd_iter": 1, "lr": 1e-3, "clip_param": 0.3},
    )
    rng = np.random.default_rng(0)

    def batch():
        return SampleBatch({
            SampleBatch.OBS: rng.standard_normal((64, 4)).astype(
                np.float32
            ),
            SampleBatch.ACTIONS: rng.integers(0, 2, 64).astype(
                np.int64
            ),
            SampleBatch.ACTION_LOGP: np.full(64, -0.69, np.float32),
            SampleBatch.ACTION_DIST_INPUTS: rng.standard_normal(
                (64, 2)
            ).astype(np.float32),
            SampleBatch.ADVANTAGES: rng.standard_normal(64).astype(
                np.float32
            ),
            SampleBatch.VALUE_TARGETS: rng.standard_normal(64).astype(
                np.float32
            ),
        })

    info = pol.learn_on_batch(batch())
    assert np.isclose(info["cur_lr"], 1e-3)
    assert len(pol._learn_fns) == 1

    pol.update_config({"lr": 5e-4, "clip_param": 0.1})
    # compiled programs dropped (clip_param is baked into them)
    assert len(pol._learn_fns) == 0
    info = pol.learn_on_batch(batch())
    # the new lr survives _update_scheduled_coeffs on the next learn
    assert np.isclose(info["cur_lr"], 5e-4)
    assert pol.config["clip_param"] == 0.1


class _Sleeper(Trainable):
    def setup(self, config):
        self.delay = config.get("delay", 1.0)

    def step(self):
        import time as _t

        _t.sleep(self.delay)
        return {"episode_reward_mean": 1.0}

    def save_checkpoint(self, d):
        return d

    def load_checkpoint(self, path):
        pass


@pytest.mark.slow  # ~34 s on the tier-1 host: wall-clock A/B of two full runs
def test_parallel_trials_beat_serial_wall_clock():
    """VERDICT r1: N trials must progress concurrently — wall-clock
    below the serial sum (both modes pay the same actor startup)."""
    import time as _t

    kwargs = dict(
        config={"delay": 3.0, "x": grid_search([1, 2, 3, 4])},
        stop={"training_iteration": 2},
        verbose=0,
    )
    t0 = _t.perf_counter()
    run(_Sleeper, parallel=True, max_concurrent_trials=4, **kwargs)
    t_par = _t.perf_counter() - t0
    t0 = _t.perf_counter()
    run(_Sleeper, parallel=True, max_concurrent_trials=1, **kwargs)
    t_serial = _t.perf_counter() - t0
    # serial floor is 4 trials x 2 iters x 3s = 24s of sleeping; 4-way
    # concurrency sleeps ~6s. Both modes pay the same actor startup
    # (which dominates on small CI boxes), hence the generous slack.
    assert t_par < t_serial * 0.75, (t_par, t_serial)


class _Carrier(Trainable):
    """Reward equals the carried state x, which only exploit changes.
    Steps take real time so concurrently-started trial actors genuinely
    overlap (instant steps would let the first-ready actor finish before
    the others produce their first result)."""

    def setup(self, config):
        self.x = float(config.get("x", 0.0))

    def step(self):
        import time as _t

        _t.sleep(0.5)
        return {"episode_reward_mean": self.x, "x": self.x}

    def __getstate__(self):
        return {"x": self.x}

    def __setstate__(self, state):
        self.x = state["x"]

    def save_checkpoint(self, d):
        return d

    def load_checkpoint(self, path):
        pass


@pytest.mark.slow  # budget rule: tier-1 keeps PBT coverage via the
# scheduler-decision unit tests in this file
def test_pbt_exploit_transfers_state_across_actors():
    scheduler = PopulationBasedTraining(
        perturbation_interval=2,
        quantile_fraction=0.34,
        hyperparam_mutations={"lr": [0.1, 0.2]},
    )
    analysis = run(
        _Carrier,
        config={"x": grid_search([0.0, 5.0, 100.0]), "lr": 0.1},
        stop={"training_iteration": 16},
        scheduler=scheduler,
        parallel=True,
        max_concurrent_trials=3,
        verbose=0,
    )
    assert scheduler.num_perturbations > 0
    # the bottom trial adopted the donor's carried state (x=100)
    finals = sorted(
        t.last_result.get("x", -1.0) for t in analysis.trials
    )
    assert finals.count(100.0) >= 2, finals
