"""HyperBand + MedianStoppingRule (reference
``tune/tests/test_trial_scheduler.py`` HyperBand / median-stopping
cases)."""

import pytest

from ray_tpu.tune import (
    HyperBandScheduler,
    MedianStoppingRule,
    grid_search,
    run,
)
from ray_tpu.tune.schedulers import CONTINUE, STOP


class _Trial:
    def __init__(self, tid):
        self.trial_id = tid
        self.status = "RUNNING"


class _Runner:
    def __init__(self, trials):
        self.trials = trials


def test_median_stopping_stops_below_median():
    rule = MedianStoppingRule(
        grace_period=2, min_samples_required=2
    )
    trials = [_Trial(f"t{i}") for i in range(3)]
    runner = _Runner(trials)
    # t0/t1 report well at iters 1-2; t2 reports badly
    for it in (1, 2):
        for tr, m in zip(trials, [10.0, 9.0, 0.1]):
            decisions = rule.on_trial_result(
                runner, tr, {"training_iteration": it,
                             "episode_reward_mean": m}
            )
    assert decisions == STOP  # t2's best < median of running avgs
    # good trial continues
    assert rule.on_trial_result(
        runner, trials[0],
        {"training_iteration": 3, "episode_reward_mean": 10.0},
    ) == CONTINUE


def test_median_stopping_min_mode():
    rule = MedianStoppingRule(
        mode="min", grace_period=1, min_samples_required=2
    )
    trials = [_Trial(f"t{i}") for i in range(3)]
    runner = _Runner(trials)
    out = {}
    for tr, loss in zip(trials, [0.1, 0.2, 5.0]):
        out[tr.trial_id] = rule.on_trial_result(
            runner, tr,
            {"training_iteration": 1, "episode_reward_mean": loss},
        )
    assert out["t2"] == STOP and out["t0"] == CONTINUE


def test_hyperband_synchronous_cut():
    sched = HyperBandScheduler(max_t=9, reduction_factor=3)
    trials = [_Trial(f"t{i}") for i in range(3)]
    runner = _Runner(trials)
    # rung at t=1 and t=3; all three must report before any cut
    a = sched.on_trial_result(
        runner, trials[0],
        {"training_iteration": 1, "episode_reward_mean": 3.0},
    )
    b = sched.on_trial_result(
        runner, trials[1],
        {"training_iteration": 1, "episode_reward_mean": 2.0},
    )
    assert a == CONTINUE and b == CONTINUE  # waiting on t2
    c = sched.on_trial_result(
        runner, trials[2],
        {"training_iteration": 1, "episode_reward_mean": 1.0},
    )
    assert c == STOP  # bottom 2/3 cut once the rung is complete
    # t1 was also cut; it learns on its next report
    assert sched.on_trial_result(
        runner, trials[1],
        {"training_iteration": 2, "episode_reward_mean": 2.0},
    ) == STOP
    # the survivor keeps going to max_t, then stops
    assert sched.on_trial_result(
        runner, trials[0],
        {"training_iteration": 5, "episode_reward_mean": 3.0},
    ) == CONTINUE
    assert sched.on_trial_result(
        runner, trials[0],
        {"training_iteration": 9, "episode_reward_mean": 3.0},
    ) == STOP


# A rung is decided when the last of its population reports, so what
# each trial reaches follows the ORDER results arrive in. Both
# executors below fix that order (every trial one iteration a step;
# one trial actor at a time), so the iterations are the scheduler's
# decisions and nothing else. With all four actors at once the order
# is the processes' start-up race, and "someone was cut" a coin.
@pytest.mark.parametrize(
    "executor, iterations",
    [
        # in step: rung 1 cuts the two far trials as its fourth report
        # lands (the third has its STOP at its next), rung 2 the
        # worse of the two left
        ({"parallel": False}, [3, 8, 2, 1]),
        # one after another: three brackets' worth run out alone, the
        # fourth report fills rung 1 and the last trial is cut there
        ({"max_concurrent_trials": 1}, [8, 8, 8, 1]),
    ],
    ids=["in_step", "one_actor_at_a_time"],
)
def test_hyperband_end_to_end(executor, iterations):
    from tests.test_tune import _Quadratic as Quad

    sched = HyperBandScheduler(max_t=8, reduction_factor=2)
    analysis = run(
        Quad,
        config={"x": grid_search([0.0, 1.0, 20.0, 40.0]), "lr": 0.05},
        stop={"training_iteration": 8},
        scheduler=sched,
        verbose=0,
        **executor,
    )
    trials = analysis.trials
    assert [
        t.last_result["training_iteration"] for t in trials
    ] == iterations
    # the decisions: which rung cut whom (x=1.0, the nearest, never)
    assert sched._stopped_at == {
        trials[2].trial_id: 1,
        trials[3].trial_id: 1,
        trials[0].trial_id: 2,
    }
