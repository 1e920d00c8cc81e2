"""Fault-tolerant training loop (docs/resilience.md): retry/backoff
schedule units, fault-injection determinism, AsyncRequestsManager
re-add semantics, bounded health probes, NaN-batch skip bit-exactness,
checkpoint auto-restore, and the chaos e2e (kill 2 of 4 rollout
workers + poison one learn batch mid-PPO ``train()``; the run must
complete with the fleet restored and the recovery telemetry correct).

Reference precedent: ``ray/python/ray/tests/test_chaos.py`` (NodeKiller
chaos), rllib's ``ignore_worker_failures`` fault-tolerance tests."""

import time
import urllib.request

import numpy as np
import pytest

import ray_tpu as ray
from ray_tpu.resilience import (
    FaultInjector,
    InjectedCrash,
    RetryPolicy,
    batch_is_finite,
    probe_actors,
)
from ray_tpu.resilience.faults import _parse_env_spec


# ---------------------------------------------------------------------------
# RetryPolicy units
# ---------------------------------------------------------------------------


def test_retry_backoff_schedule():
    p = RetryPolicy(
        max_attempts=5,
        backoff_s=0.1,
        backoff_mult=2.0,
        max_backoff_s=0.5,
        jitter=0.0,
    )
    # exponential, capped, one delay per retry (attempts - 1)
    assert p.schedule() == pytest.approx([0.1, 0.2, 0.4, 0.5])
    # jitter adds AT MOST the configured fraction, deterministically
    # under a seed
    pj = RetryPolicy(
        max_attempts=3, backoff_s=0.1, jitter=0.5, seed=7
    )
    d0, d1 = pj.schedule(), pj.schedule()
    assert d0 == d1  # seeded → reproducible
    for base, d in zip([0.1, 0.2], d0):
        assert base <= d <= base * 1.5


def test_retry_call_retries_then_succeeds_then_raises():
    p = RetryPolicy(
        max_attempts=3, backoff_s=0.001, jitter=0.0
    )
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise TimeoutError("transient")
        return "ok"

    assert p.call(flaky) == "ok"
    assert calls["n"] == 3

    # budget exhausted → the last error propagates
    with pytest.raises(TimeoutError):
        p.call(lambda: (_ for _ in ()).throw(TimeoutError("always")))

    # non-retryable errors propagate immediately (no backoff burn)
    calls["n"] = 0

    def fatal():
        calls["n"] += 1
        raise ValueError("bug")

    with pytest.raises(ValueError):
        p.call(fatal)
    assert calls["n"] == 1


def test_fault_spec_env_parsing():
    spec = _parse_env_spec(
        "kill_worker:2@3,4@1;nan_batch:@2;delay_sample:1@2x0.5;"
        "crash_learner:@7"
    )
    assert spec["kill_worker"] == [
        {"worker_index": 2, "on_call": 3},
        {"worker_index": 4, "on_call": 1},
    ]
    assert spec["nan_batch"] == {"on_learn_call": 2}
    assert spec["delay_sample"] == [
        {"worker_index": 1, "on_call": 2, "delay_s": 0.5}
    ]
    assert spec["crash_learner"] == {"on_learn_call": 7}


def test_fault_injector_nan_and_crash_fire_once():
    inj = FaultInjector(
        {
            "nan_batch": {"on_learn_call": 2},
            "crash_learner": {"on_learn_call": 4},
        }
    )
    b = {"adv": np.ones(4, np.float32)}
    inj.on_learn(b)
    assert batch_is_finite(b)  # call 1: untouched
    inj.on_learn(b)
    assert not batch_is_finite(b)  # call 2: poisoned
    b2 = {"adv": np.ones(4, np.float32)}
    inj.on_learn(b2)
    assert batch_is_finite(b2)  # call 3: nan fired once only
    with pytest.raises(InjectedCrash):
        inj.on_learn(b2)  # call 4
    inj.on_learn(b2)  # call 5: crash fired once only


# ---------------------------------------------------------------------------
# AsyncRequestsManager re-add + bounded probes
# ---------------------------------------------------------------------------


@ray.remote
class _Pingable:
    def __init__(self, ping_delay=0.0):
        self.delay = float(ping_delay)

    def ping(self):
        if self.delay:
            time.sleep(self.delay)
        return "pong"

    def sample(self):
        return 1


def test_manager_readd_clears_dead_mark_and_counts():
    """Satellite: a recreated worker re-added to the manager must get
    fresh in-flight slots and a cleared dead-mark (stale state from a
    freed id() would cap it at zero slots and eat its next death
    report)."""
    from ray_tpu.execution.parallel_requests import (
        AsyncRequestsManager,
    )

    if not ray.is_initialized():
        ray.init()
    w = _Pingable.remote()
    mgr = AsyncRequestsManager(
        [w], max_remote_requests_in_flight_per_worker=2
    )
    assert mgr.submit(worker=w) and mgr.submit(worker=w)
    mgr.report_dead(w)  # caller-observed death
    assert mgr.take_dead_workers() == [w]
    assert not mgr.submit(worker=w)  # out of rotation

    # the "replacement" is the same handle here — the point is the
    # bookkeeping reset, which id()-reuse makes indistinguishable
    mgr.add_workers([w])
    assert mgr.in_flight(w) == 0  # counters reset, not inherited
    assert mgr.submit(worker=w)  # full slot budget again
    mgr.report_dead(w)
    # dead-mark was cleared on re-add: the second death reports again
    assert mgr.take_dead_workers() == [w]


def test_probe_actors_bounded_by_single_budget(monkeypatch):
    """Satellite: one wedged actor must cost the sweep at most the
    probe budget — not a per-worker timeout each. Held as events, not
    as a wall-clock bound (the machine's load is not the code's): the
    sweep blocks in ONE wait, on the budget, and reports the wedged
    actor alone."""
    if not ray.is_initialized():
        ray.init()
    ok = _Pingable.remote()
    wedged = _Pingable.remote(ping_delay=60.0)
    # both processes are up before the budget starts: a healthy actor
    # still starting is not a wedged one
    assert ray.get([ok.sample.remote(), wedged.sample.remote()]) == [1, 1]
    waits = []
    wait = ray.wait

    def counted(refs, **kw):
        waits.append(kw.get("timeout"))
        return wait(refs, **kw)

    monkeypatch.setattr(ray, "wait", counted)
    bad = probe_actors([ok, wedged, ok], timeout_s=5.0)
    assert bad == [1]
    assert [t for t in waits if t] == [5.0], waits


# ---------------------------------------------------------------------------
# NaN guard: skip leaves params bit-identical
# ---------------------------------------------------------------------------


def _local_ppo(**ft):
    from ray_tpu.algorithms.ppo import PPOConfig

    return (
        PPOConfig()
        .environment("CartPole-v1")
        .rollouts(num_rollout_workers=0, rollout_fragment_length=64)
        .training(
            train_batch_size=128,
            sgd_minibatch_size=64,
            num_sgd_iter=2,
            lr=3e-4,
        )
        .fault_tolerance(**ft)
        .debugging(seed=1)
        .build()
    )


def _leaves(algo):
    import jax

    return [
        np.asarray(x).copy()
        for x in jax.tree_util.tree_leaves(
            algo.get_policy().get_weights()
        )
    ]


def test_nan_guard_skips_batch_params_bit_identical():
    """A poisoned learn batch is skipped: params after the skipped
    iteration are bit-identical to params before it (the clean run
    minus the skipped batch), and the skip is counted."""
    algo = _local_ppo(
        nan_guard=True,
        fault_injection={"nan_batch": {"on_learn_call": 2}},
    )
    try:
        algo.train()  # learn call 1: clean
        before = _leaves(algo)
        r2 = algo.train()  # learn call 2: poisoned → skipped
        after = _leaves(algo)
        assert r2["info"]["recovery"]["skipped_batches"] == 1
        assert r2["info"]["num_nan_batches_skipped"] == 1
        for a, b in zip(before, after):
            np.testing.assert_array_equal(a, b)
        r3 = algo.train()  # learn call 3: clean again, learning resumes
        assert r3["info"]["recovery"]["skipped_batches"] == 1
        assert any(
            not np.array_equal(a, b)
            for a, b in zip(after, _leaves(algo))
        )
    finally:
        algo.cleanup()


def test_without_nan_guard_poison_propagates():
    """Counter-proof that the guard is load-bearing: the same poisoned
    batch with nan_guard off drives the loss non-finite."""
    algo = _local_ppo(
        nan_guard=False,
        fault_injection={"nan_batch": {"on_learn_call": 1}},
    )
    try:
        r = algo.train()
        loss = r["info"]["learner"]["default_policy"]["total_loss"]
        assert not np.isfinite(loss)
    finally:
        algo.cleanup()


# ---------------------------------------------------------------------------
# checkpoint auto-restore + pruning
# ---------------------------------------------------------------------------


def test_auto_restore_from_checkpoint_and_prune(tmp_path):
    """An injected driver-side crash mid-train() restores the latest
    periodic checkpoint and continues; periodic checkpoints prune to
    keep_checkpoints_num."""
    import os

    root = str(tmp_path / "ckpts")
    algo = _local_ppo(
        checkpoint_frequency=1,
        checkpoint_root=root,
        keep_checkpoints_num=2,
        restore_on_failure=True,
        max_failures=3,
        fault_injection={"crash_learner": {"on_learn_call": 3}},
    )
    try:
        algo.train()  # learn 1, ckpt 1
        algo.train()  # learn 2, ckpt 2
        r3 = algo.train()  # learn 3 crashes → restore ckpt 2 → retry
        rec = r3["info"]["recovery"]
        assert rec["recoveries"].get("restore") == 1
        assert rec["failures"] == 1
        assert rec["time_lost_s_this_iter"] > 0.0
        assert np.isfinite(
            r3["info"]["learner"]["default_policy"]["total_loss"]
        )
        # pruned to the newest 2 periodic checkpoints
        ckpts = sorted(
            d
            for d in os.listdir(root)
            if d.startswith("checkpoint_")
        )
        assert len(ckpts) == 2
        # the restore target still exists on disk
        assert os.path.isdir(rec["latest_checkpoint"])
    finally:
        algo.cleanup()


def test_restore_without_checkpoint_propagates():
    """restore_on_failure without a checkpoint yet → the crash must
    surface, not be silently absorbed."""
    algo = _local_ppo(
        restore_on_failure=True,
        checkpoint_frequency=5,  # no checkpoint before the crash
        fault_injection={"crash_learner": {"on_learn_call": 1}},
    )
    try:
        with pytest.raises(InjectedCrash):
            algo.train()
    finally:
        algo.cleanup()


# ---------------------------------------------------------------------------
# chaos e2e
# ---------------------------------------------------------------------------


@pytest.mark.slow  # PR-1 budget rule: 23 s; every failure mode it
# composes (worker kill + probe/recreate, nan-batch skip, recovery
# counters) keeps tier-1 coverage via the individual tests above
def test_chaos_e2e_kill_two_of_four_workers_and_nan_batch():
    """The acceptance scenario: FaultInjector kills 2 of 4 rollout
    workers and poisons one learn batch mid-PPO-run; ``train()`` must
    complete without a driver crash, the fleet must be restored to
    full size (replacements disarmed — they don't re-die), and the
    recovery counts must land in ``info/recovery`` AND the Prometheus
    scrape."""
    from ray_tpu.algorithms.ppo import PPOConfig
    from ray_tpu.telemetry import metrics as tm

    restarts0 = tm.counter_total(tm.WORKER_RESTARTS_TOTAL)
    skipped0 = tm.counter_total(tm.SKIPPED_BATCHES_TOTAL)
    algo = (
        PPOConfig()
        .environment("CartPole-v1")
        .rollouts(num_rollout_workers=4, rollout_fragment_length=32)
        .training(
            train_batch_size=128,
            sgd_minibatch_size=64,
            num_sgd_iter=2,
            lr=3e-4,
        )
        .fault_tolerance(
            recreate_failed_workers=True,
            nan_guard=True,
            max_failures=10,
            worker_health_probe_timeout_s=10.0,
            fault_injection={
                "kill_worker": [
                    {"worker_index": 2, "on_call": 2},
                    {"worker_index": 3, "on_call": 3},
                ],
                "nan_batch": {"on_learn_call": 2},
            },
        )
        .telemetry(metrics_port=0)
        .debugging(seed=1)
        .build()
    )
    try:
        last = {}
        for _ in range(4):
            last = algo.train()  # must never raise
        rec = last["info"]["recovery"]
        assert algo.workers.num_remote_workers() == 4, (
            "fleet not restored"
        )
        assert rec["worker_restarts"] >= 2
        assert rec["skipped_batches"] == 1
        assert rec["time_lost_s"] > 0.0
        assert np.isfinite(
            last["info"]["learner"]["default_policy"]["total_loss"]
        )
        assert (
            tm.counter_total(tm.WORKER_RESTARTS_TOTAL) - restarts0
            >= 2
        )
        assert (
            tm.counter_total(tm.SKIPPED_BATCHES_TOTAL) - skipped0
            == 1
        )
        # the same counts must be scrapeable (acceptance: Prometheus
        # reports the restarts/recoveries/skipped-batch counts)
        port = algo._telemetry.metrics_port
        scrape = (
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10
            )
            .read()
            .decode()
        )
        assert "ray_tpu_worker_restarts_total" in scrape
        assert "ray_tpu_skipped_batches_total" in scrape
        assert 'ray_tpu_recoveries_total{kind="workers"}' in scrape
    finally:
        algo.cleanup()
