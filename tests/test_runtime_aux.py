"""Autoscaler-lite, log monitor, chaos (fault injection) tests
(reference autoscaler tests with FakeMultiNodeProvider,
_private/log_monitor tests, python/ray/tests/test_chaos.py)."""

import os
import time

import numpy as np
import pytest

import ray_tpu as ray


def setup_function(_):
    ray.shutdown()


def teardown_function(_):
    ray.shutdown()


def test_autoscaler_upscales_and_reaps(tmp_path):
    from ray_tpu.autoscaler import StandardAutoscaler

    ray.init(num_cpus=4)
    scaler = StandardAutoscaler(
        min_workers=0,
        max_workers=4,
        idle_timeout_s=1.0,
        update_interval_s=0.1,
    )

    room = str(tmp_path)

    @ray.remote
    def meet():
        # holds its worker until a second worker is in the room too:
        # an event, where a sleep would race the reaper and the
        # machine's load
        open(os.path.join(room, str(os.getpid())), "w").close()
        deadline = time.time() + 60
        while len(os.listdir(room)) < 2 and time.time() < deadline:
            time.sleep(0.05)
        return os.getpid()

    # demand-driven dispatch (the node-provider role) grew the pool:
    # two workers held a task at the same time
    pids = ray.get([meet.remote() for _ in range(4)])
    assert len(set(pids)) >= 2
    # idle reaping brings the pool back down: wait on that state
    deadline = time.time() + 60
    while time.time() < deadline:
        if scaler.stats()["num_workers"] == 0:
            break
        time.sleep(0.2)
    assert scaler.stats()["num_workers"] == 0
    assert scaler.num_downscales >= 2
    # pool regrows on new demand after reaping
    assert ray.get(meet.remote()) > 0
    scaler.stop()


def test_log_monitor_captures_worker_output(tmp_path):
    from ray_tpu.core.log_monitor import LogMonitor

    log_dir = str(tmp_path / "logs")
    ray.init(num_cpus=1, log_dir=log_dir)

    @ray.remote
    def chatty():
        print("hello from the worker")
        return 1

    assert ray.get(chatty.remote()) == 1
    seen = []
    mon = LogMonitor(
        log_dir, callback=lambda w, line: seen.append((w, line))
    )
    deadline = time.time() + 10
    while time.time() < deadline and not any(
        "hello from the worker" in line for _, line in seen
    ):
        time.sleep(0.2)
    mon.stop()
    assert any("hello from the worker" in line for _, line in seen)
    assert any(w.startswith("worker-") for w, _ in seen)
    assert any(
        "hello from the worker" in line for line in LogMonitor(
            log_dir, callback=lambda *a: None
        ).tail(50)
    )


@pytest.mark.slow  # PR-1 budget rule: 11 s; worker-kill-during-train
# coverage stays in tier-1 via tests/test_resilience.py's targeted
# kill/recreate tests and tests/test_elastic.py's drain paths
def test_chaos_worker_kills_during_training():
    """Fault injection (reference NodeKillerActor + test_chaos.py):
    kill rollout workers mid-run; training must recover via task
    retries + recreate_failed_workers."""
    from ray_tpu.algorithms.ppo import PPOConfig

    algo = (
        PPOConfig()
        .environment("CartPole-v1")
        .rollouts(
            num_rollout_workers=2,
            rollout_fragment_length=32,
            recreate_failed_workers=True,
        )
        .training(train_batch_size=128, sgd_minibatch_size=64,
                  num_sgd_iter=2)
        .debugging(seed=0)
        .build()
    )
    algo.train()  # warm
    rt = ray.core.api._require_runtime()
    # kill one remote rollout worker's process mid-training
    victim = algo.workers.remote_workers()[0]
    rec = rt.actors.get(victim._actor_id)
    rec.worker.proc.kill()
    for _ in range(3):
        result = algo.train()
    assert np.isfinite(
        result["info"]["learner"]["default_policy"]["total_loss"]
    )
    assert result["num_env_steps_sampled"] > 128
    algo.cleanup()
