"""End-to-end (rollout + learner) benchmarks: the five BASELINE.md configs.

Counterpart of the reference's tuned-example benchmark runs
(``rllib/tuned_examples/ppo/pong-ppo.yaml:1``,
``impala/pong-impala.yaml:1-5``, ``sac/halfcheetah-sac.yaml:1``): each
config builds the real Algorithm (CPU rollout actors + TPU learner),
trains under a wall-clock budget, and records a reward-vs-env-steps
curve plus end-to-end env-steps/s (total wall clock, sampling AND
learning included).

Stand-ins, documented: ALE and PettingZoo are not in this image, so
Pong/Breakout run on the in-repo Atari-shaped ``PongLite-v0``
(``ray_tpu/env/pong_lite.py``: 84x84 uint8 pixels, framestack 4,
genuine tracking task; random ~-11/episode, oracle +21) and the
multi-agent pistonball slot runs shared-policy PPO on N-agent
multi-CartPole (``env/multi_agent_env.py make_multi_agent``).
HalfCheetah is the real MuJoCo task. The driver host exposes ONE CPU
core, so rollout throughput is host-bound in a way the reference's
32-128-worker clusters were not; the learner-side headline lives in
``bench.py``.

Writes one JSON artifact per config under ``benchmarks/e2e/`` and
prints ONE summary JSON line. Usage:

    python bench.py --e2e [--only NAME] [--budget SECONDS]
"""

import json
import pathlib
import sys
import time

import numpy as np

ARTIFACT_DIR = pathlib.Path(__file__).parent / "benchmarks" / "e2e"


def _ppo_cartpole():
    # FUSED LANE (ROADMAP 5a): the jax-native CartPole rolls out ON
    # the learner mesh and rollout+GAE+the SGD nest dispatch as one
    # fused superstep program (jax_fused_rollout, superstep="auto") —
    # zero rollout bytes over H2D. The old actor-lane variant of this
    # config lives on as `plumbing_ppo` (SyntheticFast) for sampler-
    # loop trend continuity; fixed-seed trajectory parity between the
    # two lanes is tests/test_jax_env.py's contract.
    import ray_tpu.env.jax_control  # noqa: F401  registers CartPoleJax-v0
    from ray_tpu.algorithms.ppo import PPOConfig

    return (
        PPOConfig()
        .environment("CartPoleJax-v0", env_backend="jax")
        .rollouts(
            num_rollout_workers=0,
            num_envs_per_worker=32,
            rollout_fragment_length=64,
        )
        .training(
            gamma=0.99, lr=3e-4, lambda_=0.95,
            train_batch_size=2048, sgd_minibatch_size=256,
            num_sgd_iter=8, entropy_coeff=0.01, clip_param=0.2,
            kl_coeff=0.0, model={"fcnet_hiddens": [256, 256]},
        )
        .debugging(seed=0)
    )


def _ppo_pong():
    # reference geometry: ppo/pong-ppo.yaml (1 GPU + 32 workers).
    # FUSED LANE (ROADMAP 5a): PongLiteJax rolls the pixel env out on
    # the learner mesh — the rollout+learn superstep replaces the
    # 2-worker CPU sampler ensemble the earlier rounds measured
    import ray_tpu.env.jax_pong  # noqa: F401  registers PongLiteJax-v0
    from ray_tpu.algorithms.ppo import PPOConfig

    return (
        PPOConfig()
        .environment("PongLiteJax-v0", env_backend="jax")
        .rollouts(
            num_rollout_workers=0,
            num_envs_per_worker=16,
            rollout_fragment_length=128,
        )
        .training(
            gamma=0.99, lr=2.5e-4, lambda_=0.95,
            train_batch_size=2048, sgd_minibatch_size=512,
            num_sgd_iter=6, entropy_coeff=0.01, clip_param=0.2,
            kl_coeff=0.0, vf_clip_param=10.0,
        )
        .debugging(seed=0)
    )


def _impala_pong():
    # reference geometry: impala/pong-impala.yaml (async learner)
    import ray_tpu.env.pong_lite  # noqa: F401
    from ray_tpu.algorithms.impala import IMPALAConfig

    return (
        IMPALAConfig()
        .environment("PongLite-v0")
        .rollouts(
            num_rollout_workers=2,
            num_envs_per_worker=8,
            rollout_fragment_length=64,
        )
        .training(
            train_batch_size=1024, lr=4e-4, entropy_coeff=0.01,
            vf_loss_coeff=0.5, grad_clip=40.0,
        )
        .debugging(seed=0)
    )


def _sac_halfcheetah():
    # reference geometry: sac/halfcheetah-sac.yaml (9k @ 400k steps)
    from ray_tpu.algorithms.sac import SACConfig

    return (
        SACConfig()
        .environment("HalfCheetah-v4")
        # fragment 32 amortizes the rollout round trip; the reference's
        # 1-update-per-env-step ratio (halfcheetah-sac.yaml fragment 1,
        # batch 256) is restored via training_intensity=256 — the 32
        # updates per round fuse into ONE lax.scan dispatch
        # (sac.py learn_on_stacked_batch), and sample_async overlaps
        # the next fragment with the update chain
        .rollouts(num_rollout_workers=1, rollout_fragment_length=32)
        .training(
            train_batch_size=256,
            gamma=0.99, tau=0.005,
            training_intensity=256,
            num_steps_sampled_before_learning_starts=10000,
            sample_async=True,
            optimization={
                "actor_learning_rate": 3e-4,
                "critic_learning_rate": 3e-4,
                "entropy_learning_rate": 3e-4,
            },
            replay_buffer_config={"capacity": 400000},
        )
        .debugging(seed=0)
    )


def _ma_cartpole():
    # pistonball slot: shared-params multi-agent PPO (pettingzoo absent)
    import gymnasium as gym

    from ray_tpu.algorithms.ppo import PPOConfig
    from ray_tpu.env.multi_agent_env import make_multi_agent
    from ray_tpu.env.registry import register_env

    register_env(
        "ma_cartpole4",
        lambda cfg: make_multi_agent("CartPole-v1")({"num_agents": 4}),
    )
    obs_sp = gym.spaces.Box(-np.inf, np.inf, (4,), np.float64)
    act_sp = gym.spaces.Discrete(2)
    return (
        PPOConfig()
        .environment("ma_cartpole4")
        .rollouts(num_rollout_workers=1, rollout_fragment_length=256)
        .training(
            train_batch_size=2048, sgd_minibatch_size=256,
            num_sgd_iter=8, lr=3e-4, entropy_coeff=0.01,
            model={"fcnet_hiddens": [128, 128]},
        )
        .multi_agent(
            policies={"shared": (None, obs_sp, act_sp, {})},
            policy_mapping_fn=lambda aid, **kw: "shared",
        )
        .debugging(seed=0)
    )


def _plumbing_ppo():
    # framework-bound config: near-free env (SyntheticEnv) + tiny MLP,
    # so steps/s measures the plumbing (sampler loop, shipping, learner
    # queue), not env or model compute
    import ray_tpu.env.synthetic_env  # noqa: F401  registers SyntheticFast-v0
    from ray_tpu.algorithms.ppo import PPOConfig

    return (
        PPOConfig()
        .environment("SyntheticFast-v0")
        .rollouts(
            num_rollout_workers=2,
            num_envs_per_worker=16,
            rollout_fragment_length=256,
            sample_prefetch=1,
        )
        .training(
            train_batch_size=8192, sgd_minibatch_size=1024,
            num_sgd_iter=2, lr=3e-4,
            model={"fcnet_hiddens": [64, 64]},
        )
        .debugging(seed=0)
    )


def _plumbing_impala():
    import ray_tpu.env.synthetic_env  # noqa: F401
    from ray_tpu.algorithms.impala import IMPALAConfig

    return (
        IMPALAConfig()
        .environment("SyntheticFast-v0")
        .rollouts(
            num_rollout_workers=2,
            num_envs_per_worker=16,
            rollout_fragment_length=64,
        )
        .training(
            train_batch_size=4096, lr=3e-4,
            model={"fcnet_hiddens": [64, 64]},
        )
        .debugging(seed=0)
    )


CONFIGS = {
    # name -> (builder, default_budget_s, reward_target_note)
    "ppo_cartpole": (_ppo_cartpole, 150, "reward 150 (ref: @<=100k steps)"),
    "ppo_pong": (_ppo_pong, 420, "reward rising from ~-12 (ref: Pong max)"),
    "impala_pong": (
        _impala_pong,
        420,
        "throughput-focused async config; flat at <=1.8M-step "
        "budgets (ref IMPALA-Pong consumes >20M frames across "
        "32-128 workers)",
    ),
    "sac_halfcheetah": (_sac_halfcheetah, 300, "reward rising (ref: 9k@400k)"),
    "ma_cartpole": (_ma_cartpole, 150, "shared-policy reward 150"),
}

# not part of the headline sweep: throughput-only, no learning target
PLUMBING_CONFIGS = {
    "plumbing_ppo": (_plumbing_ppo, 90, "throughput only (synthetic env)"),
    "plumbing_impala": (
        _plumbing_impala, 90, "throughput only (synthetic env)",
    ),
}


def _hardware():
    """The devices this run actually saw (called after the algorithm
    built its mesh, so the backend is already up)."""
    import os

    from ray_tpu.utils.platform import device_info

    return {**device_info(), "host_cpus": os.cpu_count()}


def run_plumbing(budget_s=None):
    """Framework-bound throughput: the five-config sweep's configs keep
    the chip ~5% busy, but nothing there separates "rollout-starved by
    the 1-core host" from "framework overhead". These two runs remove
    env and model cost; the resulting steps/s IS the plumbing bound
    (sampler loop + object shipping + queues + learner dispatch) on
    this host. Writes ``benchmarks/e2e/plumbing_bound.json``."""
    results = {}
    for name in PLUMBING_CONFIGS:
        r = run_config(name, budget_s)
        results[name] = {
            "env_steps_per_sec": r["env_steps_per_sec"],
            "env_steps": r["env_steps"],
            "wall_clock_s": r["wall_clock_s"],
        }
    out = {
        "what": (
            "e2e throughput with env.step ~1us and a 64x64 MLP: the "
            "framework plumbing bound on this host (cf. ppo_pong/"
            "impala_pong, where the 1-core host splits between CPU "
            "CNN inference and per-step obs byte handling, and sync "
            "PPO additionally serializes rollout against the learner "
            "phase)"
        ),
        "hardware": _hardware(),
        "results": results,
    }
    ARTIFACT_DIR.mkdir(parents=True, exist_ok=True)
    (ARTIFACT_DIR / "plumbing_bound.json").write_text(
        json.dumps(out, indent=1)
    )
    print(json.dumps({"metric": "plumbing_bound", **out}))
    return out


def run_config(name, budget_s=None, overrides=None, artifact_suffix=""):
    builder, default_budget, note = CONFIGS.get(name) or (
        PLUMBING_CONFIGS[name]
    )
    budget = float(budget_s or default_budget)
    cfg = builder()
    for k, v in (overrides or {}).items():
        setattr(cfg, k, v)
    algo = cfg.build()
    curve = []
    t0 = time.perf_counter()
    steps = 0
    try:
        while time.perf_counter() - t0 < budget:
            result = algo.train()
            steps = int(result.get("num_env_steps_sampled", 0))
            rew = result.get("episode_reward_mean")
            curve.append(
                {
                    "wall_s": round(time.perf_counter() - t0, 1),
                    "env_steps": steps,
                    "episode_reward_mean": (
                        None if rew is None or not np.isfinite(rew)
                        else round(float(rew), 2)
                    ),
                }
            )
    finally:
        try:
            algo.cleanup()
        except Exception:
            pass
    wall = time.perf_counter() - t0
    rewards = [
        c["episode_reward_mean"]
        for c in curve
        if c["episode_reward_mean"] is not None
    ]
    if len(curve) > 200:  # thin long runs; endpoints kept
        idx = np.unique(
            np.linspace(0, len(curve) - 1, 200).astype(int)
        )
        curve = [curve[i] for i in idx]
    out = {
        "name": name + artifact_suffix,
        "note": note,
        "env_steps": steps,
        "wall_clock_s": round(wall, 1),
        "env_steps_per_sec": round(steps / wall, 1),
        "first_reward": rewards[0] if rewards else None,
        "best_reward": max(rewards) if rewards else None,
        "final_reward": rewards[-1] if rewards else None,
        "curve": curve,
        "hardware": _hardware(),
    }
    ARTIFACT_DIR.mkdir(parents=True, exist_ok=True)
    (ARTIFACT_DIR / f"{name}{artifact_suffix}.json").write_text(
        json.dumps(out, indent=1)
    )
    return out


def main():
    args = sys.argv
    only = None
    if "--only" in args:
        only = args[args.index("--only") + 1]
    budget = None
    if "--budget" in args:
        budget = float(args[args.index("--budget") + 1])
    # --prefetch N overrides config.sample_prefetch for A/B runs of the
    # pipelined vs synchronous sampling path (0 = force synchronous);
    # artifacts get a _prefetchN suffix so both sides persist
    overrides = None
    suffix = ""
    if "--prefetch" in args:
        n = int(args[args.index("--prefetch") + 1])
        overrides = {"sample_prefetch": n}
        suffix = f"_prefetch{n}"
    if "--plumbing" in args:
        run_plumbing(budget)
        return
    names = [only] if only else list(CONFIGS)
    summary = {}
    for name in names:
        r = run_config(name, budget, overrides, suffix)
        summary[name] = {
            "env_steps_per_sec": r["env_steps_per_sec"],
            "best_reward": r["best_reward"],
            "final_reward": r["final_reward"],
            "env_steps": r["env_steps"],
        }
        print(f"# {name}: {summary[name]}", file=sys.stderr)
    agg = round(
        float(np.mean([s["env_steps_per_sec"] for s in summary.values()])), 1
    )
    print(
        json.dumps(
            {
                "metric": "e2e_env_steps_per_sec_mean",
                "value": agg,
                "unit": "env_steps/s",
                "vs_baseline": None,
                "configs": summary,
                "artifacts": str(ARTIFACT_DIR),
            }
        )
    )


if __name__ == "__main__":
    main()
