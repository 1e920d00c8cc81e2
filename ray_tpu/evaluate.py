"""`python -m ray_tpu.evaluate` — rollout a trained checkpoint.

Counterpart of the reference's ``rllib/evaluate.py:282`` (`rllib evaluate`).
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description="ray_tpu evaluate CLI")
    parser.add_argument("checkpoint", type=str)
    parser.add_argument("--run", type=str, required=True)
    parser.add_argument("--env", type=str, required=True)
    parser.add_argument("--episodes", type=int, default=10)
    parser.add_argument("--config", type=str, default="{}")
    parser.add_argument("--explore", action="store_true")
    args = parser.parse_args(argv)

    from ray_tpu.algorithms.registry import get_algorithm_class

    cls = get_algorithm_class(args.run)
    config = json.loads(args.config)
    config.update({"env": args.env, "num_workers": 0})
    algo = cls(config=config)
    algo.restore(args.checkpoint)

    import gymnasium as gym

    from ray_tpu.env.registry import get_env_creator

    env = get_env_creator(args.env)({})
    rewards = []
    for ep in range(args.episodes):
        obs, _ = env.reset(seed=ep)
        done = trunc = False
        total = 0.0
        state = algo.get_policy().get_initial_state() or None
        while not (done or trunc):
            if state:
                action, state, _ = algo.compute_single_action(
                    obs, state, explore=args.explore
                )
            else:
                action = algo.compute_single_action(
                    obs, explore=args.explore
                )
            obs, r, done, trunc, _ = env.step(action)
            total += float(r)
        rewards.append(total)
        print(f"episode {ep}: reward={total}")
    print(
        json.dumps(
            {
                "episodes": args.episodes,
                "mean_reward": float(np.mean(rewards)),
                "max_reward": float(np.max(rewards)),
            }
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
