"""`python -m ray_tpu.train` — yaml/flag-driven training CLI.

Counterpart of the reference's ``rllib/train.py:160,280`` (`rllib train`):
accepts either a tuned-example style yaml experiment file or --run/--env
flags, drives tune.run, prints per-iteration progress, and writes a final
checkpoint.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict


def load_experiments(path: str) -> Dict:
    import yaml

    with open(path) as f:
        return yaml.safe_load(f)


def experiment_args(spec: Dict):
    """``(run, config, stop)`` of one experiment spec (a yaml entry or
    the flag-built equivalent): the config assembly this CLI hands to
    ``tune.run`` — shared with ``chip_smoke.py`` so the smoke run
    builds exactly what ``python -m ray_tpu.train -f`` would."""
    config = dict(spec.get("config") or {})
    if "env" in spec:
        config["env"] = spec["env"]
    stop = dict(spec.get("stop") or {})
    stop.pop("time_total_s", None)
    return spec["run"], config, stop


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description="ray_tpu train CLI")
    parser.add_argument(
        "-f", "--file", type=str, default=None,
        help="yaml experiment file (tuned_examples format)",
    )
    parser.add_argument("--run", type=str, default=None,
                        help="algorithm name, e.g. PPO")
    parser.add_argument("--env", type=str, default=None)
    parser.add_argument(
        "--stop", type=str, default="{}",
        help='json stop criteria, e.g. \'{"training_iteration": 10}\'',
    )
    parser.add_argument(
        "--config", type=str, default="{}",
        help="json config overrides",
    )
    parser.add_argument("--num-samples", type=int, default=1)
    parser.add_argument("--checkpoint-freq", type=int, default=0)
    parser.add_argument(
        "--local-dir", type=str,
        default=os.path.expanduser("~/ray_tpu_results"),
    )
    parser.add_argument("-v", "--verbose", action="store_true")
    args = parser.parse_args(argv)

    from ray_tpu.tune import run

    experiments = {}
    if args.file:
        raw = load_experiments(args.file)
        for name, spec in raw.items():
            experiments[name] = spec
    else:
        if not args.run or not args.env:
            parser.error("either --file or both --run and --env")
        experiments["default"] = {
            "run": args.run,
            "env": args.env,
            "stop": json.loads(args.stop),
            "config": json.loads(args.config),
        }

    for name, spec in experiments.items():
        run_name, config, stop = experiment_args(spec)
        print(f"== running experiment {name}: {run_name} ==")
        analysis = run(
            run_name,
            config=config,
            stop=stop,
            num_samples=int(spec.get("num_samples", args.num_samples)),
            checkpoint_freq=args.checkpoint_freq,
            local_dir=args.local_dir,
            verbose=1 if args.verbose else 0,
        )
        best = analysis.get_best_trial()
        if best is not None:
            print(
                json.dumps(
                    {
                        "experiment": name,
                        "best_reward": best.last_result.get(
                            "episode_reward_mean"
                        ),
                        "iterations": best.last_result.get(
                            "training_iteration"
                        ),
                        "timesteps": best.last_result.get(
                            "timesteps_total"
                        ),
                    }
                )
            )
    return 0


if __name__ == "__main__":
    sys.exit(main())
