"""Read the two numbers every ``correct`` limit is set from.

    python3 -m perf.control --workload <name> --seeds 12

In ONE process (set-up is paid once) and at the cell's own size, for
each seed: the distances of the SYSTEM's outcome from the plain
reference's (what sound runs give), and the distances of each
CONTROL's — the reference computed in the configuration's
``control_precisions`` (int8 and float8 where it states bf16), one
precision step below what it states, put in the system's place. The
limits in ``perf/limits/<config>.json`` sit above the largest of the
first and below the smallest of the second, and carry both readings.
Not part of a benchmark run; the same comparison at a small size is a
test in ``perf/tests``.
"""

from __future__ import annotations

import argparse
import json
import sys

from perf import correct as correct_lib
from perf import manifest as manifest_lib
from perf import run as run_lib


def _fresh_optimizer(policy) -> None:
    import jax

    policy.opt_state = jax.device_put(
        policy._tx.init(policy.params),
        policy._opt_sharding or policy._param_sharding,
    )


def readings(cell, seeds, require_tpu: bool = True):
    """One row a seed: ``{"seed", "system": {...}, "<control>": {...}}``
    with every number of the cell's set-up comparisons in each: what
    each check's own ``readings(state)`` gives."""
    import jax

    import ray_tpu as ray

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise SystemExit("perf.control: needs a TPU")
    algo = run_lib.build_algorithm(cell, 0, cell.chips, len(devices))
    out = []
    try:
        policy = algo.get_policy()
        num_actions = int(policy.action_space.n)
        ref = cell.reference()
        run_lib.warm_up(algo, (cell.traffic.get("warmup") or {}).get(
            "first_iterations", 0))
        for seed in seeds:
            seed32 = int(seed) % (2**31 - 1)
            ref_params = run_lib.load_seeded_weights(
                cell, policy, ref, seed32, num_actions
            )
            state = correct_lib.CheckState(
                cell, algo, policy, ref, ref_params, seed, num_actions,
                list(policy.mesh.devices.flat), correct_lib.Checks(),
            )
            row = {"seed": int(seed), "system": {}}
            row.update({p: {} for p in cell.control_precisions})
            for stage in manifest_lib.SETUP_STAGES:
                for _, check in cell.checks(stage):
                    if not hasattr(check, "readings"):
                        continue
                    _fresh_optimizer(policy)
                    for who, numbers in check.readings(state).items():
                        row[who].update(numbers)
            print(f"[control] {json.dumps(row)}", flush=True)
            out.append(row)
    finally:
        algo.cleanup()
        ray.shutdown()
    return out


def summary(cell, rows):
    """Per number: the largest the system read, the smallest each
    control read, and the limit."""
    out = {}
    for k in rows[0]["system"]:
        if k not in cell.limits:
            continue
        out[k] = {
            "system_max": max(r["system"][k] for r in rows),
            **{
                f"{p}_min": min(r[p][k] for r in rows)
                for p in cell.control_precisions
            },
            "limit": cell.limit(k),
        }
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="python3 -m perf.control")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, default=12)
    parser.add_argument("--first-seed", type=int, default=2147480000)
    args = parser.parse_args(argv)
    cell = manifest_lib.load_cell(args.workload)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    import jax

    rows = readings(cell, seeds)
    dev = jax.devices()
    print(json.dumps({
        "workload": cell.name,
        "device": {"platform": dev[0].platform, "kind": dev[0].device_kind,
                   "count": len(dev)},
        "summary": summary(cell, rows),
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
