"""The ``kda_rule`` comparison's readings alone, the system's and every
control's, a seed a row, in one process:

    chiprun --timeout 1500 -- env PYTHONPATH=. python3 perf/chip/kda_rule_readings.py <seed> ...

``perf.control`` reads every comparison of the cell (8 chip-minutes a
seed here); the four ``kda_rule_*`` limits need this one only (5 seeds in
9.4 chip-minutes, PR 61). For each seed the policy takes that seed's
weights and the streams are generated a whole episode on (16 fragments:
every stream is back at its phase, and all it carries was made under
those weights)."""
import json, sys, time
import numpy as np
from perf import correct as correct_lib
from perf import manifest as manifest_lib
from perf import run as run_lib

cell = manifest_lib.load_cell("ling3flash_ppo.fused_tokens.1chip")
seeds = [int(s) for s in sys.argv[1:]]
import jax
devices = jax.devices()
assert devices[0].platform == "tpu", devices
algo = run_lib.build_algorithm(cell, 0, cell.chips, len(devices))
policy = algo.get_policy()
num_actions = int(policy.action_space.n)
ref = cell.reference()
rule = cell._module("checks", "kda_rule")
eng = algo._jax_engine()
length = int(cell.traffic["algo_config"]["env_config"]["episode_length"])
for seed in seeds:
    t0 = time.perf_counter()
    seed32 = seed % (2**31 - 1)
    ref_params = run_lib.load_seeded_weights(cell, policy, ref, seed32, num_actions)
    del ref_params
    key = jax.random.fold_in(jax.random.PRNGKey(seed32), 29)
    for _ in range(length // eng.T):
        key, sub = jax.random.split(key)
        eng._carry, _, _ = eng.rollout_from(
            policy.params, eng._carry, jax.random.split(sub, eng.T), eng._pre_dispatch())
    at = np.asarray(eng._carry["env"]["t"])
    state = correct_lib.CheckState(
        cell, algo, policy, ref, None, seed, num_actions,
        list(policy.mesh.devices.flat), correct_lib.Checks())
    t1 = time.perf_counter()
    got, note, _ = rule._system(state)
    t2 = time.perf_counter()
    row = {"seed": seed, "depths": [int(at.min()), int(at.max())], "note": note,
           "system_s": t2 - t1}
    row.update(rule.readings(state))
    row["all_s"] = time.perf_counter() - t0
    print("[kda_rule] " + json.dumps(row), flush=True)
algo.cleanup()
