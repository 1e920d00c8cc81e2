"""Replay superstep: the ring is filled by the benchmark from the seed,
ONE real superstep dispatch runs (K updates drawn through the device
sum tree, rows gathered from the ring in the scan, priorities
refreshed), and the weights after it, the refreshed priorities and the
set of rows refreshed are held to the reference's K updates on the
rows it knows were drawn. The ring is full, on the device, and its
tree holds the seeded leaves before the superstep runs."""

import numpy as np

from perf import correct

STAGE = "after_first_iterations"  # they define the ring's columns
LIMITS = (
    "tree_draw_mismatches", "tree_weight_rel_max", "ring_leaves_rel_max",
    "superstep_update_rel_l2", "superstep_priority_rel_l2",
    "superstep_rows_refreshed_wrongly", "superstep_loss_rel",
)


def _buffer(algo):
    return algo.local_replay_buffer.buffers["default_policy"]


def fill_ring_and_draw(cell, algo, ref, seed: int, num_actions: int):
    """Set-up of a replay cell: the whole ring overwritten with seeded
    rows and priorities (perf/ringfill.py), the buffer's uniform
    stream replaced by the seeded one. Returns what the reference
    needs to follow the next superstep: leaves, the K x B rows it
    draws (index, weight, content)."""
    import jax.numpy as jnp

    from perf import ringfill

    algo_cfg = cell.config["algo_config"]
    rb = algo_cfg["replay_buffer_config"]
    k = int(cell.traffic["expect"]["updates_per_iteration"])
    rows = int(algo_cfg["train_batch_size"])
    buf = _buffer(algo)
    raw = ringfill.seeded_priorities(seed, buf.capacity)
    leaves, idx, weights = correct.reference_draws(
        ref, raw, seed, k, rows, float(rb["prioritized_replay_alpha"]),
        float(rb["prioritized_replay_beta"]),
    )
    env = algo.workers.local_worker().env
    _, picked = ringfill.bulk_fill(
        buf, env, num_actions, seed, cell.traffic["ring_fill"], want=idx
    )
    buf._rng = correct.draw_stream(seed)
    batches = {
        c: v.reshape((k, rows) + v.shape[1:])
        for c, v in picked.items() if c != "truncateds"
    }
    batches["weights"] = jnp.asarray(weights, jnp.float32)
    return {"leaves": leaves, "idx": idx, "batches": batches, "k": k, "rows": rows}


def system_superstep(cell, algo, policy, ref, drawn):
    """ONE real superstep dispatch of the program: K updates drawn
    through the device tree, gathered from the ring in the scan,
    priorities refreshed. Returns its outcome in the reference's
    names."""
    import jax

    from ray_tpu.execution.train_ops import superstep_train_replay

    rb = cell.config["algo_config"]["replay_buffer_config"]
    buf = _buffer(algo)
    before = buf._dtree.leaf_values(len(buf))
    info = superstep_train_replay(
        algo, policy, buf, drawn["k"], drawn["k"], drawn["rows"],
        prioritized=True, beta=float(rb["prioritized_replay_beta"]),
    )
    if info is None:
        raise RuntimeError("the replay superstep refused this batch shape")
    return {
        "params": ref.from_policy_tree(jax.device_get(policy.params), cell.config),
        "leaves_before": before,
        "leaves": buf._dtree.leaf_values(len(buf)),
        "last_loss": float(info["total_loss"]),
    }


def reference_superstep(cell, ref, ref_params, drawn, precision="float32"):
    rb = cell.config["algo_config"]["replay_buffer_config"]
    out = correct.reference_updates(
        ref, ref_params, drawn["batches"], cell.config, precision
    )
    return {
        "params": out["params"],
        "leaves_before": drawn["leaves"],
        "leaves": correct.refreshed_leaves(
            drawn["leaves"], drawn["idx"], out["abs_td"],
            float(rb["prioritized_replay_alpha"]),
        ),
        "last_loss": float(out["losses"][-1]),
    }


def tree_draw_check(checks, cell, ref, buf, seed: int, beta: float) -> None:
    """The device tree's stratified draw equals the plain cumsum draw
    over the same leaves, row for row."""
    size = len(buf)
    leaves = buf._dtree.leaf_values(size)
    rng = np.random.default_rng([int(seed), 3])
    rand = rng.random(512)
    idx, weights = buf._dtree.draw(rand, size, beta)
    ref_idx, ref_w = ref.stratified_draw(leaves, rand, beta)
    idx = np.asarray(idx, np.int64)
    weights = np.asarray(weights, np.float64)
    checks.equal(
        "tree_draw_mismatches",
        int(np.sum(idx != ref_idx)),
        cell.limit("tree_draw_mismatches"),
        f"{len(np.unique(leaves))} distinct priorities among {size} leaves",
    )
    checks.at_most(
        "tree_weight_rel_max",
        float(np.max(np.abs(weights - ref_w) / np.maximum(ref_w, 1e-12))),
        cell.limit("tree_weight_rel_max"),
    )


def run(state):
    cell, algo, policy, ref = state.cell, state.algo, state.policy, state.ref
    checks, seed = state.checks, state.seed
    rb = cell.config["algo_config"]["replay_buffer_config"]
    drawn = fill_ring_and_draw(cell, algo, ref, seed, state.num_actions)
    buf = _buffer(algo)
    capacity = int(rb["capacity"])
    checks.true("replay_ring_on_device", not buf.spilled,
                f"{buf.storage_bytes} B of ring")
    checks.equal("replay_tree_plane", buf.tree_plane, "device")
    checks.equal("replay_ring_rows_filled", len(buf), capacity,
                 f"capacity {buf.capacity}")
    if buf.spilled or buf.tree_plane != "device" or len(buf) != capacity:
        return {}
    tree_draw_check(checks, cell, ref, buf, seed,
                    float(rb["prioritized_replay_beta"]))
    sys_out = system_superstep(cell, algo, policy, ref, drawn)
    checks.at_most(
        "ring_leaves_rel_max",
        float(np.max(np.abs(sys_out["leaves_before"] - drawn["leaves"])
                     / drawn["leaves"])),
        cell.limit("ring_leaves_rel_max"),
    )
    ref_out = reference_superstep(cell, ref, state.ref_params, drawn)
    d = correct.compare_updates(
        sys_out, ref_out, state.ref_params, cell.limits.floor("superstep_loss_rel")
    )
    note = (f"{drawn['k']} updates of {drawn['rows']} rows in one dispatch; last "
            f"system loss {sys_out['last_loss']:.6g}, reference "
            f"{ref_out['last_loss']:.6g}")
    for name, value in d.items():
        if name == "superstep_rows_refreshed_wrongly":
            checks.equal(name, value, cell.limit(name),
                         f"{len(np.unique(drawn['idx']))} distinct rows drawn")
        else:
            checks.at_most(name, value, cell.limit(name), note)
            note = ""
    # the bulk fill stands for that many sampled env steps: the
    # configuration's learning start and epsilon schedule see them
    algo._counters["num_env_steps_sampled"] += buf.capacity
    return d


def readings(state):
    """``{"system": {...}, "<precision>": {...}}``: the superstep's
    distances as sound runs give them, and with each control (the
    reference's K updates in that precision) in the system's place.
    For ``perf/control.py``; leaves the sampled-steps counter alone."""
    cell, ref, ref_params = state.cell, state.ref, state.ref_params
    floor = cell.limits.floor("superstep_loss_rel")
    drawn = fill_ring_and_draw(cell, state.algo, ref, state.seed, state.num_actions)
    sys_out = system_superstep(cell, state.algo, state.policy, ref, drawn)
    ref_out = reference_superstep(cell, ref, ref_params, drawn)
    out = {"system": correct.compare_updates(sys_out, ref_out, ref_params, floor)}
    for precision in cell.control_precisions:
        ctl = reference_superstep(cell, ref, ref_params, drawn, precision)
        out[precision] = correct.compare_updates(ctl, ref_out, ref_params, floor)
    return out
