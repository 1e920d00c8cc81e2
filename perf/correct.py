"""The comparison that decides ``correct``.

Every check is a number beside its limit; a run is correct when every
number is within its limit. The learner check compares the SYSTEM's
loss and per-leaf gradients — taken from the program's own sharded
learn body (``JaxPolicy._build_learn_fn``: shard_map, per-shard
gradient, ``pmean``, optimizer), not from a side computation — with
the plain reference's on one seeded minibatch and seeded weights.

The replay check goes further down the same road: the ring is filled
by the benchmark from the seed, ONE real superstep dispatch (K updates
drawn through the device sum tree, rows gathered from the ring in the
scan, priorities refreshed) runs, and the weights after it, the
refreshed priorities and the set of rows refreshed are compared with
the reference's K updates on the rows it knows were drawn.

LIMITS. The system computes convs in bfloat16 (8-bit mantissa); the
reference is float32 at precision "highest". So the two differ by
bf16 rounding carried through three convs and a dense layer, forward
and backward: a few parts in a thousand of the gradient's norm. The
controls — the reference one precision step down, int8 or float8
operands — differ by several percent. Each limit below sits between
the largest number sound runs gave and the smallest either control
gave, on the chip, at the cell's own size; PERF.md section 2 has both
readings for each. They are data of the yardstick: a later PR may not
move them.
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

# name -> limit. Readings: my chip runs, PR 24, TPU v5 lite, at the
# cell's size (PERF.md section 2 has the table): "sound" is the largest
# the system read over 61 seeds (40 through ``python3 -m perf.control``,
# 21 more in benchmark runs), "int8" and "fp8" the smallest each
# control read over the 40.
LIMITS = {
    # relative L2 distance of the whole gradient from the reference's:
    # sound 0.0118 (one seed; the next 0.0097; mean 0.0081, sd 0.0010),
    # int8 0.0280, fp8 0.0223. Was 0.015 until the 0.0118 was read.
    "grad_rel_l2": 1.6e-2,
    # the worst single leaf's relative L2 distance (structure: a leaf
    # missing reads 1): sound 0.069; the controls do not separate
    "grad_leaf_rel_l2_max": 2.5e-1,
    # |loss - reference loss| / max(|reference loss|, loss_floor)
    # (a wrong term or coefficient): sound 0.00153
    "loss_rel": 6.0e-3,
    # prioritized draw against cumsum+searchsorted: rows that differ
    "tree_draw_mismatches": 0,
    "tree_weight_rel_max": 1.0e-5,
    # one real superstep (K updates) against the reference's K updates:
    # refreshed leaves at the rows drawn, the forward pass of the online
    # and target networks through gather, updates and refresh:
    # sound 0.00430 (mean 0.0030, sd 0.0004), int8 0.0140, fp8 0.00985
    "superstep_priority_rel_l2": 6.0e-3,
    # weights after - weights before (structure: rows from the wrong
    # place read 1.4): sound 0.107; Adam's step is a sign where the
    # gradient is large, so rounding flips entries and the controls
    # do not separate (int8 0.127, fp8 0.059)
    "superstep_update_rel_l2": 3.0e-1,
    "superstep_rows_refreshed_wrongly": 0,  # rows refreshed xor rows drawn
    "superstep_loss_rel": 6.0e-3,  # sound 0.00034
    # the ring's leaves against the seeded priorities ^ alpha (numpy's
    # power differs in the last place with the array's length):
    # sound 1.8e-15
    "ring_leaves_rel_max": 1.0e-12,
}
LOSS_FLOOR = 0.05
# the loss of real ring rows under seeded weights is small (most TD
# errors are hundredths): a floor below it
SUPERSTEP_LOSS_FLOOR = 1e-3
# the precision steps below the configurations' bf16 that the controls take
CONTROL_PRECISIONS = ("int8", "fp8")
# seeded minibatches a run compares on
BATCHES = 4


class Checks:
    """Collects ``(name, value, limit, ok)`` and prints each as it is
    made, so every run shows each number compared beside its limit."""

    def __init__(self):
        self.rows: List[Dict[str, Any]] = []

    def at_most(self, name: str, value: float, limit: float, note: str = ""):
        ok = bool(np.isfinite(value)) and value <= limit
        self._add(name, float(value), limit, ok, note)

    def equal(self, name: str, value, want, note: str = ""):
        self._add(name, value, want, value == want, note)

    def true(self, name: str, ok: bool, note: str = ""):
        self._add(name, bool(ok), True, bool(ok), note)

    def _add(self, name, value, limit, ok, note):
        row = {"check": name, "value": value, "limit": limit, "ok": ok}
        if note:
            row["note"] = note
        self.rows.append(row)
        print(f"[correct] {row}", flush=True)

    @property
    def ok(self) -> bool:
        return bool(self.rows) and all(r["ok"] for r in self.rows)


def _rel_l2(a, b) -> float:
    a = np.asarray(a, np.float64).ravel()
    b = np.asarray(b, np.float64).ravel()
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def compare_grads(sys_grads: Dict, ref_grads: Dict) -> Dict[str, float]:
    """Both in the reference's names: ``{layer: {kernel, bias}}``."""
    flat_s, flat_r, worst, worst_name = [], [], 0.0, ""
    for layer in sorted(ref_grads):
        for leaf in sorted(ref_grads[layer]):
            s = np.asarray(sys_grads[layer][leaf], np.float64)
            r = np.asarray(ref_grads[layer][leaf], np.float64)
            if s.shape != r.shape:
                raise ValueError(
                    f"gradient leaf {layer}/{leaf}: system {s.shape} "
                    f"vs reference {r.shape}"
                )
            flat_s.append(s.ravel())
            flat_r.append(r.ravel())
            d = _rel_l2(s, r)
            if d > worst:
                worst, worst_name = d, f"{layer}/{leaf}"
    return {
        "grad_rel_l2": _rel_l2(np.concatenate(flat_s), np.concatenate(flat_r)),
        "grad_leaf_rel_l2_max": worst,
        "worst_leaf": worst_name,
    }


_REF_FNS: Dict[Any, Any] = {}  # jitted reference programs, by what defines them


def _reference_fn(ref, config, precision):
    import json

    import jax

    key = (ref.__name__, json.dumps(config, sort_keys=True), precision)
    if key not in _REF_FNS:
        _REF_FNS[key] = jax.jit(
            jax.value_and_grad(lambda p, b: ref.loss(p, b, config, precision))
        )
    return _REF_FNS[key]


def reference_loss_and_grads(ref, params, batch, config, precision="float32"):
    """The reference's loss and gradients, float32 at "highest" (or
    the control's int8). A stated ``grad_clip`` is the optimizer's
    first stage in the system, so the reference applies the same rule
    to its own gradient."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        loss, grads = _reference_fn(ref, config, precision)(
            params, {k: jnp.asarray(v) for k, v in batch.items()}
        )
    grads = jax.device_get(grads)
    clip = config["algo_config"].get("grad_clip")
    if clip:
        norm = float(
            np.sqrt(
                sum(
                    float(np.sum(np.square(np.asarray(x, np.float64))))
                    for x in jax.tree_util.tree_leaves(grads)
                )
            )
        )
        if norm > float(clip):
            grads = jax.tree_util.tree_map(
                lambda g: g * (float(clip) / norm), grads
            )
    return float(loss), grads


def system_learn_step(policy, rows: int):
    """The policy's OWN learn program for one optimizer step over a
    whole minibatch of ``rows`` (one epoch, one minibatch). That is
    the program's real path: ``shard_map`` over the mesh, the
    per-shard ``value_and_grad`` of ``varying(params)``, the
    ``pmean``, the clip, Adam."""
    saved = (policy.num_sgd_iter, policy.minibatch_size)
    policy.num_sgd_iter, policy.minibatch_size = 1, rows
    try:
        return policy._build_learn_fn(rows)
    finally:
        policy.num_sgd_iter, policy.minibatch_size = saved


def system_loss_and_grads(policy, step_fn, batch: Dict[str, np.ndarray]):
    """Run ``step_fn`` from a fresh optimizer state and read the
    gradient back out of Adam's first moment, ``mu = (1 - b1) * g``
    after the first step. A sum in place of the mean over shards
    comes back N times too large. Returns ``(loss, grads as the
    policy's param tree)``."""
    import jax

    opt0 = jax.device_put(
        policy._tx.init(policy.params),
        policy._opt_sharding or policy._param_sharding,
    )
    dev = jax.device_put(batch, policy.batch_shardings(batch))
    _, opt1, stats = step_fn(
        policy.params, opt0, policy.aux_state, dev, jax.random.PRNGKey(0),
        policy._coeff_array(),
    )
    adam = next(s for s in opt1 if hasattr(s, "mu"))
    b1 = 0.9  # optax.scale_by_adam's default, which JaxPolicy uses
    grads = jax.tree_util.tree_map(
        lambda m: np.asarray(m, np.float64) / (1.0 - b1),
        jax.device_get(adam.mu),
    )
    return float(stats["total_loss"]), grads


def _distances(per_batch: List[Dict[str, float]]) -> Dict[str, Any]:
    """Over the seeded minibatches: the root mean square of the whole
    gradient's distance (steadier from seed to seed than one batch's),
    the worst leaf's and the worst loss's."""
    worst = max(per_batch, key=lambda d: d["grad_leaf_rel_l2_max"])
    return {
        "grad_rel_l2": float(
            np.sqrt(np.mean([d["grad_rel_l2"] ** 2 for d in per_batch]))
        ),
        "grad_leaf_rel_l2_max": worst["grad_leaf_rel_l2_max"],
        "worst_leaf": worst["worst_leaf"],
        "loss_rel": max(d["loss_rel"] for d in per_batch),
    }


def _seeded_batches(ref, config, seed: int, rows: int, num_actions: int):
    for i in range(BATCHES):
        rng = np.random.default_rng([int(seed), 1, i])
        yield ref.make_batch(rng, config, rows, num_actions)


def learner_check(checks: Checks, cell, policy, ref, ref_params, seed: int,
                  num_actions: int, rows: int = 512) -> Dict[str, Any]:
    """System against reference on ``BATCHES`` seeded minibatches of
    ``rows``."""
    step_fn = system_learn_step(policy, rows)
    per_batch = []
    for batch in _seeded_batches(ref, cell.config, seed, rows, num_actions):
        ref_loss, ref_grads = reference_loss_and_grads(
            ref, ref_params, batch, cell.config
        )
        sys_loss, sys_tree = system_loss_and_grads(policy, step_fn, batch)
        d = compare_grads(ref.from_policy_tree(sys_tree, cell.config), ref_grads)
        d["loss_rel"] = abs(sys_loss - ref_loss) / max(abs(ref_loss), LOSS_FLOOR)
        per_batch.append(d)
    out = _distances(per_batch)
    checks.at_most(
        "grad_rel_l2", out["grad_rel_l2"], LIMITS["grad_rel_l2"],
        f"rms over {BATCHES} minibatches of {rows} rows on "
        f"{policy.n_shards} shard(s); last system loss {sys_loss:.6g}, "
        f"reference {ref_loss:.6g}",
    )
    checks.at_most(
        "grad_leaf_rel_l2_max", out["grad_leaf_rel_l2_max"],
        LIMITS["grad_leaf_rel_l2_max"], f"worst leaf {out['worst_leaf']}",
    )
    checks.at_most("loss_rel", out["loss_rel"], LIMITS["loss_rel"])
    return out


def control_readings(cell, ref, ref_params, seed: int, num_actions: int,
                     rows: int = 512,
                     precision: str = "int8") -> Dict[str, Any]:
    """A control: the reference in the system's place, computed in
    ``precision``. Not part of a benchmark run; ``perf/control.py``
    and the tests call it."""
    per_batch = []
    for batch in _seeded_batches(ref, cell.config, seed, rows, num_actions):
        ref_loss, ref_grads = reference_loss_and_grads(
            ref, ref_params, batch, cell.config
        )
        ctl_loss, ctl_grads = reference_loss_and_grads(
            ref, ref_params, batch, cell.config, precision=precision
        )
        d = compare_grads(ctl_grads, ref_grads)
        d["loss_rel"] = abs(ctl_loss - ref_loss) / max(abs(ref_loss), LOSS_FLOOR)
        per_batch.append(d)
    return _distances(per_batch)


def mesh_checks(checks: Checks, policy, devices) -> None:
    """Params replicated on every chip of the cell; the learner's mesh
    spans exactly those chips."""
    import jax

    want = set(devices)
    checks.true(
        "mesh_spans_every_chip",
        set(policy.mesh.devices.flat) == want,
        f"mesh over {sorted(d.id for d in policy.mesh.devices.flat)}",
    )
    leaves = jax.tree_util.tree_leaves(policy.params)
    checks.true(
        "params_replicated_on_every_chip",
        bool(leaves)
        and all(
            set(leaf.sharding.device_set) == want and leaf.is_fully_replicated
            for leaf in leaves
        ),
    )


def rows_split_evenly(tree: Dict, rows: int, devices) -> bool:
    """Every column of ``tree`` with ``rows`` leading rows holds one
    equal shard on each device."""
    want = set(devices)
    seen = 0
    for col in tree.values():
        if not getattr(col, "shape", None) or col.shape[0] != rows:
            continue
        seen += 1
        shards = col.addressable_shards
        if {s.device for s in shards} != want:
            return False
        if any(s.data.shape[0] != rows // len(want) for s in shards):
            return False
    return seen > 0


def _flat(tree) -> np.ndarray:
    """A ``{layer: {kernel, bias}}`` tree as one float64 vector, in
    sorted order."""
    return np.concatenate([
        np.asarray(tree[layer][leaf], np.float64).ravel()
        for layer in sorted(tree)
        for leaf in sorted(tree[layer])
    ])


def reference_draws(ref, raw_priorities, seed: int, k: int, rows: int,
                    alpha: float, beta: float):
    """What the K draws of one superstep must be: the leaves the
    seeded priorities make, the uniform stream the benchmark hands the
    buffer, and the plain stratified draw over them. Priorities are
    frozen within a superstep, so all K draws see the same leaves."""
    leaves = np.maximum(np.asarray(raw_priorities, np.float64), 1e-6) ** alpha
    rng = draw_stream(seed)
    idx, weights = [], []
    for _ in range(k):
        i, w = ref.stratified_draw(leaves, rng.random(rows), beta)
        idx.append(i)
        weights.append(w)
    return leaves, np.stack(idx), np.stack(weights)


def draw_stream(seed: int) -> np.random.Generator:
    """The uniform stream of the replay draws, from the seed: the
    benchmark gives the buffer this generator, and the reference a
    second one in the same state."""
    return np.random.default_rng([int(seed), 5])


def reference_updates(ref, ref_params, batches, config, precision="float32"):
    """``ref.updates`` (K clipped Adam updates and the |TD| each
    leaves behind), jitted once per precision."""
    import json

    import jax

    key = (ref.__name__, "updates", json.dumps(config, sort_keys=True), precision)
    if key not in _REF_FNS:
        _REF_FNS[key] = jax.jit(lambda p, b: ref.updates(p, b, config, precision))
    with jax.default_matmul_precision("highest"):
        return jax.device_get(_REF_FNS[key](ref_params, batches))


def refreshed_leaves(leaves, idx, abs_td, alpha: float) -> np.ndarray:
    """The leaves after a superstep's refresh: update by update, in
    order, ``(|td| + 1e-6) ^ alpha`` at the rows drawn."""
    out = np.array(leaves, np.float64)
    for i, td in zip(idx, abs_td):
        # the system adds the epsilon in float32, before the power
        out[i] = np.maximum(
            (np.asarray(td, np.float32) + np.float32(1e-6)).astype(np.float64), 1e-6
        ) ** alpha
    return out


def compare_updates(sys_out: Dict, ref_out: Dict, start_params: Dict) -> Dict[str, float]:
    """Distances of one K-update outcome from the reference's. Both
    hold ``params`` in the reference's names, ``leaves`` (before and
    after the refresh) and ``last_loss``."""
    p0 = _flat(start_params)
    changed = sys_out["leaves"] != sys_out["leaves_before"]
    expected = ref_out["leaves"] != ref_out["leaves_before"]
    touched = np.flatnonzero(expected)
    return {
        "superstep_update_rel_l2": _rel_l2(
            _flat(sys_out["params"]) - p0, _flat(ref_out["params"]) - p0
        ),
        "superstep_priority_rel_l2": _rel_l2(
            sys_out["leaves"][touched], ref_out["leaves"][touched]
        ),
        "superstep_rows_refreshed_wrongly": int(np.sum(changed != expected)),
        "superstep_loss_rel": abs(sys_out["last_loss"] - ref_out["last_loss"])
        / max(abs(ref_out["last_loss"]), SUPERSTEP_LOSS_FLOOR),
    }


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
    buf = algo.local_replay_buffer.buffers["default_policy"]
    raw = ringfill.seeded_priorities(seed, buf.capacity)
    leaves, idx, weights = reference_draws(
        ref, raw, seed, k, rows, float(rb["prioritized_replay_alpha"]),
        float(rb["prioritized_replay_beta"]),
    )
    env = algo.workers.local_worker().env
    _, picked = ringfill.bulk_fill(
        buf, env, num_actions, seed, cell.traffic["ring_fill"], want=idx
    )
    buf._rng = draw_stream(seed)
    batches = {
        c: v.reshape((k, rows) + v.shape[1:])
        for c, v in picked.items() if c != "truncateds"
    }
    batches["weights"] = jnp.asarray(weights, jnp.float32)
    return {"leaves": leaves, "idx": idx, "batches": batches, "k": k, "rows": rows}


def system_superstep(cell, algo, policy, ref, drawn: Dict) -> Dict:
    """ONE real superstep dispatch of the program: K updates drawn
    through the device tree, gathered from the ring in the scan,
    priorities refreshed. Returns its outcome in the reference's
    names."""
    import jax

    from ray_tpu.execution.train_ops import superstep_train_replay

    rb = cell.config["algo_config"]["replay_buffer_config"]
    buf = algo.local_replay_buffer.buffers["default_policy"]
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


def reference_superstep(cell, ref, ref_params, drawn: Dict, precision="float32") -> Dict:
    rb = cell.config["algo_config"]["replay_buffer_config"]
    out = reference_updates(ref, ref_params, drawn["batches"], cell.config, precision)
    return {
        "params": out["params"],
        "leaves_before": drawn["leaves"],
        "leaves": refreshed_leaves(
            drawn["leaves"], drawn["idx"], out["abs_td"],
            float(rb["prioritized_replay_alpha"]),
        ),
        "last_loss": float(out["losses"][-1]),
    }


def replay_superstep_check(checks: Checks, cell, algo, policy, ref, ref_params,
                           seed: int, num_actions: int) -> Dict[str, float]:
    """Fill the ring, run one real superstep, hold its outcome to the
    reference's. The ring is full, on the device, and its tree holds
    the seeded leaves before the superstep runs."""
    drawn = fill_ring_and_draw(cell, algo, ref, seed, num_actions)
    buf = algo.local_replay_buffer.buffers["default_policy"]
    capacity = int(cell.config["algo_config"]["replay_buffer_config"]["capacity"])
    checks.true("replay_ring_on_device", not buf.spilled,
                f"{buf.storage_bytes} B of ring")
    checks.equal("replay_tree_plane", buf.tree_plane, "device")
    checks.equal("replay_ring_rows_filled", len(buf), capacity,
                 f"capacity {buf.capacity}")
    if buf.spilled or buf.tree_plane != "device" or len(buf) != capacity:
        return {}
    tree_draw_check(checks, ref, buf, seed,
                    float(cell.config["algo_config"]["replay_buffer_config"]
                          ["prioritized_replay_beta"]))
    sys_out = system_superstep(cell, algo, policy, ref, drawn)
    checks.at_most(
        "ring_leaves_rel_max",
        float(np.max(np.abs(sys_out["leaves_before"] - drawn["leaves"])
                     / drawn["leaves"])),
        LIMITS["ring_leaves_rel_max"],
    )
    ref_out = reference_superstep(cell, ref, ref_params, drawn)
    d = compare_updates(sys_out, ref_out, ref_params)
    note = (f"{drawn['k']} updates of {drawn['rows']} rows in one dispatch; last "
            f"system loss {sys_out['last_loss']:.6g}, reference "
            f"{ref_out['last_loss']:.6g}")
    for name, value in d.items():
        if name == "superstep_rows_refreshed_wrongly":
            checks.equal(name, value, LIMITS[name],
                         f"{len(np.unique(drawn['idx']))} distinct rows drawn")
        else:
            checks.at_most(name, value, LIMITS[name], note)
            note = ""
    return d


def tree_draw_check(checks: Checks, ref, buf, seed: int, beta: float) -> None:
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
        LIMITS["tree_draw_mismatches"],
        f"{len(np.unique(leaves))} distinct priorities among {size} leaves",
    )
    checks.at_most(
        "tree_weight_rel_max",
        float(np.max(np.abs(weights - ref_w) / np.maximum(ref_w, 1e-12))),
        LIMITS["tree_weight_rel_max"],
    )


def rollout_rows_check(checks: Checks, buf, first: int, count: int,
                       frame_stack: int) -> None:
    """The rows one real iteration's device rollout put into the ring
    (``count`` rows from ring position ``first``) are transitions of
    the traffic mix's env: pixels of the env's three grey levels, each
    live row's ``new_obs`` its ``obs`` moved on by one frame, rewards
    of -1, 0 or 1, and not one constant action."""
    import jax

    pos = (int(first) + np.arange(int(count))) % buf.capacity
    rows = jax.device_get(buf.gather(pos).tree)
    obs, new_obs = rows["obs"], rows["new_obs"]
    live = ~(rows["dones"] | rows["truncateds"])
    shifted = bool(live.any())
    if frame_stack > 1:
        c = obs.shape[-1] // frame_stack
        shifted &= bool(np.array_equal(new_obs[live][..., :-c], obs[live][..., c:]))
    checks.true(
        "rollout_rows_are_transitions",
        shifted
        and bool(obs.any())
        and set(np.unique(obs)) <= {0, 180, 255}
        and set(np.unique(rows["rewards"])) <= {-1.0, 0.0, 1.0}
        and len(np.unique(rows["actions"])) > 1,
        f"{count} rows at ring position {first}: {int(live.sum())} live, "
        f"rewards {np.unique(rows['rewards']).tolist()}, "
        f"actions {np.unique(rows['actions']).tolist()}",
    )
