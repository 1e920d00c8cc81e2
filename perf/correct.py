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

LIMITS. No limit is a number in this file. Each configuration brings
``perf/limits/<name>.json``: every limit beside the largest number
sound runs gave and the smallest each control gave, on the chip, at the
cell's own size (``perf/manifest.Limits``). A check asks its cell for a
limit by name. They are data of the yardstick: a later PR adds a file
for its own configuration and may not move another's.

WHAT IS HERE is what the comparisons share; each comparison itself is
``perf/checks/<name>.py`` (``STAGE``, ``LIMITS``, ``run(state)`` over a
``CheckState``; an after-warm-up check may add ``prepare(state)``, and
one whose numbers a limit is set from ``readings(state)``), found by
the name a configuration's or a traffic mix's ``checks`` lists.
"""

from __future__ import annotations

import re
from typing import Any, Dict, List

import numpy as np


class CheckState:
    """What one comparison may read: the cell and its files, the
    system under test, the reference and the weights it made from the
    seed, and the rows of ``correct`` so far."""

    def __init__(self, cell, algo, policy, ref, ref_params, seed: int,
                 num_actions: int, devices, checks: "Checks"):
        self.cell = cell
        self.algo = algo
        self.policy = policy
        self.ref = ref
        self.ref_params = ref_params
        self.seed = seed
        self.num_actions = num_actions
        self.devices = devices
        self.checks = checks
        # after warm-up: what each check's ``prepare`` returned before
        # the check iteration, by check name, and that iteration's
        # ``{"before", "after"}`` counters and ``"result"``
        self.prepared: Dict[str, Any] = {}
        self.iteration: Dict[str, Any] = {}


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


def rel_l2(a, b) -> float:
    a = np.asarray(a, np.float64).ravel()
    b = np.asarray(b, np.float64).ravel()
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def compare_grads(sys_grads: Dict, ref_grads: Dict,
                  leaf_floor: float = 0.0) -> Dict[str, float]:
    """Both in the reference's names: ``{layer: {kernel, bias}}``.
    ``leaf_floor`` is the least denominator of a single leaf's
    distance, as a share of the whole reference gradient's norm: a
    leaf whose true gradient is zero (a key bias under softmax) is
    otherwise rounding over rounding."""
    pairs = []
    for layer in sorted(ref_grads):
        for leaf in sorted(ref_grads[layer]):
            s = np.asarray(sys_grads[layer][leaf], np.float64)
            r = np.asarray(ref_grads[layer][leaf], np.float64)
            if s.shape != r.shape:
                raise ValueError(
                    f"gradient leaf {layer}/{leaf}: system {s.shape} "
                    f"vs reference {r.shape}"
                )
            pairs.append((f"{layer}/{leaf}", s.ravel(), r.ravel()))
    whole_s = np.concatenate([s for _, s, _ in pairs])
    whole_r = np.concatenate([r for _, _, r in pairs])
    least = max(leaf_floor * float(np.linalg.norm(whole_r)), 1e-30)
    worst, worst_name = 0.0, ""
    for name, s, r in pairs:
        d = float(np.linalg.norm(s - r) / max(np.linalg.norm(r), least))
        if d > worst:
            worst, worst_name = d, name
    return {
        "grad_rel_l2": rel_l2(whole_s, whole_r),
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


def distances(per_batch: List[Dict[str, float]]) -> Dict[str, Any]:
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


def seeded_batches(ref, config, seed: int, rows: int, batches: int,
                   num_actions: int):
    for i in range(batches):
        rng = np.random.default_rng([int(seed), 1, i])
        yield ref.make_batch(rng, config, rows, num_actions)


def _spec_of(leaf) -> tuple:
    """The leaf's partition spec as a tuple of its rank (an axis name
    or None for each dimension)."""
    spec = tuple(getattr(leaf.sharding, "spec", ()))
    return spec + (None,) * (leaf.ndim - len(spec))


def layout_checks(checks: Checks, policy, devices, layout) -> None:
    """The learner's mesh spans exactly the cell's chips, and the
    parameters lie on them as the configuration's ``param_layout``
    states: ``"replicated"`` (whole on every chip), or ``{"mesh":
    {axis: size}, "rules": [[leaf-path regex, partition spec], ...],
    "fullest_chip_share": x}`` — every leaf's spec equals that of the
    first rule its "/"-joined path matches and is replicated where none
    does, at least one leaf is really split, and the fullest chip holds
    at most the stated share of the parameters' bytes."""
    import jax

    want = set(devices)
    checks.true(
        "mesh_spans_every_chip",
        set(policy.mesh.devices.flat) == want,
        f"mesh over {sorted(d.id for d in policy.mesh.devices.flat)}",
    )
    leaves = jax.tree_util.tree_leaves_with_path(policy.params)
    if layout == "replicated":
        checks.true(
            "params_replicated_on_every_chip",
            bool(leaves)
            and all(
                set(leaf.sharding.device_set) == want and leaf.is_fully_replicated
                for _, leaf in leaves
            ),
        )
        return
    checks.equal(
        "mesh_axes_as_stated",
        {k: int(v) for k, v in policy.mesh.shape.items()},
        {k: int(v) for k, v in layout["mesh"].items()},
    )
    rules = [
        (re.compile(pat),
         tuple(tuple(e) if isinstance(e, list) else e for e in spec))
        for pat, spec in layout["rules"]
    ]
    misplaced, split = [], 0
    on_chip = {d: 0 for d in want}
    for path, leaf in leaves:
        name = jax.tree_util.keystr(path, simple=True, separator="/")
        stated = next((spec for pat, spec in rules if pat.search(name)), ())
        stated = stated + (None,) * (leaf.ndim - len(stated))
        if _spec_of(leaf) != stated or set(leaf.sharding.device_set) != want:
            misplaced.append(name)
        split += not leaf.is_fully_replicated
        for shard in leaf.addressable_shards:
            on_chip[shard.device] += shard.data.nbytes
    checks.equal(
        "param_leaves_not_laid_out_as_stated", len(misplaced), 0,
        f"{len(leaves)} leaves, {split} split over chips"
        + (f"; misplaced: {misplaced[:4]}" if misplaced else ""),
    )
    checks.true("params_split_over_chips", split > 0, f"{split} leaves")
    total = sum(leaf.nbytes for _, leaf in leaves)
    checks.at_most(
        "param_share_on_fullest_chip",
        max(on_chip.values()) / max(total, 1),
        float(layout["fullest_chip_share"]),
        f"{max(on_chip.values())} B of {total} B on the fullest chip",
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


def compare_updates(sys_out: Dict, ref_out: Dict, start_params: Dict,
                    loss_floor: float) -> Dict[str, float]:
    """Distances of one K-update outcome from the reference's. Both
    hold ``params`` in the reference's names, ``leaves`` (before and
    after the refresh) and ``last_loss``; ``loss_floor`` is the least
    denominator of the relative loss."""
    p0 = _flat(start_params)
    changed = sys_out["leaves"] != sys_out["leaves_before"]
    expected = ref_out["leaves"] != ref_out["leaves_before"]
    touched = np.flatnonzero(expected)
    return {
        "superstep_update_rel_l2": rel_l2(
            _flat(sys_out["params"]) - p0, _flat(ref_out["params"]) - p0
        ),
        "superstep_priority_rel_l2": rel_l2(
            sys_out["leaves"][touched], ref_out["leaves"][touched]
        ),
        "superstep_rows_refreshed_wrongly": int(np.sum(changed != expected)),
        "superstep_loss_rel": abs(sys_out["last_loss"] - ref_out["last_loss"])
        / max(abs(ref_out["last_loss"]), loss_floor),
    }
