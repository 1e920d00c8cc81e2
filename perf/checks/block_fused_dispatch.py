"""``fused_dispatch`` for a policy that commits a BLOCK of tokens a lane
step: one REAL iteration of the fused lane against the reference, end to
end, with that file's helpers, stages and limits' names.

What differs: the dispatch hands back the TRACE beside the tokens (the
engine keeps ``unmask_step`` of its last dispatch as it keeps the
actions), and the reference's account of the iteration reads both: its
forward over the tokens and their trace (``block_rollout_fragment``'s,
one ``2T``-row forward a denoise step) gives its old policy and its
values, and its loss is differentiated over batches whose tokens are the
``actions`` column and that carry ``unmask_step``. The env is replayed
on the reported actions a token at a time as for every other policy
(the lane steps it ``block_length`` times a lane step). So the gradient
held here is the update's three passes a fragment through the kernels,
the gradient through the clean pass's keys included, against a forward
that has no passes."""

import time

import numpy as np

STAGE = "after_first_iterations"
LIMITS = (
    "loss_rel", "grad_rel_l2", "grad_leaf_rel_l2_max", "update_rel_l2",
    "adam_step_rel_l2", "dispatch_rows_wrong",
)
TRACE = "unmask_step"


def _base(state):
    return state.cell._module("checks", "fused_dispatch")


def _lane(state):
    return state.cell._module("checks", "block_rollout_fragment")


def _batch_like(state, rows):
    """The abstract batch of ``rows`` tokens the reference's loss reads."""
    import jax
    import jax.numpy as jnp

    blocks = _lane(state)._base(state)
    eng = state.algo._jax_engine()
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    batch = {
        "actions": i32(rows), TRACE: i32(rows), "resets": f32(rows),
        "action_logp": f32(rows),
        "action_dist_inputs": f32(rows, state.num_actions),
        "advantages": f32(rows), "value_targets": f32(rows),
    }
    for k, leaf in enumerate(eng._carry["state"]):
        batch[f"__chunk__state_in_{k}"] = blocks._like(leaf, blocks.BLOCK)
    return batch


def ahead(state):
    """For ``token_streams_at_phase``'s compile thread: the reference's
    loss and gradient over a block of fragments, and the comparison of
    the three trees."""
    import jax

    base = _base(state)
    blocks = _lane(state)._base(state)
    eng = state.algo._jax_engine()
    params = jax.tree_util.tree_map(blocks._like, state.policy.params)
    views = state.ref.from_policy_tree(params, state.cell.config)
    algo = state.cell.config["algo_config"]
    sums = base._fn(
        ("leaf_sums", False), lambda: base._leaf_sums(state.ref, algo, False))
    return [
        (base._grad_fn(state, "float32"),
         (views, _batch_like(state, blocks.BLOCK * eng.T)), True),
        (sums, (views, views, views), False),
    ]


def _reference_iteration(state, rolled, end, n, t, precision="float32"):
    """``fused_dispatch._reference_iteration`` over the tokens and their
    trace: ``(loss, whole gradient, clipped like the system's, its
    norm before the clip)``."""
    import jax
    import jax.numpy as jnp

    cell, ref, base, lane = state.cell, state.ref, _base(state), _lane(state)
    blocks = lane._base(state)
    config, algo = cell.config, cell.config["algo_config"]
    want = lane._reference(state, rolled, end, n, t, precision)
    adv, targets = blocks._advantages(
        state, rolled, want["value"], want["tail"], n, t)
    logits = want["logits"]  # (n * t, V), env-major like the rows
    views = ref.from_policy_tree(state.policy.params, config)
    grad_fn = base._grad_fn(state, precision)
    add = base._fn("add", lambda: jax.jit(
        lambda acc, g: jax.tree_util.tree_map(jnp.add, acc, g), donate_argnums=0))
    acc, losses = None, []
    with jax.default_matmul_precision("highest"):
        for frags in blocks._blocks(n):
            rows = slice(frags.start * t, frags.stop * t)
            lg = logits[rows].astype(np.float64)
            top = lg.max(1, keepdims=True)
            logp = np.take_along_axis(
                lg - top - np.log(np.sum(np.exp(lg - top), 1, keepdims=True)),
                rolled["actions"][rows, None], 1)[:, 0]
            batch = {
                "actions": rolled["actions"][rows], TRACE: rolled[TRACE][rows],
                "resets": rolled["resets"][rows],
                "action_logp": logp.astype(np.float32),
                "action_dist_inputs": logits[rows],
                "advantages": adv[rows].astype(np.float32),
                "value_targets": targets[rows].astype(np.float32),
            }
            for k, leaf in enumerate(rolled["start"]):
                batch[f"__chunk__state_in_{k}"] = leaf[frags]
            loss, grads = grad_fn(views, {k: jnp.asarray(v) for k, v in batch.items()})
            losses.append(float(loss))
            acc = grads if acc is None else add(acc, grads)
            del grads
    # the mean over the blocks, then the system's clip: on the device
    sq = base._fn("sq", lambda: jax.jit(lambda tree: sum(
        jnp.sum(jnp.square(g)) for g in jax.tree_util.tree_leaves(tree))))
    scale = base._fn("scale", lambda: jax.jit(
        lambda tree, f: jax.tree_util.tree_map(lambda g: g * f, tree),
        donate_argnums=0))
    count = len(losses)
    norm = float(np.sqrt(float(sq(acc)))) / count
    clip = algo.get("grad_clip")
    factor = 1.0 / count
    if clip and norm > float(clip):
        factor *= float(clip) / norm
    return float(np.mean(losses)), scale(acc, np.float32(factor)), norm


def _system(state):
    import jax

    base = _base(state)
    laps = [("", time.perf_counter())]
    lap = lambda name: laps.append((name, time.perf_counter()))
    start, out = base._dispatch(state)
    # the trace of the same dispatch, (T, N) like the actions
    trace = np.asarray(state.algo._jax_rollout_engine.last_trace)
    trace = trace.reshape(trace.shape[-2:])
    lap("dispatch")
    t, n = out["actions"].shape
    rolled, replayed = base._replay(state, start, out["actions"])
    rolled[TRACE] = np.swapaxes(trace, 0, 1).reshape(-1).astype(np.int32)
    # Adam's first moment waits on the host while the reference's
    # gradient is accumulated
    mu = jax.device_get(out.pop("mu"))
    lap("replay_and_park")
    ref_loss, ref_grads, norm = _reference_iteration(state, rolled, replayed, n, t)
    lap("reference")
    to_ref = lambda tree: state.ref.from_policy_tree(tree, state.cell.config)
    # the three trees placed as the policy's weights are: what the
    # comparison was compiled for ahead
    where = jax.tree_util.tree_map(lambda x: x.sharding, state.policy.params)
    sys_grads = base._fn("unscale", lambda: jax.jit(
        lambda tree: jax.tree_util.tree_map(
            lambda m: m / np.float32(1.0 - base.B1), tree),
        donate_argnums=0))(jax.device_put(mu, where))
    del mu
    sys_grads = to_ref(jax.device_put(sys_grads, where))
    change = to_ref(jax.device_put(out.pop("change"), where))
    ref_grads = jax.device_put(ref_grads, to_ref(where))
    got = base._distances(state, out["loss"], sys_grads, change, ref_loss, ref_grads)
    del change
    got["dispatch_rows_wrong"] = base._rows_wrong(out["end"], replayed)
    lap("distances")
    note = (
        f"one Algorithm.train(): {n} streams x {t} tokens generated a block at "
        f"a time and trained in one dispatch; system loss {out['loss']:.6g}, "
        f"reference {ref_loss:.6g}; reference gradient norm {norm:.4g} before "
        f"the clip; {int(rolled['resets'].sum())} episode starts inside, depths "
        f"{int(start['state'][-1].min())}-{int(start['state'][-1].max())} at the start"
    )
    print("[setup-part] block_fused_dispatch " + " ".join(
        f"{name}={b - a:.1f}s" for (_, a), (name, b) in zip(laps, laps[1:])),
        flush=True)
    return got, note, (sys_grads, rolled, replayed, n, t, ref_loss, ref_grads)


def run(state):
    got, note, trees = _system(state)
    del trees  # gigabytes of gradients go before the optimizer state comes
    _base(state)._fresh_optimizer(state.policy)
    for name in LIMITS:
        check = state.checks.equal if name == "dispatch_rows_wrong" else (
            state.checks.at_most)
        if "leaf" in name:
            note = f"worst leaf {got['worst_leaf']}"
        check(name, got[name], state.cell.limit(name), note)
        note = ""
    return got


def readings(state):
    """``{"system": {...}, "<precision>": {...}}`` for ``perf.control``
    (see ``fused_dispatch.readings``)."""
    import jax

    base = _base(state)
    got, _, (sys_grads, rolled, replayed, n, t, ref_loss, ref_grads) = _system(state)
    del sys_grads
    out = {"system": got}
    for precision in state.cell.control_precisions:
        held = jax.device_get(ref_grads)
        del ref_grads
        loss, grads, _ = _reference_iteration(state, rolled, replayed, n, t, precision)
        ref_grads = jax.device_put(held)
        del held
        out[precision] = base._distances(state, loss, grads, None, ref_loss, ref_grads)
        del grads
        # not a precision's: the optimizer and the carry are the system's
        out[precision]["adam_step_rel_l2"] = 0.0
        out[precision]["dispatch_rows_wrong"] = 0
    base._fresh_optimizer(state.policy)
    return out
