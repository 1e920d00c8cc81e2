"""One REAL iteration of the fused lane against the reference, end to end.

The check calls ``Algorithm.train()`` once: the ONE ``rollout_superstep[``
dispatch the window times, at its sizes (64 streams x 128 generated
tokens from the carried state, in-program GAE, one PPO update over the
8,192 tokens as 64 fragments from their stored start states, params and
carry donated). Before it, the carry is copied to the host, the seeded
weights are made again on the device (``ref.init_params(host=False)``:
the first iterations moved them) and the optimizer state is made
fresh, so that after it Adam's first moment is ``(1 - b1) x`` the
clipped gradient the program took. What the dispatch hands back (the tokens it generated, its loss, the
weights and the moment after it, the carry after it) is then held to
the plain reference's OWN account of the same iteration from the same
start: the env replayed on those tokens (observations, rewards, episode
ends), the reference's forward over them from the same start states
(its logits are its old policy, its values feed its own GAE and
standardisation), its loss and whole gradient in blocks of fragments,
its clip and its Adam step. The trees (the program's gradient, the
reference's, the weights' change: 2.5 GB each) stay on the device and
are compared there, leaf by leaf, in one jitted call: on the host the
same sums took over a minute of set-up.

- ``loss_rel``: the dispatch's ``total_loss`` against the reference's;
- ``grad_rel_l2``, ``grad_leaf_rel_l2_max``: the whole gradient, read
  out of Adam's first moment, against the reference's (clipped alike);
- ``update_rel_l2``: the weights' change against the reference's Adam
  step on ITS gradient;
- ``adam_step_rel_l2``: the weights' change against the reference's
  Adam step on the gradient the program took (learning rate, bias
  correction, epsilon, the commit of the donated weights);
- ``dispatch_rows_wrong``: streams whose carry after the dispatch (last
  token, place in the episode, episode length, model position) differs
  from the replay's.

Afterwards the seeded weights are put back and the optimizer state is
fresh: the iteration trained nothing that stays; the streams did move.
The controls put the reference, computed with int8 or float8 operands
(its forward, hence its advantages, and its gradient), in the system's
place."""

import time

import numpy as np

STAGE = "after_first_iterations"
LIMITS = (
    "loss_rel", "grad_rel_l2", "grad_leaf_rel_l2_max", "update_rel_l2",
    "adam_step_rel_l2", "dispatch_rows_wrong",
)
B1 = 0.9  # optax.scale_by_adam's default, which JaxPolicy uses
WEIGHTS_FOLD = 7  # ``perf.run.load_seeded_weights`` folds the seed's key with it
_FNS = {}  # jitted programs, by what defines them: one compile a process


def _fn(name, make):
    if name not in _FNS:
        _FNS[name] = make()
    return _FNS[name]


def _fresh_optimizer(policy):
    import jax

    policy.opt_state = None  # the old moments go before the new ones come
    policy.opt_state = jax.device_put(
        policy._tx.init(policy.params),
        policy._opt_sharding or policy._param_sharding,
    )


def _seed_weights(state):
    """The seeded weights, made again on the device (no copy through
    the host) and handed to the policy, whose old ones go first."""
    import jax

    policy = state.policy
    policy.params = None
    key = jax.random.fold_in(
        jax.random.PRNGKey(int(state.seed) % (2**31 - 1)), WEIGHTS_FOLD
    )
    policy.set_weights(state.ref.to_policy_tree(
        state.ref.init_params(key, state.cell.config, state.num_actions, host=False),
        state.cell.config,
    ))


def _dispatch(state):
    """One real iteration from the seeded weights and a fresh
    optimizer state. Returns the carry it started from (on the host)
    and what came out: the tokens, the loss and the carry after it on
    the host; Adam's first moment and the weights' change ON THE
    DEVICE. Leaves the seeded weights and NO optimizer state (the
    reference needs the room; ``run`` makes a fresh one last)."""
    import jax

    algo, policy = state.algo, state.policy
    eng = algo._jax_rollout_engine
    start = jax.device_get(
        {k: eng._carry[k] for k in ("env", "obs", "ep_len", "state")}
    )
    _seed_weights(state)
    _fresh_optimizer(policy)
    result = algo.train()
    info = result["info"]["learner"]["default_policy"]
    actions = np.asarray(eng.last_actions)
    mu = next(s for s in policy.opt_state if hasattr(s, "mu")).mu
    policy.opt_state = None  # the second moment goes; ``mu`` stays
    new = policy.params
    _seed_weights(state)
    sub = _fn("sub", lambda: jax.jit(
        lambda a, b: jax.tree_util.tree_map(lambda x, y: x - y, a, b),
        donate_argnums=0))
    out = {
        "actions": actions.reshape(actions.shape[-2:]),  # (T, N)
        "loss": float(info["total_loss"]),
        "mu": mu,
        # float32 weights this close subtract exactly
        "change": sub(new, policy.params),
        "end": jax.device_get({
            "t": eng._carry["env"]["t"], "obs": eng._carry["obs"],
            "ep_len": eng._carry["ep_len"],
            "position": eng._carry["state"][-1],
        }),
    }
    return start, out


def _replay(state, start, actions):
    """The env on the dispatch's own tokens, from the carry it started
    from, as the lane steps it (auto-reset at an episode's end): the
    rows a reference needs, env-major, and the env after them."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.env.jax_env import tree_where

    env = state.algo._jax_rollout_engine.env

    def run(env_state, obs, ep_len, actions):
        def step(c, a):
            s, o, n = c
            s2, o2, rew, term, trunc = jax.vmap(env.step)(s, a)
            done = term | trunc
            s3, o3 = jax.vmap(env.reset)(s2)
            row = {"obs": o, "rewards": rew, "dones": term,
                   "truncateds": trunc, "resets": (n == 0).astype(jnp.float32)}
            return (tree_where(done, s3, s2), tree_where(done, o3, o2),
                    jnp.where(done, 0, n + 1)), row

        (s, o, n), rows = jax.lax.scan(step, (env_state, obs, ep_len), actions)
        return {"t": s["t"], "obs": o, "ep_len": n}, rows

    fn = _fn("replay", lambda: jax.jit(run))
    end, rows = jax.device_get(
        fn(start["env"], start["obs"], start["ep_len"], jnp.asarray(actions))
    )
    n = actions.shape[1]
    rolled = {
        k: np.swapaxes(v, 0, 1).reshape((n * v.shape[0],) + v.shape[2:])
        for k, v in rows.items()
    }
    rolled["actions"] = np.swapaxes(actions, 0, 1).reshape(-1)
    rolled["start"] = list(start["state"])
    return rolled, end


def _rows_wrong(got, replayed) -> int:
    """Streams whose carry after the dispatch is not the replay's; the
    model's position is the env's place in the episode (the streams
    were brought to phase in set-up)."""
    wrong = np.asarray(got["position"]) != np.asarray(replayed["t"])
    for k in ("t", "obs", "ep_len"):
        a, b = np.asarray(got[k]), np.asarray(replayed[k])
        wrong = wrong | np.any((a != b).reshape(a.shape[0], -1), axis=1)
    return int(np.sum(wrong))


def _grad_fn(state, precision):
    import jax

    ref, config = state.ref, state.cell.config
    return _fn(("grad", precision), lambda: jax.jit(jax.value_and_grad(
        lambda p, b: ref.loss(p, b, config, precision))))


def ahead(state):
    """For ``token_streams_at_phase``'s compile thread (see
    ``rollout_fragment.ahead``): the reference's loss and gradient over
    a block of fragments, and the comparison of the three trees (which
    ``_system`` places like the policy's weights for it)."""
    import jax
    import jax.numpy as jnp

    lane = state.cell._module("checks", "rollout_fragment")
    eng = state.algo._jax_engine()
    params = jax.tree_util.tree_map(lane._like, state.policy.params)
    views = state.ref.from_policy_tree(params, state.cell.config)
    rows = lane.BLOCK * eng.T
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    batch = {
        "obs": jax.ShapeDtypeStruct(
            (rows,) + tuple(eng._carry["obs"].shape[1:]), eng._carry["obs"].dtype),
        "actions": i32(rows), "resets": f32(rows), "action_logp": f32(rows),
        "action_dist_inputs": f32(rows, state.num_actions),
        "advantages": f32(rows), "value_targets": f32(rows),
    }
    for k, leaf in enumerate(eng._carry["state"]):
        batch[f"__chunk__state_in_{k}"] = lane._like(leaf, lane.BLOCK)
    algo = state.cell.config["algo_config"]
    sums = _fn(("leaf_sums", False), lambda: _leaf_sums(state.ref, algo, False))
    return [
        (_grad_fn(state, "float32"), (views, batch), True),
        (sums, (views, views, views), False),
    ]


def _reference_iteration(state, rolled, end, n, t, precision="float32"):
    """The reference's own account of the iteration, in ``precision``:
    ``(loss, whole gradient on the host, clipped like the system's)``.
    Its forward over the tokens gives its old policy and its values;
    its GAE and standardisation give the batch; its loss is
    differentiated a block of fragments at a time (``rollout_fragment``'s
    blocks) and the blocks' means averaged (the loss is a mean over
    tokens)."""
    import jax
    import jax.numpy as jnp

    cell, ref = state.cell, state.ref
    config, algo = cell.config, cell.config["algo_config"]
    lane = cell._module("checks", "rollout_fragment")
    want = lane._reference(state, rolled, end, n, t, precision)
    adv, targets = lane._advantages(state, rolled, want["value"], want["tail"], n, t)
    logits = want["logits"]  # (n * t, V), env-major like the rows
    views = ref.from_policy_tree(state.policy.params, config)
    grad_fn = _grad_fn(state, precision)
    add = _fn("add", lambda: jax.jit(
        lambda acc, g: jax.tree_util.tree_map(jnp.add, acc, g), donate_argnums=0))
    acc, losses = None, []
    with jax.default_matmul_precision("highest"):
        for frags in lane._blocks(n):
            rows = slice(frags.start * t, frags.stop * t)
            lg = logits[rows].astype(np.float64)
            top = lg.max(1, keepdims=True)
            logp = np.take_along_axis(
                lg - top - np.log(np.sum(np.exp(lg - top), 1, keepdims=True)),
                rolled["actions"][rows, None], 1)[:, 0]
            batch = {
                "obs": rolled["obs"][rows], "actions": rolled["actions"][rows],
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
    sq = _fn("sq", lambda: jax.jit(lambda tree: sum(
        jnp.sum(jnp.square(g)) for g in jax.tree_util.tree_leaves(tree))))
    scale = _fn("scale", lambda: jax.jit(
        lambda tree, f: jax.tree_util.tree_map(lambda g: g * f, tree),
        donate_argnums=0))
    blocks = len(losses)
    norm = float(np.sqrt(float(sq(acc)))) / blocks
    clip = algo.get("grad_clip")
    factor = 1.0 / blocks
    if clip and norm > float(clip):
        factor *= float(clip) / norm
    return float(np.mean(losses)), scale(acc, np.float32(factor)), norm


def _leaf_sums(ref, algo, change_is_own: bool):
    """The jitted comparison of three trees in the reference's names
    (the system's gradient, the reference's, the weights' change): for
    each leaf the six sums of squares the distances are made of, as one
    float32 vector. ``change_is_own``: the outcome is a control's
    gradient and its weights' change is the reference's Adam step on
    it (``change`` is not read)."""
    import jax
    import jax.numpy as jnp

    def adam_change(g):
        """The change the reference's first Adam step on ``g`` (already
        clipped) makes to a leaf, in the leaf's float32."""
        zero = jnp.zeros((), jnp.float32)
        after, _, _ = ref.adam_step(
            {"w": zero}, {"w": g}, {"w": zero}, {"w": zero}, 1, float(algo["lr"]),
            None, eps=float(algo.get("adam_epsilon", 1e-8)), b1=B1, xp=jnp,
        )
        return after["w"]

    def sums(s, r, change):
        by_ref, by_own = adam_change(r), adam_change(s)
        if change_is_own:
            change = by_own
        sq = lambda x: jnp.sum(jnp.square(x))
        return jnp.stack([sq(s - r), sq(r), sq(change - by_ref), sq(by_ref),
                          sq(change - by_own), sq(by_own)])

    return jax.jit(lambda s, r, c: jax.tree_util.tree_map(sums, s, r, c))


def _distances(state, out_loss, out_grads, change, ref_loss, ref_grads):
    """Leaf by leaf on the device, in float32 (a sum of squares is a
    tree reduction there). ``change`` None: the outcome is a control's
    gradient and its weights after are the reference's Adam step on
    it."""
    ref, algo = state.ref, state.cell.config["algo_config"]
    leaf_floor = state.cell.limits.floor("grad_leaf_rel_l2_max", 0.0)
    own = change is None
    fn = _fn(("leaf_sums", own), lambda: _leaf_sums(ref, algo, own))
    table = fn(out_grads, ref_grads, out_grads if own else change)
    leaves = {
        f"{layer}/{leaf}": np.asarray(v, np.float64)
        for layer, group in table.items() for leaf, v in group.items()
    }
    total = np.sum(list(leaves.values()), axis=0)
    whole = np.sqrt(total[1])
    least = max(leaf_floor * whole, 1e-30)
    worst = max(leaves, key=lambda k: np.sqrt(leaves[k][0])
                / max(np.sqrt(leaves[k][1]), least))
    return {
        "loss_rel": abs(out_loss - ref_loss)
        / max(abs(ref_loss), state.cell.limits.floor("loss_rel")),
        "grad_rel_l2": float(np.sqrt(total[0]) / max(whole, 1e-30)),
        "grad_leaf_rel_l2_max": float(
            np.sqrt(leaves[worst][0]) / max(np.sqrt(leaves[worst][1]), least)),
        "worst_leaf": worst,
        "update_rel_l2": float(np.sqrt(total[2] / max(total[3], 1e-300))),
        "adam_step_rel_l2": float(np.sqrt(total[4] / max(total[5], 1e-300))),
    }


def _system(state):
    import jax

    laps = [("", time.perf_counter())]
    lap = lambda name: laps.append((name, time.perf_counter()))
    start, out = _dispatch(state)
    lap("dispatch")
    t, n = out["actions"].shape
    rolled, replayed = _replay(state, start, out["actions"])
    # Adam's first moment waits on the host while the reference's
    # gradient is accumulated: weights, change, sum and one block's
    # gradient are 10 GB of the chip's 15.75 without it
    mu = jax.device_get(out.pop("mu"))
    lap("replay_and_park")
    ref_loss, ref_grads, norm = _reference_iteration(state, rolled, replayed, n, t)
    lap("reference")
    to_ref = lambda tree: state.ref.from_policy_tree(tree, state.cell.config)
    # the three trees placed as the policy's weights are: what the
    # comparison was compiled for ahead
    where = jax.tree_util.tree_map(lambda x: x.sharding, state.policy.params)
    sys_grads = _fn("unscale", lambda: jax.jit(
        lambda tree: jax.tree_util.tree_map(lambda m: m / np.float32(1.0 - B1), tree),
        donate_argnums=0))(jax.device_put(mu, where))
    del mu
    sys_grads = to_ref(jax.device_put(sys_grads, where))
    change = to_ref(jax.device_put(out.pop("change"), where))
    ref_grads = jax.device_put(ref_grads, to_ref(where))
    got = _distances(state, out["loss"], sys_grads, change, ref_loss, ref_grads)
    del change
    got["dispatch_rows_wrong"] = _rows_wrong(out["end"], replayed)
    lap("distances")
    note = (
        f"one Algorithm.train(): {n} streams x {t} tokens generated and "
        f"trained in one dispatch; system loss {out['loss']:.6g}, reference "
        f"{ref_loss:.6g}; reference gradient norm {norm:.4g} before the clip; "
        f"{int(rolled['resets'].sum())} episode starts inside, depths "
        f"{int(start['state'][-1].min())}-{int(start['state'][-1].max())} at the start"
    )
    print("[setup-part] fused_dispatch " + " ".join(
        f"{name}={b - a:.1f}s" for (_, a), (name, b) in zip(laps, laps[1:])),
        flush=True)
    return got, note, (sys_grads, rolled, replayed, n, t, ref_loss, ref_grads)


def run(state):
    got, note, trees = _system(state)
    del trees  # 5 GB of gradients go before the optimizer state comes
    _fresh_optimizer(state.policy)
    for name in LIMITS:
        check = state.checks.equal if name == "dispatch_rows_wrong" else (
            state.checks.at_most)
        if "leaf" in name:
            note = f"worst leaf {got['worst_leaf']}"
        check(name, got[name], state.cell.limit(name), note)
        note = ""
    return got


def readings(state):
    """``{"system": {...}, "<precision>": {...}}`` for ``perf.control``:
    a control is the reference's iteration in that precision in the
    system's place. The system's gradient has done its part and goes;
    the float32 reference's waits on the host while a control's is
    accumulated."""
    import jax

    got, _, (sys_grads, rolled, replayed, n, t, ref_loss, ref_grads) = _system(state)
    del sys_grads
    out = {"system": got}
    for precision in state.cell.control_precisions:
        held = jax.device_get(ref_grads)
        del ref_grads
        loss, grads, _ = _reference_iteration(state, rolled, replayed, n, t, precision)
        ref_grads = jax.device_put(held)
        del held
        out[precision] = _distances(state, loss, grads, None, ref_loss, ref_grads)
        del grads
        # not a precision's: the optimizer and the carry are the system's
        out[precision]["adam_step_rel_l2"] = 0.0
        out[precision]["dispatch_rows_wrong"] = 0
    _fresh_optimizer(state.policy)
    return out
