"""``rollout_fragment`` for a policy that commits a BLOCK of tokens a
lane step (``perf/reference/sdar.py``): the rollout the window runs
against the reference's one ``2T``-row forward a denoise step.

What differs from ``rollout_fragment.py``, whose helpers this file
borrows and whose limits' names it keeps: a fragment of ``T`` tokens is
``T / block_length`` lane steps (the lane takes the ``T`` keys all the
same and uses a block's first); the tokens are the ACTIONS the dispatch
stored, with their trace (``unmask_step``: the pass that committed
each), since the model reads no observation; the reference's forward
takes both, and what is stored for token ``i`` is held to ITS pass
``unmask_step[i]`` at ``i``; the tail's value is position 0 of the next
block's all-mask forward from the state after the fragment
(``ref.first_value``); the policy's learn form is its replay of the
trace (a clean and ``denoising_steps`` noisy passes) and its routes are
the clean pass's, held to the reference's clean rows'.

- ``rollout_logit_rel_l2``, ``rollout_value_rel_l2``: the logits and
  values the lane stored at every ``(unmask_step[i], i)``: denoise
  forwards of a block against the cache, against the rows of a forward
  that never ran token by token;
- ``rollout_state_rel_l2``: the caches after the fragment: the COMMIT
  forwards' rows (a cache left holding a denoise pass's rows, keys of
  ``[MASK]`` where a token was committed later, reads 0.89 at a small
  size on the CPU);
  ``rollout_positions_wrong`` counts streams whose position differs;
- ``route_top_k_mismatch_share``, ``forms_logit_rel_l2``,
  ``rollout_advantage_rel_l2``: as in ``rollout_fragment.py``.

The controls put the reference, computed with int8 or float8 operands,
in the system's place."""

import time

import numpy as np

STAGE = "after_first_iterations"
LIMITS = (
    "rollout_logit_rel_l2", "rollout_value_rel_l2", "rollout_state_rel_l2",
    "rollout_positions_wrong", "route_top_k_mismatch_share",
    "forms_logit_rel_l2", "rollout_advantage_rel_l2",
)
TRACE = "unmask_step"
_FNS = {}  # jitted forwards, by what defines them: one compile a process


def _base(state):
    return state.cell._module("checks", "rollout_fragment")


def _dispatch(state):
    """One more rollout of the lane's body from the live carry:
    ``(batch, end carry)`` on the host, as numpy."""
    import jax

    eng = state.algo._jax_rollout_engine
    policy = state.policy
    keys = jax.random.split(
        jax.random.fold_in(jax.random.PRNGKey(int(state.seed) % (2**31 - 1)), 23),
        eng.T,
    )
    carry, batch, _ = eng.rollout_from(
        policy.params, eng._carry, keys, eng._pre_dispatch()
    )
    keep = ("actions", TRACE, "resets", "action_dist_inputs", "vf_preds",
            "rewards", "dones", "truncateds", "advantages")
    out = {k: np.asarray(batch[k]) for k in keep}
    out["start"] = [
        np.asarray(batch[f"__chunk__state_in_{k}"])
        for k in range(len(carry["state"]))
    ]
    # the state after the fragment stays on the device: it is compared there
    return out, {"state": tuple(carry["state"])}, eng.N, eng.T


def _forward_fn(state, precision):
    import jax

    ref, config, actions = state.ref, state.cell.config, state.num_actions

    def both(p, tok, trace, st, fr, next_fresh):
        """The fragment, and the next block's first value for the tail."""
        got = ref.forward(p, tok, trace, st, fr, config, actions, precision)
        return got, ref.first_value(
            p, got["state"], next_fresh, config, actions, precision)

    return _FNS.setdefault(("reference", precision), jax.jit(both))


def _learn_form_fn(state):
    import jax

    model = state.policy.model

    def apply(p, tok, trace, st, fr):
        stats = {"moe_routes": None}
        logits, _, _ = model.apply(
            p, tok, st, resets=fr, trace=trace, stats_out=stats)
        return logits, stats["moe_routes"]

    return _FNS.setdefault("learn_form", jax.jit(apply))


def ahead(state):
    """For ``token_streams_at_phase``'s compile thread (see
    ``rollout_fragment.ahead``)."""
    import jax
    import jax.numpy as jnp

    base = _base(state)
    eng = state.algo._jax_engine()
    params = jax.tree_util.tree_map(base._like, state.policy.params)
    views = state.ref.from_policy_tree(params, state.cell.config)
    start = tuple(base._like(s, base.BLOCK) for s in eng._carry["state"])
    i32 = jax.ShapeDtypeStruct((base.BLOCK, eng.T), jnp.int32)
    fresh = jax.ShapeDtypeStruct((base.BLOCK, eng.T), jnp.bool_)
    resets = jax.ShapeDtypeStruct((base.BLOCK, eng.T), jnp.float32)
    ended = jax.ShapeDtypeStruct((base.BLOCK,), jnp.bool_)
    return [
        (_forward_fn(state, "float32"), (views, i32, i32, start, fresh, ended), True),
        (_learn_form_fn(state), (params, i32, i32, start, resets), False),
    ]


def _reference(state, rolled, end, n, t, precision="float32"):
    """The reference over the same tokens and trace from the same start
    states, and the next block's first value for the tail. ``"state"``
    is a list of the blocks' states after the fragment, left on the
    device."""
    import jax
    import jax.numpy as jnp

    base = _base(state)
    base._wait_ahead(state)
    views = state.ref.from_policy_tree(state.policy.params, state.cell.config)
    fwd = _forward_fn(state, precision)
    tokens = rolled["actions"].reshape(n, t).astype(np.int32)
    trace = rolled[TRACE].reshape(n, t).astype(np.int32)
    fresh = rolled["resets"].reshape(n, t) > 0.5
    done = (rolled["dones"] | rolled["truncateds"]).reshape(n, t)
    out = {"logits": [], "value": [], "routes": [], "tail": [], "state": []}
    with jax.default_matmul_precision("highest"):
        for rows in base._blocks(n):
            start = tuple(jnp.asarray(s[rows]) for s in rolled["start"])
            # the next block of a stream opens an episode where the
            # fragment's last step ended one
            got, tail = fwd(
                views, jnp.asarray(tokens[rows]), jnp.asarray(trace[rows]), start,
                jnp.asarray(fresh[rows]), jnp.asarray(done[rows, -1]))
            out["logits"].append(np.asarray(got["logits"]))
            out["value"].append(np.asarray(got["value"]))
            out["routes"].append(np.asarray(got["routes"]).reshape(
                got["routes"].shape[0], -1, t, got["routes"].shape[-1]))
            out["tail"].append(np.asarray(tail))
            out["state"].append(got["state"])
    return {
        "logits": np.concatenate(out["logits"]).reshape(n * t, -1),
        "value": np.concatenate(out["value"]).reshape(n * t),
        "routes": np.concatenate(out["routes"], axis=1),  # (layers, N, T, k)
        "tail": np.concatenate(out["tail"]),
        "state": out["state"],
        "positions": np.concatenate([np.asarray(st[-1]) for st in out["state"]]),
    }


def _learn_form(state, rolled, n, t):
    """The policy's replay of the trace over the same fragment: logits
    and the clean pass's top-k sets."""
    import jax.numpy as jnp

    base = _base(state)
    base._wait_ahead(state)
    fn = _learn_form_fn(state)
    tokens = rolled["actions"].reshape(n, t).astype(np.int32)
    trace = rolled[TRACE].reshape(n, t).astype(np.int32)
    resets = rolled["resets"].reshape(n, t)
    logits, routes = [], []
    for rows in base._blocks(n):
        start = tuple(jnp.asarray(s[rows]) for s in rolled["start"])
        lg, rt = fn(state.policy.params, jnp.asarray(tokens[rows]),
                    jnp.asarray(trace[rows]), start, jnp.asarray(resets[rows]))
        logits.append(np.asarray(lg))
        routes.append(np.asarray(rt).reshape(rt.shape[0], -1, t, rt.shape[-1]))
    return np.concatenate(logits), np.concatenate(routes, axis=1)


def _system(state):
    """The system's numbers, and what the controls are computed from."""
    base = _base(state)
    laps = [("", time.perf_counter())]
    lap = lambda name: laps.append((name, time.perf_counter()))
    rolled, end, n, t = _dispatch(state)
    lap("dispatch")
    want = _reference(state, rolled, end, n, t)
    lap("reference")
    form_logits, form_routes = _learn_form(state, rolled, n, t)
    lap("learn_form")
    got = base._numbers(
        state, rolled, end, n, t, want, rolled["action_dist_inputs"],
        rolled["vf_preds"], end["state"], form_routes, rolled["advantages"],
    )
    got["forms_logit_rel_l2"] = base._rel_l2(
        form_logits, rolled["action_dist_inputs"])
    lap("numbers")
    print("[setup-part] block_rollout_fragment " + " ".join(
        f"{name}={b - a:.1f}s" for (_, a), (name, b) in zip(laps, laps[1:])),
        flush=True)
    depth = np.asarray(rolled["start"][-1])
    passes = np.bincount(rolled[TRACE].reshape(-1).astype(np.int64))
    note = (
        f"{n} streams x {t} tokens generated a block at a time by the lane's "
        f"body from the live carry; tokens committed by pass {passes.tolist()}; "
        f"cache depths {int(depth.min())}-{int(depth.max())} at its start, "
        f"{len(np.unique(depth))} distinct"
    )
    return got, note, (rolled, end, n, t, want)


def run(state):
    got, note, _ = _system(state)
    for name in LIMITS:
        check = state.checks.equal if name == "rollout_positions_wrong" else (
            state.checks.at_most)
        check(name, got[name], state.cell.limit(name), note)
    return got


def readings(state):
    """``{"system": {...}, "<precision>": {...}}`` for ``perf.control``:
    each control is the reference, computed in that precision, in the
    system's place on the same tokens and trace."""
    base = _base(state)
    got, _, (rolled, end, n, t, want) = _system(state)
    out = {"system": got}
    for precision in state.cell.control_precisions:
        low = _reference(state, rolled, end, n, t, precision)
        out[precision] = base._numbers(
            state, rolled, end, n, t, want, low["logits"], low["value"],
            low["state"], low["routes"],
            base._advantages(state, rolled, low["value"], low["tail"], n, t)[0],
        )
        # not a precision's: the forms and the reset are the system's
        out[precision]["forms_logit_rel_l2"] = 0.0
        out[precision]["rollout_positions_wrong"] = 0
    return out
