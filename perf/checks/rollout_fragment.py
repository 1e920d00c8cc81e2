"""The rollout the window runs against the reference's full forward.

After the program's first iteration (the streams were brought to the
traffic's phase in set-up: ``token_streams_at_phase``, so their caches
are 0 to an episode deep) the check dispatches the lane's own rollout
body once more (``JaxRolloutEngine.rollout_from``: the
body the fused superstep scans, as the standalone program) from the
engine's live carry with the policy's live weights: 64 streams
generate a fragment token by token from carried state. Nothing is
committed; the window goes on from the carry it had. What that
dispatch stored is then held to the plain reference run over the SAME
tokens from the SAME start states (logits, not sampled ids):

- ``rollout_logit_rel_l2``, ``rollout_value_rel_l2``: the logits and
  values stored for every generated token;
- ``rollout_state_rel_l2``: the state after the fragment (DeltaNet
  matrices, convolution inputs, the keys and values below each
  stream's position); ``rollout_positions_wrong`` counts streams whose
  position differs;
- ``route_top_k_mismatch_share``: tokens x layers whose top-k expert
  set, in the policy's learn form over the same fragment, differs from
  the float32 reference's;
- ``forms_logit_rel_l2``: the policy's learn form (chunked, from the
  stored start states) against its own rollout: the PPO ratio divides
  one by the other;
- ``rollout_advantage_rel_l2``: the in-program GAE and
  standardisation against the reference's own, from ITS values.

The controls put the reference, computed with int8 or float8 operands,
in the system's place. The reference runs in blocks of streams, with
the policy's weight arrays as views."""

import time

import numpy as np

from perf import correct

STAGE = "after_first_iterations"
LIMITS = (
    "rollout_logit_rel_l2", "rollout_value_rel_l2", "rollout_state_rel_l2",
    "rollout_positions_wrong", "route_top_k_mismatch_share",
    "forms_logit_rel_l2", "rollout_advantage_rel_l2",
)
BLOCK = 8  # streams a reference call
_FNS = {}  # jitted forwards, by what defines them: one compile a process


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
    keep = ("obs", "resets", "action_dist_inputs", "vf_preds", "rewards",
            "dones", "truncateds", "advantages")
    out = {k: np.asarray(batch[k]) for k in keep}
    out["start"] = [
        np.asarray(batch[f"__chunk__state_in_{k}"])
        for k in range(len(carry["state"]))
    ]
    # the state after the fragment stays on the device: it is compared there
    end = {"state": tuple(carry["state"]), "obs": np.asarray(carry["obs"])}
    return out, end, eng.N, eng.T


def _blocks(n):
    return [slice(i, min(n, i + BLOCK)) for i in range(0, n, BLOCK)]


def _forward_fn(state, precision):
    import jax

    ref, config, actions = state.ref, state.cell.config, state.num_actions

    def both(p, tok, st, fr, next_tok, next_fresh):
        """The fragment, and one token further for the tail's value."""
        got = ref.forward(p, tok, st, fr, config, actions, precision)
        nxt = ref.forward(p, next_tok, got["state"], next_fresh, config, actions,
                          precision)
        return got, nxt["value"][:, 0]

    return _FNS.setdefault(("reference", precision), jax.jit(both))


def _learn_form_fn(state):
    import jax

    model = state.policy.model

    def apply(p, tok, st, fr):
        stats = {"moe_routes": None}
        logits, _, _ = model.apply(p, tok[..., None], st, resets=fr, stats_out=stats)
        return logits, stats["moe_routes"]

    return _FNS.setdefault("learn_form", jax.jit(apply))


def _like(x, rows=None):
    """The abstract value of ``x`` (of its first ``rows`` rows, as a
    block handed over from the host: no placement)."""
    import jax

    if rows is None:
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
    return jax.ShapeDtypeStruct((rows,) + tuple(x.shape[1:]), x.dtype)


def ahead(state):
    """``(jitted, abstract arguments, under "highest")`` of the two
    large programs this comparison calls, for
    ``token_streams_at_phase`` to compile on a thread beside the fused
    program's own compile: the reference's forward over a block and
    the policy's learn form over one. What they are compiled for is
    what ``_reference`` and ``_learn_form`` hand them (blocks from the
    host, the policy's weights where they lie), so the calls find them
    in the compile cache; a shape that differed would only compile
    again there."""
    import jax
    import jax.numpy as jnp

    eng = state.algo._jax_engine()
    params = jax.tree_util.tree_map(_like, state.policy.params)
    views = state.ref.from_policy_tree(params, state.cell.config)
    start = tuple(_like(s, BLOCK) for s in eng._carry["state"])
    tokens = jax.ShapeDtypeStruct((BLOCK, eng.T), eng._carry["obs"].dtype)
    fresh = jax.ShapeDtypeStruct((BLOCK, eng.T), jnp.bool_)
    resets = jax.ShapeDtypeStruct((BLOCK, eng.T), jnp.float32)
    one = lambda like: jax.ShapeDtypeStruct((BLOCK, 1), like.dtype)
    return [
        (_forward_fn(state, "float32"),
         (views, tokens, start, fresh, one(tokens), one(fresh)), True),
        (_learn_form_fn(state), (params, tokens, start, resets), False),
    ]


def _wait_ahead(state):
    thread = state.prepared.pop("compile_ahead", None)
    if thread is not None:
        thread.join(timeout=300)  # past that the calls compile for themselves


def _reference(state, rolled, end, n, t, precision="float32"):
    """The reference over the same tokens from the same start states,
    and one token further for the tail's value. ``"state"`` is a list
    of the blocks' states after the fragment, left on the device."""
    import jax
    import jax.numpy as jnp

    _wait_ahead(state)
    views = state.ref.from_policy_tree(state.policy.params, state.cell.config)
    fwd = _forward_fn(state, precision)
    tokens = rolled["obs"].reshape(n, t)
    fresh = rolled["resets"].reshape(n, t) > 0.5
    done = (rolled["dones"] | rolled["truncateds"]).reshape(n, t)
    out = {"logits": [], "value": [], "routes": [], "tail": [], "state": []}
    with jax.default_matmul_precision("highest"):
        for rows in _blocks(n):
            start = tuple(jnp.asarray(s[rows]) for s in rolled["start"])
            # the next token of each stream opens an episode where the
            # fragment's last step ended one
            got, tail = fwd(
                views, jnp.asarray(tokens[rows]), start, jnp.asarray(fresh[rows]),
                jnp.asarray(end["obs"][rows].reshape(-1, 1)),
                jnp.asarray(done[rows, -1:]))
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
    """The policy's learn form over the same fragment: logits and
    every token's top-k set."""
    import jax.numpy as jnp

    _wait_ahead(state)
    fn = _learn_form_fn(state)
    tokens = rolled["obs"].reshape(n, t)
    resets = rolled["resets"].reshape(n, t)
    logits, routes = [], []
    for rows in _blocks(n):
        start = tuple(jnp.asarray(s[rows]) for s in rolled["start"])
        lg, rt = fn(state.policy.params, jnp.asarray(tokens[rows]), start,
                    jnp.asarray(resets[rows]))
        logits.append(np.asarray(lg))
        routes.append(np.asarray(rt).reshape(rt.shape[0], -1, t, rt.shape[-1]))
    return np.concatenate(logits), np.concatenate(routes, axis=1)


def _advantages(state, rolled, values, tail, n, t):
    """The reference's GAE from ITS values, rows env-major like the
    program's: ``(standardised advantages, value targets)``."""
    algo = state.cell.config["algo_config"]
    values = values.reshape(n, t).T
    term = rolled["dones"].reshape(n, t).T
    done = term | rolled["truncateds"].reshape(n, t).T
    next_values = np.concatenate([values[1:], tail[None]], axis=0)
    adv, targets = state.ref.gae(
        rolled["rewards"].reshape(n, t).T, values, next_values, term, done,
        float(algo["gamma"]), float(algo["lambda"]),
    )
    return state.ref.standardize(adv).T.reshape(n * t), targets.T.reshape(n * t)


def _state_sums(got, want, positions, going_on):
    """Jitted, a block: ``(sum of squared differences, sum of squares
    of the reference's)`` over the state's float leaves of the streams
    whose episode goes on past the fragment (the lane has already
    reset the others); a cache (streams, slots, row) counts below each
    stream's position."""
    import jax.numpy as jnp

    num = den = jnp.float32(0.0)
    for a, b in zip(got[:-1], want[:-1]):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        keep = going_on.reshape((-1,) + (1,) * (a.ndim - 1))
        if a.ndim == 3 and a.shape[1] >= 8:  # a cache, not a convolution's tail
            keep = keep & (
                jnp.arange(a.shape[1])[None, :, None] < positions[:, None, None])
        num += jnp.sum(jnp.where(keep, jnp.square(a - b), 0.0))
        den += jnp.sum(jnp.where(keep, jnp.square(b), 0.0))
    return num, den


def _state_distance(got, want, positions, going_on, n):
    """Relative L2 of the state after the fragment, summed on the
    device block by block (the caches alone are half a gigabyte of
    bfloat16: a minute of conversions on the host). ``got`` is the
    whole state (the lane's carry) or a list of blocks (a control's
    reference), ``want`` the reference's list of blocks."""
    import jax
    import jax.numpy as jnp

    fn = _FNS.setdefault("state_sums", jax.jit(_state_sums))
    num = den = 0.0
    for i, rows in enumerate(_blocks(n)):
        block = got[i] if isinstance(got, list) else tuple(s[rows] for s in got)
        a, b = fn(block, want[i], jnp.asarray(positions[rows]),
                  jnp.asarray(going_on[rows]))
        num, den = num + float(a), den + float(b)
    return float(np.sqrt(num / max(den, 1e-30)))


def _rel_l2(a, b, rows=64) -> float:
    """``correct.rel_l2`` over two large arrays a piece at a time: the
    float64 copies of 8,192 x 18,992 logits are gigabytes, and a piece
    of 10 MB stays in memory the allocator already holds."""
    a, b = a.reshape(len(a), -1), b.reshape(len(b), -1)
    num = den = 0.0
    for i in range(0, len(a), rows):
        y = b[i:i + rows].astype(np.float64)
        d = a[i:i + rows].astype(np.float64) - y
        num, den = num + float(np.sum(d * d)), den + float(np.sum(y * y))
    return float(np.sqrt(num) / max(np.sqrt(den), 1e-30))


def _sets_differ(a, b) -> float:
    return float(np.mean(np.any(np.sort(a, -1) != np.sort(b, -1), axis=-1)))


def _numbers(state, rolled, end, n, t, want, got_logits, got_value, got_state,
             got_routes, got_adv):
    ended = (rolled["dones"] | rolled["truncateds"]).reshape(n, t)[:, -1]
    # a stream whose episode ended on the fragment's last step is back
    # at position 0, its state reset
    positions = np.where(ended, 0, want["positions"])
    got_positions = np.concatenate([np.asarray(s[-1]) for s in got_state]) if (
        isinstance(got_state, list)) else np.asarray(got_state[-1])
    return {
        "rollout_logit_rel_l2": _rel_l2(got_logits, want["logits"]),
        "rollout_value_rel_l2": correct.rel_l2(got_value, want["value"]),
        "rollout_state_rel_l2": _state_distance(
            got_state, want["state"], want["positions"], ~ended, n),
        "rollout_positions_wrong": int(np.sum(got_positions != positions)),
        "route_top_k_mismatch_share": _sets_differ(got_routes, want["routes"]),
        "rollout_advantage_rel_l2": correct.rel_l2(
            got_adv,
            _advantages(state, rolled, want["value"], want["tail"], n, t)[0]),
    }


def _system(state):
    """The system's numbers, and what the controls are computed from."""
    laps = [("", time.perf_counter())]
    lap = lambda name: laps.append((name, time.perf_counter()))
    rolled, end, n, t = _dispatch(state)
    lap("dispatch")
    want = _reference(state, rolled, end, n, t)
    lap("reference")
    form_logits, form_routes = _learn_form(state, rolled, n, t)
    lap("learn_form")
    got = _numbers(
        state, rolled, end, n, t, want, rolled["action_dist_inputs"],
        rolled["vf_preds"], end["state"], form_routes, rolled["advantages"],
    )
    got["forms_logit_rel_l2"] = _rel_l2(
        form_logits, rolled["action_dist_inputs"])
    lap("numbers")
    print("[setup-part] rollout_fragment " + " ".join(
        f"{name}={b - a:.1f}s" for (_, a), (name, b) in zip(laps, laps[1:])),
        flush=True)
    depth = np.asarray(rolled["start"][-1])
    note = (
        f"{n} streams x {t} tokens generated by the lane's body from the "
        f"live carry; cache depths {int(depth.min())}-{int(depth.max())} at its "
        f"start, {len(np.unique(depth))} distinct"
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
    system's place on the same tokens."""
    got, _, (rolled, end, n, t, want) = _system(state)
    out = {"system": got}
    for precision in state.cell.control_precisions:
        low = _reference(state, rolled, end, n, t, precision)
        out[precision] = _numbers(
            state, rolled, end, n, t, want, low["logits"], low["value"],
            low["state"], low["routes"],
            _advantages(state, rolled, low["value"], low["tail"], n, t)[0],
        )
        # not a precision's: the forms and the reset are the system's
        out[precision]["forms_logit_rel_l2"] = 0.0
        out[precision]["rollout_positions_wrong"] = 0
    return out
