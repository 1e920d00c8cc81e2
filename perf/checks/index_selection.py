"""The rows the program's index CHOOSES against the reference's.

After the program's first iteration the check dispatches the lane's own
rollout body once more from the live carry (``rollout_fragment``'s
``_dispatch``; nothing is committed) and takes what it generated: the
tokens of every stream and the start states they were generated from.
Over that fragment the policy's LEARN form hands out every query's
chosen rows of every layer (``SequenceLM.apply(..., stats_out=
{"index_choices": None})``: a mask over the cache's slots and then the
fragment's own rows), and the plain reference its own
(``forward(...)["selected"]``: a stable sort a query of the full score
matrix in float32).

- ``index_top_k_mismatch_share``: of the (query, row) choices the
  reference makes, the share the program does not make. Both choose
  ``min(rows seen, topk)`` rows a query, so every row chosen by one and
  not by the other is a swap: the rows in dispute are those whose scores
  lie within the program's bfloat16 rounding of the ``topk``-th, which
  the index ranked last, and what a swap does to the logits is in
  ``rollout_logit_rel_l2`` / ``forms_logit_rel_l2``, which decide;
- ``index_rows_selected_wrong``: queries whose count of chosen rows is
  not ``min(position + 1, topk)``, exact.

The controls put the reference, computed with int8 or float8 operands
(the index's two projections and its score product among them), in the
program's place."""

import time

import numpy as np

STAGE = "after_first_iterations"
LIMITS = ("index_top_k_mismatch_share", "index_rows_selected_wrong")
_FNS = {}


def _lane(state):
    return state.cell._module("checks", "rollout_fragment")


def _program_fn(state):
    import jax

    model = state.policy.model

    def chosen(p, tok, st, fr):
        stats = {"index_choices": None}
        model.apply(p, tok[..., None], st, resets=fr, stats_out=stats)
        return stats["index_choices"]

    return _FNS.setdefault("program", jax.jit(chosen))


def _reference_chosen(state, precision, views, tokens, start, fresh):
    """The reference's choices over a block, by the program
    ``rollout_fragment`` compiled for its own comparison (the fragment
    and one token further: the same shapes, no second compile of the
    reference's forward)."""
    import jax.numpy as jnp

    fwd = _lane(state)._forward_fn(state, precision)
    one = jnp.zeros((tokens.shape[0], 1), tokens.dtype)
    got, _ = fwd(views, tokens, start, fresh, one, jnp.zeros(one.shape, bool))
    return got["selected"]


def ahead(state):
    """Called by ``token_streams_at_phase`` when it starts its compile
    thread: the learn form that hands out its choices is compiled HERE
    on a thread of this check's own, beside that one (which is the
    longest path of a run on an empty cache: nine seconds more at its
    end are nine seconds of set-up), and the harness's thread gets no
    job of ours."""
    import threading

    import jax
    import jax.numpy as jnp

    lane = _lane(state)
    eng = state.algo._jax_engine()
    params = jax.tree_util.tree_map(lane._like, state.policy.params)
    start = tuple(lane._like(s, lane.BLOCK) for s in eng._carry["state"])
    tokens = jax.ShapeDtypeStruct((lane.BLOCK, eng.T), eng._carry["obs"].dtype)
    resets = jax.ShapeDtypeStruct((lane.BLOCK, eng.T), jnp.float32)
    fn = _program_fn(state)

    def work():
        try:
            fn.lower(params, tokens, start, resets).compile()
        except Exception as e:  # the call compiles for itself then
            print(f"[setup-part] index_selection ahead gave up: {e!r}"[:300], flush=True)

    thread = threading.Thread(target=work, name="index_selection_ahead", daemon=True)
    thread.start()
    state.prepared["index_selection_ahead"] = thread
    return []


def _counts(got, want, positions, top_k):
    """Jitted, a block: ``(choices of the reference's the other side
    lacks, the reference's choices, queries whose count is off)``."""
    import jax.numpy as jnp

    due = jnp.minimum(positions + 1, top_k)[None]
    return (jnp.sum(want & ~got), jnp.sum(want),
            jnp.sum(jnp.sum(got, axis=-1) != due))


def _positions(start, fresh):
    """Each token's position in its episode, on the host."""
    out = np.zeros(fresh.shape, np.int32)
    at = np.asarray(start, np.int64).copy()
    for t in range(fresh.shape[1]):
        at = np.where(fresh[:, t], 0, at)
        out[:, t] = at
        at = at + 1
    return out


def _numbers(state, rolled, n, t, side):
    """``side(rows)``: the choices of a block of streams ``(layers, b,
    T, slots)`` on the device; against the float32 reference's."""
    import jax
    import jax.numpy as jnp

    lane = _lane(state)
    views = state.ref.from_policy_tree(state.policy.params, state.cell.config)
    top_k = int(state.cell.config["sa_config"]["topk"])
    tokens = rolled["obs"].reshape(n, t)
    fresh = rolled["resets"].reshape(n, t) > 0.5
    positions = _positions(rolled["start"][-1], fresh)
    counts = _FNS.setdefault("counts", jax.jit(_counts, static_argnums=3))
    lane._wait_ahead(state)
    own = state.prepared.pop("index_selection_ahead", None)
    if own is not None:
        own.join(timeout=120)
    missing = chosen = off = 0
    for rows in lane._blocks(n):
        start = tuple(jnp.asarray(s[rows]) for s in rolled["start"])
        args = (jnp.asarray(tokens[rows]), start)
        with jax.default_matmul_precision("highest"):
            want = _reference_chosen(
                state, "float32", views, *args, jnp.asarray(fresh[rows]))
        got = side(rows, views, args, fresh[rows])
        a, b, c = counts(got, want, jnp.asarray(positions[rows]), top_k)
        missing, chosen, off = missing + int(a), chosen + int(b), off + int(c)
    return {"index_top_k_mismatch_share": missing / max(chosen, 1),
            "index_rows_selected_wrong": off}, chosen


def _system(state):
    import jax.numpy as jnp

    t0 = time.perf_counter()
    rolled, _, n, t = _lane(state)._dispatch(state)
    fn = _program_fn(state)

    def program(rows, views, args, fresh):
        return fn(state.policy.params, *args, jnp.asarray(fresh, jnp.float32))

    got, chosen = _numbers(state, rolled, n, t, program)
    depth = np.asarray(rolled["start"][-1])
    print(f"[setup-part] index_selection in {time.perf_counter() - t0:.1f}s",
          flush=True)
    note = (f"{n} streams x {t} queries of every layer's learn form over a "
            f"fragment the lane generated; "
            f"{chosen} choices of the reference's; depths {int(depth.min())}-"
            f"{int(depth.max())} at its start")
    return got, note, (rolled, n, t)


def run(state):
    got, note, _ = _system(state)
    state.checks.at_most(
        "index_top_k_mismatch_share", got["index_top_k_mismatch_share"],
        state.cell.limit("index_top_k_mismatch_share"), note)
    state.checks.equal(
        "index_rows_selected_wrong", got["index_rows_selected_wrong"],
        state.cell.limit("index_rows_selected_wrong"))
    return got


def readings(state):
    """``{"system": {...}, "<precision>": {...}}`` for ``perf.control``:
    each control is the reference's choice in that precision in the
    program's place on the same fragment."""
    import jax
    import jax.numpy as jnp

    got, _, (rolled, n, t) = _system(state)
    out = {"system": got}
    for precision in state.cell.control_precisions:
        def low(rows, views, args, fresh, precision=precision):
            with jax.default_matmul_precision("highest"):
                return _reference_chosen(
                    state, precision, views, *args, jnp.asarray(fresh))

        out[precision], _ = _numbers(state, rolled, n, t, low)
    return out
