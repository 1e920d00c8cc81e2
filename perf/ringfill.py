"""Fill a device replay ring to its capacity in set-up, from the seed.

The window of a replay cell has to draw from a FULL ring, and filling
131,072 rows through the program's own 512-step rollouts would cost a
minute of every run. So the benchmark makes the rows itself, as it
makes the weights: the traffic mix's env stepped under uniformly
random actions (what the configuration's initial epsilon of 1.0 does),
``chunk_envs`` envs x ``chunk_steps`` steps a chunk, one jitted scan a
chunk with the env state carried from chunk to chunk, rows in the
rollout engine's env-major order and with its terminal-observation
rule (``new_obs`` is the pre-reset frame). Each chunk goes into the
ring through the buffer's public ``add_device_tree`` with seeded
priorities, so the sum tree is that of a ring in steady state and not
one constant.

Because the rows never come out of the program, the ``correct``
comparison can know them: ``bulk_fill`` also returns the rows at the
ring positions the caller names, picked out of each chunk BEFORE the
buffer sees it.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np

# the columns a transition row needs; any other column the program's
# own rollout rows carry (step index, env index, action extras) is
# zero in a bulk row: no loss reads them
ROW_COLUMNS = ("obs", "new_obs", "actions", "rewards", "dones", "truncateds")


def seeded_priorities(seed: int, rows: int) -> np.ndarray:
    """Raw priorities (before the alpha power) of a ring in steady
    state: positive, skewed, none equal."""
    rng = np.random.default_rng([int(seed), 4])
    return rng.exponential(0.3, rows) + 1e-3


def _chunk_fn(env, n_envs: int, steps: int, num_actions: int):
    import jax
    import jax.numpy as jnp

    from ray_tpu.env.jax_env import tree_where

    step_b = jax.vmap(env.step)
    reset_b = jax.vmap(env.reset)

    def chunk(carry, key):
        def step(c, k):
            state, obs = c
            actions = jax.random.randint(k, (n_envs,), 0, num_actions, jnp.int32)
            state2, obs2, rew, term, trunc = step_b(state, actions)
            done = term | trunc
            state3, obs3 = reset_b(state2)
            row = {
                "obs": obs,
                "new_obs": obs2,
                "actions": actions,
                "rewards": rew.astype(jnp.float32),
                "dones": term,
                "truncateds": trunc,
            }
            return (
                tree_where(done, state3, state2),
                tree_where(done, obs3, obs2),
            ), row

        carry, rows = jax.lax.scan(step, carry, jax.random.split(key, steps))
        # (T, N, ...) -> env-major (N*T, ...), the engine's row order
        rows = {
            k: jnp.swapaxes(v, 0, 1).reshape((n_envs * steps,) + v.shape[2:])
            for k, v in rows.items()
        }
        return carry, rows

    return jax.jit(chunk)


def _pick_fn():
    import jax
    import jax.numpy as jnp

    def pick(acc, rows, local, inside):
        out = {}
        for k, a in acc.items():
            taken = rows[k][local]
            mask = inside.reshape((-1,) + (1,) * (taken.ndim - 1))
            out[k] = jnp.where(mask, taken, a)
        return out

    return jax.jit(pick, donate_argnums=(0,))


def bulk_fill(buf, env, num_actions: int, seed: int, spec: Dict,
              want: Optional[np.ndarray] = None) -> Tuple[np.ndarray, Optional[Dict]]:
    """Overwrite every row of ``buf`` (a device ring whose columns the
    program's own first insert has defined). Returns ``(raw priorities
    by ring position, the rows at positions ``want`` by column)``."""
    import jax
    import jax.numpy as jnp

    n_envs, steps = int(spec["chunk_envs"]), int(spec["chunk_steps"])
    per_chunk = n_envs * steps
    cap = int(buf.capacity)
    if cap % per_chunk:
        raise ValueError(f"ring of {cap} rows is not whole chunks of {per_chunk}")
    start = int(buf.num_added) % cap  # the ring's cursor
    raw = seeded_priorities(seed, cap)  # by ring position
    seed32 = int(seed) % (2**31 - 1)
    key = jax.random.fold_in(jax.random.PRNGKey(seed32), 11)
    state = jax.jit(jax.vmap(env.init))(
        jax.random.split(jax.random.fold_in(key, 0), n_envs)
    )
    carry = jax.jit(jax.vmap(env.reset))(state)
    chunk = _chunk_fn(env, n_envs, steps, num_actions)
    extra = {
        k: jnp.zeros((per_chunk,) + tuple(shape), dtype)
        for k, (shape, dtype, _) in buf._meta.items()
        if k not in ROW_COLUMNS
    }
    missing = [k for k in ROW_COLUMNS if k not in buf._meta]
    if missing:
        raise ValueError(f"the ring has no column(s) {missing}")
    picked, pick = None, _pick_fn()
    want_flat = None if want is None else np.asarray(want, np.int64).ravel()
    for c in range(cap // per_chunk):
        carry, rows = chunk(carry, jax.random.fold_in(key, c + 1))
        first = (start + c * per_chunk) % cap
        pos = (first + np.arange(per_chunk)) % cap
        if want_flat is not None:
            local = (want_flat - first) % cap
            inside = local < per_chunk
            if picked is None:
                picked = {
                    k: jnp.zeros((len(want_flat),) + v.shape[1:], v.dtype)
                    for k, v in rows.items()
                }
            picked = pick(
                picked, rows,
                np.where(inside, local, 0).astype(np.int32), inside,
            )
        tree = {k: rows[k].astype(buf._meta[k][1]) for k in ROW_COLUMNS}
        buf.add_device_tree({**tree, **extra}, priorities=raw[pos])
    if len(buf) != cap:
        raise RuntimeError(f"ring holds {len(buf)} of {cap} rows after the fill")
    return raw, picked
