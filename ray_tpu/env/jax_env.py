"""JaxVectorEnv: the JAX-native vectorized-env API of the device
rollout lane (docs/pipeline.md "two rollout lanes").

For environments expressible as pure JAX functions (classic control,
gridworlds, synthetic traffic, pong_lite), rollouts don't need CPU
actors at all: ``execution/jax_rollout.py`` lowers
``policy.compute_actions → env.step → trajectory buffer`` as ONE jit'd
batch-sharded program on the learner mesh (the Anakin/Brax "everything
on device" pattern), so the hot path ships zero rollout bytes over
H2D. The CPU Ray-actor lane stays the default for everything else; the
two lanes share SampleBatch semantics and a fixed-seed parity contract
(tests/test_jax_env.py).

The API is three pure functions over an explicit per-env state pytree
(a dict of arrays; the carried PRNG key lives inside it):

  - ``init(key) -> state``          fresh per-env state from a PRNG key
  - ``reset(state) -> (state, obs)``  begin an episode, consuming the
    state's carried key stream (auto-reset draws come from here)
  - ``step(state, action) -> (state, obs, reward, terminated,
    truncated)``  one transition, NO auto-reset

Auto-reset is deliberately NOT part of the env: both lanes implement
it on top of ``reset`` in one documented place each, so the
terminal-observation contract cannot drift between them:

  **Terminal-observation contract** (matches the host
  ``VectorEnv``/``SyncSampler`` lane exactly — audited in
  tests/test_jax_env.py): at a step where ``terminated | truncated``,
  the row's NEXT_OBS is the env's FINAL (pre-reset) observation; the
  episode's successor row's OBS is the RESET observation of the new
  episode, drawn from the state's carried key stream. GAE bootstraps 0
  across ``terminated`` and V(final obs) across ``truncated``
  (``ops/gae.compute_gae_fragment``).

Shapes/dtypes are static: ``obs_spec``/``action_spec`` describe one
env's observation and action arrays; ``observation_space``/
``action_space`` expose the equivalent gymnasium spaces so the host
lane (policy construction, preprocessors) sees a normal env.

``JaxVectorEnvAdapter`` bridges a JaxVectorEnv into the host lane's
:class:`~ray_tpu.env.vector_env.VectorEnv` protocol — it steps ALL
sub-envs in one jitted vmapped call per ``vector_step`` (the same
functions the device lane scans over, same per-env key streams), which
is what makes the fixed-seed parity test possible: both lanes run
literally the same dynamics.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import numpy as np


class ArraySpec(NamedTuple):
    """Static shape/dtype of one per-env array (no batch dim)."""

    shape: Tuple[int, ...]
    dtype: Any
    # Discrete action count (None for continuous/box specs)
    num_values: Optional[int] = None


class JaxVectorEnv:
    """Base class for JAX-native envs (see module docstring).

    Subclasses implement :meth:`init`, :meth:`reset`, :meth:`step`
    over ONE env's state (the engines vmap them), and set
    ``obs_spec`` / ``action_spec``.
    """

    obs_spec: ArraySpec
    action_spec: ArraySpec
    # an env whose actions are its product (generated tokens): the
    # device lane hands every step's actions back with the episode
    # metrics, on the one drain (``JaxRolloutEngine.last_actions``)
    report_actions = False

    def __init__(self, config: Optional[Dict] = None):
        self.config = dict(config or {})

    # -- pure functions (single env; engines vmap) ----------------------

    def init(self, key):
        """Fresh per-env state pytree from a PRNG key. The state must
        carry the key (conventionally ``state["key"]``) — ``reset``
        and any stochastic ``step`` draw from it."""
        raise NotImplementedError

    def reset(self, state):
        """Begin a new episode using (and advancing) the state's
        carried key. Returns ``(state, obs)``."""
        raise NotImplementedError

    def step(self, state, action):
        """One transition, NO auto-reset:
        ``(state, obs, reward, terminated, truncated)`` with ``obs``
        the post-step (possibly terminal) observation, ``reward``
        float32, ``terminated``/``truncated`` bool scalars."""
        raise NotImplementedError

    # -- gym-facing surface (host lane / policy construction) ------------

    def close(self) -> None:
        """gym-API parity; pure-function envs hold no resources."""

    @property
    def observation_space(self):
        import gymnasium as gym

        spec = self.obs_spec
        if np.dtype(spec.dtype) == np.uint8:
            return gym.spaces.Box(0, 255, spec.shape, np.uint8)
        return gym.spaces.Box(
            -np.inf, np.inf, spec.shape, np.dtype(spec.dtype).type
        )

    @property
    def action_space(self):
        import gymnasium as gym

        spec = self.action_spec
        if spec.num_values is not None:
            return gym.spaces.Discrete(spec.num_values)
        return gym.spaces.Box(
            -1.0, 1.0, spec.shape, np.dtype(spec.dtype).type
        )


def env_keys(seed: Optional[int], num_envs: int):
    """The per-env PRNG keys BOTH lanes seed from: env ``i`` gets
    ``PRNGKey(seed + i)`` (mirroring the host
    ``_VectorizedGymEnv.vector_reset`` convention of ``seed + i``).
    ``None`` seeds default to 0 so the two lanes cannot diverge on the
    unseeded path either."""
    import jax

    base = 0 if seed is None else int(seed)
    return jax.numpy.stack(
        [jax.random.PRNGKey(base + i) for i in range(num_envs)]
    )


def tree_where(mask, a, b):
    """Per-leaf ``where(mask, a, b)`` with the (N,) mask broadcast
    over each leaf's trailing dims — the auto-reset selector."""
    import jax
    import jax.numpy as jnp

    def sel(x, y):
        m = mask.reshape((-1,) + (1,) * (x.ndim - 1))
        return jnp.where(m, x, y)

    return jax.tree_util.tree_map(sel, a, b)


class JaxVectorEnvAdapter:
    """Host-lane bridge: a :class:`JaxVectorEnv` exposed through the
    :class:`~ray_tpu.env.vector_env.VectorEnv` protocol the samplers
    drive. One jitted vmapped ``step`` call advances every sub-env per
    ``vector_step``; ``reset_at`` resets a single slot from its own
    carried key stream — the exact auto-reset semantics of the device
    lane (module docstring), so fixed-seed trajectories match the
    device rollout engine's bit for bit on the same backend."""

    def __init__(
        self,
        env: JaxVectorEnv,
        num_envs: int,
        seed: Optional[int] = None,
    ):
        import jax

        self.jax_env = env
        self.num_envs = int(num_envs)
        self.observation_space = env.observation_space
        self.action_space = env.action_space
        self._seed = seed
        self._init_b = jax.jit(jax.vmap(env.init))
        self._reset_b = jax.jit(jax.vmap(env.reset))
        self._step_b = jax.jit(jax.vmap(env.step))
        self._reset_1 = jax.jit(env.reset)
        self._state = None

    # -- VectorEnv protocol ----------------------------------------------

    def vector_reset(self):
        keys = env_keys(self._seed, self.num_envs)
        self._state = self._init_b(keys)
        self._state, obs = self._reset_b(self._state)
        obs = np.asarray(obs)
        return [obs[i] for i in range(self.num_envs)], [
            {} for _ in range(self.num_envs)
        ]

    def reset_at(self, index: int):
        import jax

        sub = jax.tree_util.tree_map(
            lambda x: x[index], self._state
        )
        sub, obs = self._reset_1(sub)
        self._state = jax.tree_util.tree_map(
            lambda full, s: full.at[index].set(s), self._state, sub
        )
        return np.asarray(obs), {}

    def vector_step(self, actions):
        import jax.numpy as jnp

        act = jnp.asarray(np.stack([np.asarray(a) for a in actions]))
        self._state, obs, reward, term, trunc = self._step_b(
            self._state, act
        )
        obs = np.asarray(obs)
        reward = np.asarray(reward)
        term = np.asarray(term)
        trunc = np.asarray(trunc)
        return (
            [obs[i] for i in range(self.num_envs)],
            [float(reward[i]) for i in range(self.num_envs)],
            [bool(term[i]) for i in range(self.num_envs)],
            [bool(trunc[i]) for i in range(self.num_envs)],
            [{} for _ in range(self.num_envs)],
        )

    def get_sub_environments(self):
        return []
