"""A token environment on the :class:`JaxVectorEnv` API: the policy
emits one token a step and the observation is the token it just
emitted (a language model sampling from itself, prompt-free).

Episodes have a fixed length and end ``terminated`` (a finite-horizon
problem: nothing is bootstrapped across the end). The reward is dense
and a fixed function of (previous token, action, position) — an
integer hash folded into [-0.5, 0.5] — so advantages are never all
zero and a seed fixes the whole problem. Ids are drawn from
``vocab_size``, the slice of a vocabulary the policy holds.

``phase_stride``: env ``i`` begins its FIRST episode ``phase_stride *
i`` tokens in (mod the episode length), so that a vector of envs
covers every depth of an episode at once instead of resetting in
lock-step. The engines hand ``init_at`` the env's index for this.
"""

from __future__ import annotations

import numpy as np

from ray_tpu.env.jax_env import ArraySpec, JaxVectorEnv
from ray_tpu.env.registry import register_env


class TokenStreamJax(JaxVectorEnv):
    report_actions = True  # the tokens are what the lane generates

    def __init__(self, config=None):
        super().__init__(config)
        self.vocab = int(self.config.get("vocab_size", 64))
        self.length = int(self.config.get("episode_length", 32))
        self.stride = int(self.config.get("phase_stride", 0))
        self.obs_spec = ArraySpec((1,), np.int32)
        self.action_spec = ArraySpec((), np.int32, num_values=self.vocab)

    @property
    def observation_space(self):
        import gymnasium as gym

        return gym.spaces.Box(0, self.vocab - 1, (1,), np.int32)

    def init(self, key):
        return self.init_at(key, 0)

    def init_at(self, key, index):
        import jax.numpy as jnp

        start = (jnp.asarray(index, jnp.int32) * self.stride) % self.length
        zero = jnp.zeros((), jnp.int32)
        return {"key": key, "t": zero, "token": zero, "start": start}

    def reset(self, state):
        import jax
        import jax.numpy as jnp

        key, sub = jax.random.split(state["key"])
        token = jax.random.randint(sub, (), 0, self.vocab, jnp.int32)
        state = {
            "key": key,
            "t": state["start"],
            "token": token,
            "start": jnp.zeros((), jnp.int32),
        }
        return state, token[None]

    def reward(self, prev, action, t):
        """The fixed function: an integer hash of (previous token,
        action, position) folded into [-0.5, 0.5]."""
        import jax.numpy as jnp

        mixed = (
            prev.astype(jnp.int32) * 31
            + action.astype(jnp.int32) * 17
            + t.astype(jnp.int32) * 7
        ) % 97
        return mixed.astype(jnp.float32) / 96.0 - 0.5

    def step(self, state, action):
        import jax.numpy as jnp

        action = jnp.asarray(action, jnp.int32)
        reward = self.reward(state["token"], action, state["t"])
        t = state["t"] + 1
        state = dict(state, t=t, token=action)
        return state, action[None], reward, t >= self.length, jnp.zeros((), bool)


register_env("TokenStreamJax-v0", lambda cfg: TokenStreamJax(dict(cfg)))
