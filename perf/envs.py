"""The envs a traffic mix may name, made from ray_tpu's own.

A traffic file's ``env`` is either a registered name, or
``{"base": <registered JaxVectorEnv>, "frame_stack": k}``: the base
env with its last ``k`` frames stacked along the channel axis ON the
device (Mnih et al. 2015 feed the network 84x84x4). ray_tpu's device
lane has no stacking wrapper of its own (``ray_tpu/env/jax_pong.py``
renders 84x84x1), and the env is the workload's input, not the system
under test: it is written against the public ``JaxVectorEnv`` protocol
and registered through the public ``register_env``, as a user's env
is. Nothing here knows an env or a cell by name.
"""

from __future__ import annotations

from typing import Dict, Union

from ray_tpu.env.jax_env import ArraySpec, JaxVectorEnv
from ray_tpu.env.registry import get_env_creator, register_env


class FrameStackJax(JaxVectorEnv):
    """``env`` with the newest ``k`` observations concatenated along
    the last axis, oldest first. The stack lives in the env state, so
    the rollout engine's auto-reset selects it with the rest. A reset
    fills the stack with the first frame (the gym / Atari wrapper's
    rule)."""

    def __init__(self, env: JaxVectorEnv, k: int):
        super().__init__(env.config)
        self.env = env
        self.k = int(k)
        shape = tuple(env.obs_spec.shape)
        self._channels = shape[-1]
        self.obs_spec = ArraySpec(
            shape[:-1] + (shape[-1] * self.k,), env.obs_spec.dtype
        )
        self.action_spec = env.action_spec

    def init(self, key):
        import jax.numpy as jnp

        return {
            "inner": self.env.init(key),
            "frames": jnp.zeros(self.obs_spec.shape, self.obs_spec.dtype),
        }

    def reset(self, state):
        import jax.numpy as jnp

        inner, obs = self.env.reset(state["inner"])
        frames = jnp.concatenate([obs] * self.k, axis=-1)
        return {"inner": inner, "frames": frames}, frames

    def step(self, state, action):
        import jax.numpy as jnp

        inner, obs, reward, terminated, truncated = self.env.step(
            state["inner"], action
        )
        frames = jnp.concatenate(
            [state["frames"][..., self._channels:], obs], axis=-1
        )
        return (
            {"inner": inner, "frames": frames},
            frames,
            reward,
            terminated,
            truncated,
        )


def resolve(env: Union[str, Dict]) -> str:
    """The registered name for a traffic file's ``env`` entry,
    registering the stacked variant on first use."""
    if isinstance(env, str):
        return env
    base, k = env["base"], int(env.get("frame_stack", 1))
    if k <= 1:
        return base
    name = f"{base}.stack{k}"
    make_base = get_env_creator(base)
    register_env(name, lambda cfg: FrameStackJax(make_base(cfg), k))
    return name
