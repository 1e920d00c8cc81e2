"""Env registry + creation (reference ``ray/tune/registry.py`` register_env
+ RolloutWorker env creation)."""

from __future__ import annotations

from typing import Any, Callable, Dict

from ray_tpu.env.env_context import EnvContext

_env_registry: Dict[str, Callable] = {}


def register_env(name: str, creator: Callable[[EnvContext], Any]) -> None:
    _env_registry[name] = creator


def get_env_creator(env_spec) -> Callable[[EnvContext], Any]:
    """env_spec: registered name | gymnasium id | callable | env class."""
    if callable(env_spec) and not isinstance(env_spec, str):
        if isinstance(env_spec, type):
            return lambda cfg: env_spec(cfg)
        return env_spec
    if env_spec in _env_registry:
        return _env_registry[env_spec]
    if isinstance(env_spec, str) and (
        env_spec.startswith(
            ("PongLite", "Synthetic", "CartPoleJax", "GridRoomsJax",
             "TokenStreamJax")
        )
    ):
        # in-repo envs register on import; pull them in so yaml/CLI
        # runs can name them without a registration preamble
        # (reference tuned-example UX)
        import ray_tpu.env.jax_control  # noqa: F401
        import ray_tpu.env.jax_pong  # noqa: F401
        import ray_tpu.env.jax_tokens  # noqa: F401
        import ray_tpu.env.pong_lite  # noqa: F401
        import ray_tpu.env.synthetic_env  # noqa: F401

        if env_spec in _env_registry:
            return _env_registry[env_spec]
        # recognized in-repo prefix but no such registration: fail
        # fast at config time with the real names, instead of a
        # confusing gymnasium NameNotFound inside every worker
        raise ValueError(
            f"unknown in-repo env {env_spec!r}; registered: "
            f"{sorted(n for n in _env_registry)}"
        )

    def gym_creator(cfg: EnvContext):
        import gymnasium as gym

        return gym.make(env_spec, **{
            k: v for k, v in dict(cfg).items() if k != "render_mode"
        })

    return gym_creator
