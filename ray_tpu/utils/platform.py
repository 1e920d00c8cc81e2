"""What jax runs on here, and where this checkout keeps jax's
persistent compilation cache.

Every entry point that compiles for the accelerator
(``Algorithm.setup``, ``BatchedPolicyServer``, ``chip_smoke.py``,
``bench.py``) calls :func:`ensure_compile_cache` before its first
compile, so a second process on the same machine — and a second call
on a machine that keeps its disk — starts from compiled programs.
"""

from __future__ import annotations

import os
from typing import Dict, Optional

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def device_info() -> Dict[str, object]:
    """The devices jax reports, in the form every benchmark result and
    ``chip_smoke.py`` print. Initializes the default backend."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def compile_cache_dir() -> str:
    """The directory the cache lives in: ``JAX_COMPILATION_CACHE_DIR``
    where it is set, else ``<checkout>/.jax_cache`` resolved from this
    package's own location — the path is part of what makes a cache
    entry findable again, so it never depends on the cwd, a pid or the
    time."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        _CHECKOUT, ".jax_cache"
    )


def ensure_compile_cache() -> Optional[str]:
    """Place the compile cache; returns the directory in use (None =
    no cache).

    ``JAX_COMPILATION_CACHE_DIR`` set: jax already honours it and no
    directory is set in code. Unset: the cache goes to
    :func:`compile_cache_dir` — except on the CPU backend, where
    nothing is placed: XLA:CPU compiles in seconds, and its AOT loader
    logs an error-level machine-feature warning on every cache hit.
    Initializes the default backend (every caller is about to)."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return compile_cache_dir()
    import jax

    if jax.default_backend() == "cpu":
        return None
    path = compile_cache_dir()
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
    return path
