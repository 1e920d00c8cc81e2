"""Seconds jax spent inside backend compile calls during set-up
(``/jax/core/compile/backend_compile_duration``; a persistent-cache
hit spends its load there instead of a compile)."""


def read(ctx):
    return float(ctx.setup["compile_backend_s"])
