"""Seconds under the program's ``setup:algorithm`` phase
(``Algorithm.__init__`` through ``setup``: workers, env, policy,
model and optimizer init), from ``tracing.phases()``, which the
program keeps with tracing off."""


def read(ctx):
    from ray_tpu.util import tracing

    seconds = getattr(tracing, "phase_seconds", None)
    return seconds("setup:algorithm") if seconds else None
