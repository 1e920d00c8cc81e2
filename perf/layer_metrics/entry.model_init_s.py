"""Seconds under the program's ``setup:model_init`` phase (the
model's init program, its compile or retrieval, and the placement of
the parameters), from ``tracing.phases()``; a part of
``entry.build_s``."""


def read(ctx):
    from ray_tpu.util import tracing

    seconds = getattr(tracing, "phase_seconds", None)
    return seconds("setup:model_init") if seconds else None
