"""Device time the chip WAITS for the compiler's asynchronous copies
on the learner's behalf: the ``*-done`` leaf operations that
``perf/async_waits.py`` places in the ``learn`` layer (inside a loop of
the learn nest or of a ``learn/*`` scope, else consumed by an
instruction under a ``learn/*`` scope by the program's own table), per
optimizer update. Beside ``learner.scope_device_ms_per_update``, which
holds none of it. 0 where the program holds no such pair; ``None`` for
a program without scopes."""

from perf import async_waits


def read(ctx):
    w = async_waits.waits(ctx)
    if w is None or not w.updates:
        return None
    return w.exposed_ns("learn") / 1e6 / w.updates
