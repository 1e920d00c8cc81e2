"""Device time the chip WAITS for the compiler's asynchronous copies
inside the rollout lane's loop: the ``*-done`` leaf operations
(``copy-done``, ``slice-done``, ``async-done``) that
``perf/async_waits.py`` places in the ``rollout`` layer (by the
``while`` that encloses them in time, else by the scope of the
instruction that consumes them in the program's own table), per traced
iteration and per step of the lane's loop. Beside
``rollout.decode_device_ms_per_step`` it is the step as the chip
lives it. 0 where the loop holds no pair; ``None`` for a program
without scopes."""

from perf import async_waits


def read(ctx):
    w = async_waits.waits(ctx)
    if w is None or not w.iterations:
        return None
    return w.exposed_ns("rollout") / 1e6 / (
        w.iterations * async_waits.lane_steps(ctx)
    )
