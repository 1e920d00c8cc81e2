"""Device time per traced iteration of EVERY ``*-done`` leaf operation
(``copy-done``, ``slice-done``, ``async-done``: the chip waiting for a
transfer the compiler issued), whichever layer
``perf/async_waits.py`` places it in or none: the part of
``device.unscoped_device_ms_per_iter`` that is waiting, not copying.
0 where the traced programs hold no pair; ``None`` for a program
without scopes."""

from perf import async_waits


def read(ctx):
    w = async_waits.waits(ctx)
    if w is None or not w.iterations:
        return None
    return w.exposed_ns() / 1e6 / w.iterations
