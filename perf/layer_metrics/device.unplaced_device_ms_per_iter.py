"""Device time per traced iteration of the leaf operations under none
of the program's scopes that ``perf/async_waits.py`` can place
nowhere: no enclosing loop with a layer, and for a ``*-done`` no
consumer under a scope in the program's own table. What of
``device.unscoped_device_ms_per_iter`` still has no name (the entry
computation's whole-buffer copies, mostly). ``None`` for a program
without scopes."""

from perf import async_waits


def read(ctx):
    w = async_waits.waits(ctx)
    if w is None or not w.iterations:
        return None
    return w.unplaced_ns() / 1e6 / w.iterations
