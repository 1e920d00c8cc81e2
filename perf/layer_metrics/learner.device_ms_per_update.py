"""Device time of the learn program per optimizer update, from the
trace: seconds of the program (``XLA Modules`` event name) with the
most device time in the traced span — in every cell so far that is
the learn / superstep program — over the updates the span ran
(``SUPERSTEP_UPDATES_TOTAL``, or learn calls where no superstep
runs). Programs cannot be told apart by their ``sharded_jit`` label
yet (PERF.md Open questions)."""


def read(ctx):
    if ctx.trace is None or ctx.traced is None:
        return None
    modules = ctx.trace.module_seconds()
    updates = ctx.traced.updates()
    if not modules or not updates:
        return None
    return 1e3 * max(modules.values()) / updates
