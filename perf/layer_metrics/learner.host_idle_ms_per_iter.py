"""Time per traced iteration in which the first chip ran nothing
while the main thread was inside a ``learn:*`` span (innermost span
wins): the key chain, the superstep's dispatch, what is left of the
drain once the program has ended, the stat dicts.
perf/program_trace.py ``idle_by_span``."""

from perf import program_trace


def read(ctx):
    rep = program_trace.report(ctx)
    if rep is None:
        return None
    return rep.idle_ms("learn")
