"""Time per traced iteration in which the first chip ran nothing
while the main thread was inside a ``replay:*`` span (innermost span
wins): the insert's host part, the draw's generator calls, the host
alpha-power of the refresh. perf/program_trace.py ``idle_by_span``."""

from perf import program_trace


def read(ctx):
    rep = program_trace.report(ctx)
    if rep is None:
        return None
    return rep.idle_ms("replay")
