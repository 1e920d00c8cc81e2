"""Time per traced iteration in which the first chip ran nothing
while the main thread was inside a ``rollout:*`` span (innermost span
wins): key splits, the wait for the episode metrics, the weights
pull. perf/program_trace.py ``idle_by_span``."""

from perf import program_trace


def read(ctx):
    rep = program_trace.report(ctx)
    if rep is None:
        return None
    return rep.idle_ms("rollout")
