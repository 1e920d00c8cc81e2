"""Share of the traced span in which no operation ran on the device:
100 x (1 - union of device-op intervals / span), mean over the cell's
chips. Source: profiler trace (perf/trace_reduce.py)."""


def read(ctx):
    if ctx.trace is None or ctx.trace.span_s() <= 0:
        return None
    return 100.0 * ctx.trace.idle_share()
