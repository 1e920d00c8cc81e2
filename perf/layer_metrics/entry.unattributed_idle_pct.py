"""Share of the first chip's idle time in the traced span that no
program span covers (``train:iteration`` and the benchmark's
``perf:train`` are looked through): 100 x unattributed / all idle.
Host work that still has no name. perf/program_trace.py."""

from perf import program_trace


def read(ctx):
    rep = program_trace.report(ctx)
    if rep is None:
        return None
    return rep.unattributed_idle_pct()
