"""Device time of the replay superstep program per optimizer update:
``XLA Modules`` events named ``jit_superstep*`` over the updates the
traced span ran (``SUPERSTEP_UPDATES_TOTAL``). Found by name, where
``learner.device_ms_per_update`` takes the program with most device
time; the two agree as long as that guess is right."""

from perf import program_trace


def read(ctx):
    rep = program_trace.report(ctx)
    if rep is None:
        return None
    return rep.family_ms("superstep", rep.updates)
