"""Device time per optimizer update of the leaf operations under the
program's ``replay/*`` scopes (``replay/draw``, ``replay/gather``,
``replay/refresh``, ``replay/insert``), in whichever program they
run. Scopes are read from the ``tf_op`` stat of each operation's
metadata in the run's ``.xplane.pb``."""

from perf import program_trace


def read(ctx):
    rep = program_trace.report(ctx)
    if rep is None:
        return None
    return rep.scope_ms("replay/", rep.updates)
