"""Device time per optimizer update of the leaf operations under the
model's ``learn/scan`` scope(s) in the learn program: the selective-scan
layers (``scan/in``: ``W_in``, ``W_x``, ``W_dt``; ``scan/conv``;
``scan/step``: the recurrence, a loop over the fragment's tokens, and
the skip; ``scan/out``); forward, the recomputation and the backward
pass carry the scope on their ``tf_op`` path. ``None`` for a program
without the scope."""

from perf import program_trace, sequence_model


def read(ctx):
    rep = program_trace.report(ctx)
    seconds = sequence_model.seconds_under(rep, "learn/scan/")
    if seconds is None or not rep.updates:
        return None
    return 1e3 * seconds / rep.updates
