"""Device time per optimizer update of the leaf operations under the
model's ``learn/hc`` scope(s) in the learn program: the hyper-connection
residual's norm over the lanes, its maps (the Sinkhorn rounds among
them), the pre-mix, the residual mix and the post-add (forward, the
recomputation and the backward pass). ``None`` for a program without
it."""

from perf import program_trace, sequence_model


def read(ctx):
    rep = program_trace.report(ctx)
    seconds = sequence_model.seconds_under(rep, "learn/hc")
    if seconds is None or not rep.updates:
        return None
    return 1e3 * seconds / rep.updates
