"""Device time per optimizer update of the leaf operations under the
model's ``learn/kda`` scope(s) in the learn program: Kimi Delta
Attention's projections, the decay gate, the three convolutions, the
chunked per-channel delta rule, the head norm and gate and the output
projection (forward, the recomputation and the backward pass carry the
scope on their ``tf_op`` path). ``None`` for a program without it."""

from perf import program_trace, sequence_model


def read(ctx):
    rep = program_trace.report(ctx)
    seconds = sequence_model.seconds_under(rep, "learn/kda")
    if seconds is None or not rep.updates:
        return None
    return 1e3 * seconds / rep.updates
