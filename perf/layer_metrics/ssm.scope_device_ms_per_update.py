"""Device time per optimizer update of the leaf operations under the
model's ``learn/ssm`` scope(s) in the learn program: the state-space
mixers' projections, convolution, the chunked recurrence and the gated
norm (forward, the recomputation and the backward pass carry the scope
on their ``tf_op`` path; a run of stacked layers is one scan, so one
operation's time is all its layers'). ``None`` for a program without
it."""

from perf import program_trace, sequence_model


def read(ctx):
    rep = program_trace.report(ctx)
    seconds = sequence_model.seconds_under(rep, "learn/ssm")
    if seconds is None or not rep.updates:
        return None
    return 1e3 * seconds / rep.updates
