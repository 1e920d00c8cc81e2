"""Device time per optimizer update of the leaf operations under the
model's ``learn/mla`` scope(s) in the learn program: latent attention's
projections, the ``W_kvb`` expansion of stored and own rows, scores and
values (forward, the recomputation and the backward pass carry the
scope on their ``tf_op`` path). ``None`` for a program without it."""

from perf import program_trace, sequence_model


def read(ctx):
    rep = program_trace.report(ctx)
    seconds = sequence_model.seconds_under(rep, "learn/mla")
    if seconds is None or not rep.updates:
        return None
    return 1e3 * seconds / rep.updates
