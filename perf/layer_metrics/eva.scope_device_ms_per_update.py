"""Device time per optimizer update of the leaf operations under the
model's ``learn/eva`` scope(s) in the learn program: the EVA layers'
projections and RoPE, the window store's scatter (``eva/scatter``), the
pooling of the chunks a fragment completes and the summary store's
scatter (``eva/summarise``), the score products, the four masks and the
joint softmax over the stored window rows, the stored summaries, the
fragment's own rows and its own summaries (``eva/scores``), the value
products and the output projection (``eva/out``); forward, the
recomputation and the backward pass carry the scope on their ``tf_op``
path. ``None`` for a program without the scope."""

from perf import program_trace, sequence_model


def read(ctx):
    rep = program_trace.report(ctx)
    seconds = sequence_model.seconds_under(rep, "learn/eva")
    if seconds is None or not rep.updates:
        return None
    return 1e3 * seconds / rep.updates
