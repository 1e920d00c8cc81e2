"""Device time per optimizer update of the leaf operations under the
model's ``learn/xattn`` scope(s) in the learn program: the
cross-attention layers, the readers of the shared cache (the query
projection, the fragment kernel over the stored rows and the owner's
rows of the fragment under ``xattn/scores``, ``xattn/out``,
``xattn/diff``, the output projection; the full layer that OWNS the
cache is under ``learn/attn``); forward, the recomputation and the
backward pass carry the scope on their ``tf_op`` path. ``None`` for a
program without the scope."""

from perf import program_trace, sequence_model


def read(ctx):
    rep = program_trace.report(ctx)
    seconds = sequence_model.seconds_under(rep, "learn/xattn/")
    if seconds is None or not rep.updates:
        return None
    return 1e3 * seconds / rep.updates
