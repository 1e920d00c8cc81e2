"""Device time per optimizer update of the leaf operations under the
model's ``learn/swa`` scope(s) in the learn program: the window layers'
projections and RoPE, the ring's scatter (``swa/scatter``), the score
products, masks and softmax over the stored ring and the fragment's own
keys (``swa/scores``), the value products and the output projection
(``swa/out``); forward, the recomputation and the backward pass carry
the scope on their ``tf_op`` path. The full layer is ``learn/attn``.
``None`` for a program without the scope."""

from perf import program_trace, sequence_model


def read(ctx):
    rep = program_trace.report(ctx)
    seconds = sequence_model.seconds_under(rep, "learn/swa")
    if seconds is None or not rep.updates:
        return None
    return 1e3 * seconds / rep.updates
