"""Device time per optimizer update of the leaf operations under the
model's ``learn/attn`` and ``learn/swa`` scopes in the learn program:
every softmax-attention layer of a model whose layers differ in geometry
(the full layers' projections, q/k norms and YaRN, ``attn/scatter``,
``attn/scores`` (the fragment kernel), ``attn/gate``, ``attn/out``; the
window layers' under ``swa``), forward, the recomputation and the
backward pass. ``None`` for a program with neither scope."""

from perf import program_trace, sequence_model


def read(ctx):
    rep = program_trace.report(ctx)
    seconds = sequence_model.seconds_under(rep, "learn/attn", "learn/swa")
    if seconds is None or not rep.updates:
        return None
    return 1e3 * seconds / rep.updates
