"""Device time per optimizer update of the learned index in the learn
program: the leaf operations under the attention layers'
``learn/attn/index/`` scopes (``/index/proj``: the three projections, LN
and RoPE; ``/index/scores``: the score product, relu and weighted sum
over every row a query may see; ``/index/topk``: the threshold and the
tie rule) and ``learn/attn/select`` (rows fetched by number: none
today); the first forward pass and its recomputation (the
choice has no backward pass). The selected read itself is
``attn.scope_device_ms_per_update``'s, which holds this too. ``None``
for a configuration without ``sa_config`` or a program without the
scopes."""

from perf import program_trace, sequence_model


def read(ctx):
    if "sa_config" not in ctx.cell.config:
        return None
    rep = program_trace.report(ctx)
    seconds = sequence_model.seconds_under(
        rep, "learn/attn/index/", "learn/attn/select")
    if seconds is None or not rep.updates:
        return None
    return 1e3 * seconds / rep.updates
