"""Device time per optimizer update of the attention's read of the rows
a learned index chose, in the learn program: the leaf operations under
the attention layers' ``learn/attn/scores`` (the masked scores and
softmax under the choice: XLA's text a tile at a time, or the fragment
kernel pair with the choice as an operand) and ``learn/attn/out`` (the
weighted sum, and the layer's output projection, which shares the
scope's name) scopes; the forward pass, its recomputation and the
backward pass. Without the index itself (``learn/attn/index/``:
``index.scope_device_ms_per_update``), the projections and the
scatter, which ``attn.scope_device_ms_per_update`` holds beside this.
jax wraps a transform's name around the OUTERMOST scope under it
alone, so a nested spelling would read ``jvp(learn/attn)/scores`` in
the first forward pass
(``linear_attn.rule_device_ms_per_update.py``): both are read. ``None``
for a configuration without ``sa_config`` or a program without the
scopes."""

from perf import program_trace, sequence_model


def read(ctx):
    if "sa_config" not in ctx.cell.config:
        return None
    rep = program_trace.report(ctx)
    seconds = sequence_model.seconds_under(
        rep, "learn/attn/scores", "learn/attn)/scores",
        "learn/attn/out", "learn/attn)/out")
    if seconds is None or not rep.updates:
        return None
    return 1e3 * seconds / rep.updates
