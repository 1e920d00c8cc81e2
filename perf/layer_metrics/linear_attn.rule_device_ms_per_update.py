"""Device time per optimizer update of the leaf operations under the
model's ``learn/linear_attn/rule`` scope(s) in the learn program: the
gated delta rule's fragment form alone (the chunked text, or the kernel
pair's forward, forward again and backward), without the projections,
the convolution, the norms and the gate that
``linear_attn.scope_device_ms_per_update`` counts beside it. jax wraps a
transform's name around the OUTERMOST scope under it alone, so the first
forward pass carries ``jvp(learn/linear_attn)/rule`` where the
recomputation and the backward pass carry ``learn/linear_attn/rule``:
both are read. A program with no such scope reports nothing."""

from perf import program_trace, sequence_model


def read(ctx):
    rep = program_trace.report(ctx)
    seconds = sequence_model.seconds_under(
        rep, "learn/linear_attn/rule", "learn/linear_attn)/rule")
    if seconds is None or not rep.updates:
        return None
    return 1e3 * seconds / rep.updates
