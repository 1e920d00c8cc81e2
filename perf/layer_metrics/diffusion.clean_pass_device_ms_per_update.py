"""Device time per optimizer update of the CLEAN pass of a
block-diffusion update: the leaf operations under the learn program's
``learn/clean`` scope (the fragment's committed tokens under the
block-causal mask, whose keys the noisy passes read; forward, the
recomputation and the backward pass carry the scope; the kinds' own
``learn/attn`` and ``learn/moe/*`` nest inside it). ``None`` for a
program without the scope."""

from perf import program_trace, sequence_model


def per_update(ctx, needle: str):
    rep = program_trace.report(ctx)
    seconds = sequence_model.seconds_under(rep, needle)
    if seconds is None or not rep.updates:
        return None
    return 1e3 * seconds / rep.updates


def read(ctx):
    return per_update(ctx, "learn/clean")
