"""Device time of the DENOISE forwards of one lane step (one block of
every stream) of a model that generates by block diffusion: the leaf
operations under the program's ``rollout/act/denoise`` scope (the
model's block form against rows the next pass overwrites:
``.../denoise/attn``, ``.../denoise/moe/*``, ``.../denoise/head``; the
fragment's one tail forward included), per traced iteration and per
block of the fragment. ``None`` for a program without the scope."""

from perf import block_diffusion_model, program_trace, sequence_model


def per_block(ctx, needle: str):
    rep = program_trace.report(ctx)
    seconds = sequence_model.seconds_under(rep, needle)
    if seconds is None or not rep.iterations:
        return None
    blocks = sequence_model.fragment_steps(ctx) // block_diffusion_model.generation(
        ctx.cell.config)["block"]
    return 1e3 * seconds / (rep.iterations * blocks)


def read(ctx):
    return per_block(ctx, "rollout/act/denoise")
