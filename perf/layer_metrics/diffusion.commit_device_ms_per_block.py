"""Device time of the COMMIT forward of one lane step (one block of
every stream): the leaf operations under ``rollout/act/commit`` (the
block form that writes the rows that stay), per traced iteration and
per block of the fragment. ``None`` for a program without the scope."""


def read(ctx):
    return ctx.cell._module(
        "layer_metrics", "diffusion.denoise_device_ms_per_block"
    ).per_block(ctx, "rollout/act/commit")
