"""Device time per optimizer update of the NOISY passes of a
block-diffusion update, all ``denoising_steps`` of them: the leaf
operations under the learn program's ``learn/noisy`` scope (each pass's
input the tokens committed before it and ``[MASK]`` elsewhere, its
queries over the stored rows, the clean pass's rows of strictly earlier
blocks and its own of the same block). ``None`` for a program without
the scope."""


def read(ctx):
    return ctx.cell._module(
        "layer_metrics", "diffusion.clean_pass_device_ms_per_update"
    ).per_update(ctx, "learn/noisy")
