"""Forward passes of the model (reads of its weights) the device lane
spends a committed token: the program's counters
``ray_tpu_diffusion_token_passes_total{form="denoise"|"commit"}`` (tokens
through the stack in the rollout's block forwards) over ``block_length``
tokens a forward, over ``ray_tpu_diffusion_tokens_committed_total``. A
block of 4 in 2 denoise passes and 1 commit pass: 3 / 4 = 0.75; an
autoregressive policy reads its weights once a token. ``None`` for a
program without the counters or a model that commits a token a step."""

from perf import block_diffusion_model


def read(ctx):
    from ray_tpu.telemetry import metrics

    totals = getattr(metrics, "diffusion_token_passes", lambda: {})()
    if not totals.get("committed"):
        return None
    block = block_diffusion_model.generation(ctx.cell.config)["block"]
    passes = totals.get("denoise", 0.0) + totals.get("commit", 0.0)
    return passes / block / totals["committed"]
