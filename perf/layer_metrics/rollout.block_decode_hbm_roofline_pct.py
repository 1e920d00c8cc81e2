"""Share of the HBM roofline one lane step of a block-diffusion policy
reaches: the bytes its ``S + 1`` block forwards MUST move
(``perf/block_diffusion_model.block_step_bytes``: a forward the held
bfloat16 product weights once, the other weights at 4 bytes, the cache
rows below each stream's block at the mean depth, the block's own rows
written and read, the logits) over the chip's peak bandwidth
(perf/peaks.json), over the measured device time under ``rollout/act``
of a lane step (the denoise and the commit forwards, the sampler, the
fragment's tail forward). Bound by bytes: a forward of 16 streams x 4
tokens is 0.08 TFLOP. ``None`` for a configuration that commits a token
a step or a program without the scope."""

from perf import block_diffusion_model, flops, program_trace, sequence_model


def read(ctx):
    lm = ctx.cell.config["algo_config"]["model"].get("sequence_lm") or {}
    if "block_length" not in lm:
        return None
    rep = program_trace.report(ctx)
    if rep is None or rep.scopes is None or not rep.iterations:
        return None
    seconds = rep.scopes.get("rollout/act")
    if seconds is None:
        return None
    blocks = sequence_model.fragment_steps(ctx) // int(lm["block_length"])
    need = block_diffusion_model.block_step_bytes(
        ctx.cell.config, ctx.num_actions, sequence_model.envs(ctx)
    )
    peak = flops.load_peaks(ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * need / peak / (seconds / (rep.iterations * blocks))
