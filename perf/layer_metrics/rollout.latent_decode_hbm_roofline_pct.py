"""Share of the HBM roofline one decode step of a latent-attention
policy reaches: the bytes a step MUST move
(``perf/latent_model.decode_step_bytes``: product weights once at 2
bytes, the others at 4, the latent rows of the mean depth once, one row
written; not the masked rows, no expanded key or value) over the chip's
peak bandwidth (perf/peaks.json), over the measured device time of a
step (``rollout/act`` + ``rollout/env_step`` + ``rollout/state_reset``).
Bound by bytes: a step of 64 streams is 0.2 TFLOP at most."""

from perf import flops, latent_model, program_trace, sequence_model


def read(ctx):
    if "kv_lora_rank" not in ctx.cell.config:
        return None
    rep = program_trace.report(ctx)
    seconds = sequence_model.decode_seconds(rep)
    if seconds is None or not rep.iterations:
        return None
    step = seconds / (rep.iterations * sequence_model.fragment_steps(ctx))
    need = latent_model.decode_step_bytes(
        ctx.cell.config, ctx.num_actions, sequence_model.envs(ctx)
    )
    peak = flops.load_peaks(ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * need / peak / step
