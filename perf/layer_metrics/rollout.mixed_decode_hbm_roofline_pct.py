"""Share of the HBM roofline one decode step of a mixed-geometry
attention policy reaches: the bytes a step MUST move
(``perf/mixed_attention_model.decode_step_bytes``: product weights once
at 2 bytes, the others at 4, each full cache's rows at the mean depth
once, each ring's rows inside the window once, one row a layer written;
not the slots outside the mask) over the chip's peak bandwidth
(perf/peaks.json), over the measured device time of a step
(``rollout/act`` + ``rollout/env_step`` + ``rollout/state_reset``).
Bound by bytes: a step of 16 streams is 0.02 TFLOP. ``None`` for a
configuration whose layers share one geometry or a program without the
scopes."""

from perf import flops, mixed_attention_model, program_trace, sequence_model


def read(ctx):
    if "num_attention_heads_per_layer" not in ctx.cell.config:
        return None
    rep = program_trace.report(ctx)
    seconds = sequence_model.decode_seconds(rep)
    if seconds is None or not rep.iterations:
        return None
    step = seconds / (rep.iterations * sequence_model.fragment_steps(ctx))
    need = mixed_attention_model.decode_step_bytes(
        ctx.cell.config, ctx.num_actions, sequence_model.envs(ctx)
    )
    peak = flops.load_peaks(ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * need / peak / step
