"""Share of the HBM roofline one decode step of a Nemotron-H policy
reaches: the bytes a step MUST move
(``perf/ssm_moe_model.decode_step_bytes``: product weights once at 2
bytes, every HELD expert's two matrices among them as the dense step
form reads them, the others at 4, every state-space matrix and
convolution tail once in and once out, the attention block's rows of
the mean depth once; not the masked slots) over the chip's peak
bandwidth (perf/peaks.json), over the measured device time of a step
(``rollout/act`` + ``rollout/env_step`` + ``rollout/state_reset``).
Bound by bytes: a step of 32 streams is 0.04 TFLOP. ``None`` for a
configuration without a ``hybrid_override_pattern`` or a program
without the scopes."""

from perf import flops, program_trace, sequence_model, ssm_moe_model


def read(ctx):
    if "hybrid_override_pattern" not in ctx.cell.config:
        return None
    rep = program_trace.report(ctx)
    seconds = sequence_model.decode_seconds(rep)
    if seconds is None or not rep.iterations:
        return None
    step = seconds / (rep.iterations * sequence_model.fragment_steps(ctx))
    need = ssm_moe_model.decode_step_bytes(
        ctx.cell.config, ctx.num_actions, sequence_model.envs(ctx)
    )
    peak = flops.load_peaks(ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * need / peak / step
