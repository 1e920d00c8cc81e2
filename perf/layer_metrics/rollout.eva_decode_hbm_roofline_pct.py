"""Share of the HBM roofline one decode step of an EVA-attention policy
reaches: the bytes a step MUST move
(``perf/eva_model.decode_step_bytes``: product weights once at 2 bytes,
the others at 4, the rows INSIDE both masks at the mean depth once, one
window row and 1/16 summary row a layer written; not the slots outside
the masks) over the chip's peak bandwidth (perf/peaks.json), over the
measured device time of a step (``rollout/act`` + ``rollout/env_step`` +
``rollout/state_reset``). Bound by bytes: a step of 16 streams is 0.02
TFLOP. ``None`` for a configuration without ``attention_class: eva`` or
a program without the scopes."""

from perf import eva_model, flops, program_trace, sequence_model


def read(ctx):
    if not eva_model.is_eva(ctx.cell.config):
        return None
    rep = program_trace.report(ctx)
    seconds = sequence_model.decode_seconds(rep)
    if seconds is None or not rep.iterations:
        return None
    step = seconds / (rep.iterations * sequence_model.fragment_steps(ctx))
    need = eva_model.decode_step_bytes(
        ctx.cell.config, ctx.num_actions, sequence_model.envs(ctx)
    )
    peak = flops.load_peaks(ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * need / peak / step
