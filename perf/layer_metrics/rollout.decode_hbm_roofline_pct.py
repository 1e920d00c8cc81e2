"""Share of the HBM roofline one decode step reaches: the bytes a step
must move (``perf/sequence_model.decode_step_bytes``: the weights as
a step reads them, bfloat16 where a product takes them, the DeltaNet
state read once and written once, the stored keys and values of half
an episode) over the chip's peak
bandwidth (perf/peaks.json), over the measured device time of a step
(``rollout.decode_device_ms_per_step``). Bound by bytes: a step of 64
streams is 0.1 TFLOP at most."""

from perf import flops, program_trace, sequence_model


def read(ctx):
    rep = program_trace.report(ctx)
    seconds = sequence_model.decode_seconds(rep)
    if seconds is None or not rep.iterations:
        return None
    step = seconds / (rep.iterations * sequence_model.fragment_steps(ctx))
    need = sequence_model.decode_step_bytes(
        ctx.cell.config, ctx.num_actions, sequence_model.envs(ctx)
    )
    peak = flops.load_peaks(ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * need / peak / step
