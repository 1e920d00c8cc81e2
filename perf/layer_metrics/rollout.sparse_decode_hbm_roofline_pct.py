"""Share of the HBM roofline one decode step of a policy whose attention
chooses its rows by a learned index reaches: the bytes a step MUST move
(``perf/sparse_attention_model.decode_step_bytes``: product weights once
at 2 bytes, the others at 4; for each stream and layer every index row
below its depth, ``min(depth + 1, topk)`` key and value rows and one row
a leaf written, at depths drawn evenly from the episode, which the
traffic's 16 streams, evenly apart, cover) over the chip's peak bandwidth
(perf/peaks.json), over the measured device time of a step
(``rollout/act`` + ``rollout/env_step`` + ``rollout/state_reset``). The
count is of the work the equations need, whatever implements them: a
program that reads every key and value row (today's one-token text
under the choice's mask does) moves more and reads LOW. Bound by bytes: a step
of 16 streams is 0.015 TFLOP. ``None`` for a configuration without
``sa_config`` or a program without the scopes."""

from perf import flops, program_trace, sequence_model, sparse_attention_model


def read(ctx):
    if "sa_config" not in ctx.cell.config:
        return None
    rep = program_trace.report(ctx)
    seconds = sequence_model.decode_seconds(rep)
    if seconds is None or not rep.iterations:
        return None
    step = seconds / (rep.iterations * sequence_model.fragment_steps(ctx))
    need = sparse_attention_model.decode_step_bytes(
        ctx.cell.config, ctx.num_actions, sequence_model.envs(ctx)
    )
    peak = flops.load_peaks(ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * need / peak / step
