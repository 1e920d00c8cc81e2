"""End-to-end model FLOP/s utilization of the learner, not a roofline
share: operations the forward and backward passes of the trained rows
require (the cell's perf/flop_rules/<flops_family>.py, from the
configuration file's shapes) x env
steps per second of the untraced window / (chips x the bf16 peak of
perf/peaks.json)."""

from perf import flops


def read(ctx):
    per_step = ctx.cell.flop_rule()(ctx.cell.config, ctx.num_actions)
    peak = flops.load_peaks(ctx.device_kind)["bf16_flops_per_s"]
    rate = ctx.env_steps() / ctx.window.seconds
    return 100.0 * per_step * rate / (ctx.chips * peak)
