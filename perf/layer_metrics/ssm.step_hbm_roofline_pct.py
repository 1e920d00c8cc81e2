"""Share of its HBM roofline the one-token state-space step reaches, a
call: the bytes ONE call (one block, one token of every stream) must
move (``perf/ssm_moe_model.ssm_step_bytes``: the block's float32
matrices once in and once out, 8 bytes an element, plus its ``x``,
``dt``, ``B``, ``C`` and ``y`` rows) over the chip's peak bandwidth
(perf/peaks.json), over the device time of the leaf operations under
the lane's ``rollout/act`` whose path goes on through the model's
``ssm/step`` scope (the grouped step kernel and what stands beside it
there: the split of the convolution's channels, the skip ``D x``),
per traced iteration, step of the fragment and state-space block (the
one tail forward a fragment is in the time and not in the calls: 1/256
too slow). A run is a scan, so the loop's own frames stand between the
two scopes on an operation's ``tf_op`` path: they are matched in
order, not as one string. ``None`` for a configuration without a
``hybrid_override_pattern`` or a program without the scopes."""

from perf import flops, program_trace, sequence_model, ssm_moe_model


def seconds(rep):
    return ssm_moe_model.act_seconds_under(rep, "/ssm/step")


def read(ctx):
    config = ctx.cell.config
    if "hybrid_override_pattern" not in config:
        return None
    rep = program_trace.report(ctx)
    got = seconds(rep)
    if got is None or not rep.iterations:
        return None
    calls = (rep.iterations * sequence_model.fragment_steps(ctx)
             * ssm_moe_model.kinds(config).count(ssm_moe_model.MAMBA))
    need = ssm_moe_model.ssm_step_bytes(config, sequence_model.envs(ctx))
    peak = flops.load_peaks(ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * need / peak / (got / calls)
