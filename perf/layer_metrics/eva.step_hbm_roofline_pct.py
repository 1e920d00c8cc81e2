"""Share of its HBM roofline the one-token EVA attention reaches, a
call: the bytes ONE call (one layer, one token of every stream) must
move (``perf/eva_model.eva_step_bytes``: the bfloat16 rows INSIDE both
masks at the mean depth once, the query and the output) over the chip's
peak bandwidth (perf/peaks.json), over the device time of the leaf
operations under the lane's ``rollout/act`` whose path goes on through
the model's ``eva/scores`` scope (the two-store step kernel alone: the
writes are under ``eva/scatter`` and ``eva/summarise``), per traced
iteration, step of the fragment and layer. The kernel fetches whole key
blocks of 128 rows, so at the mean it moves more than the masks' rows
(a window block is half inside on average) and reads below 100 for that
alone. ``None`` for a configuration without ``attention_class: eva`` or
a program without the scope."""

from perf import eva_model, flops, program_trace, sequence_model, ssm_moe_model


def read(ctx):
    config = ctx.cell.config
    if not eva_model.is_eva(config):
        return None
    rep = program_trace.report(ctx)
    got = ssm_moe_model.act_seconds_under(rep, "/eva/scores/")
    if got is None or not rep.iterations:
        return None
    calls = (rep.iterations * sequence_model.fragment_steps(ctx)
             * int(config["num_hidden_layers"]))
    need = eva_model.eva_step_bytes(config, sequence_model.envs(ctx))
    peak = flops.load_peaks(ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * need / peak / (got / calls)
