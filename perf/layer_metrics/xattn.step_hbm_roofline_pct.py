"""Share of its HBM roofline the one-token attention of a cross layer
reaches, a call: the bytes ONE call (one layer, one token of every
stream) must move (``perf/sambay_model.xattn_step_bytes``: the shared
cache's bfloat16 rows below the position at the mean depth once, keys
and values, for BOTH softmax maps and both value halves of every pair,
and the query and output rows) over the chip's peak bandwidth
(perf/peaks.json), over the device time of the leaf operations under the
lane's ``rollout/act`` whose path goes on through the model's
``xattn/scores`` scope (the step kernel over the cache the layer does
not own: it scatters nothing), per traced iteration, step of the
fragment and cross layer. The kernel fetches whole key blocks of 512
rows, so at the mean it moves up to a block more than the rows below
the position and reads below 100 for that alone. ``None`` for a
configuration that is not ``model_type: phi4flash`` or a program without
the scope."""

from perf import flops, program_trace, sambay_model, sequence_model, ssm_moe_model


def read(ctx):
    config = ctx.cell.config
    if not sambay_model.is_sambay(config):
        return None
    rep = program_trace.report(ctx)
    got = ssm_moe_model.act_seconds_under(rep, "/xattn/scores/")
    layers = sambay_model.kinds(config).count(sambay_model.CROSS)
    if got is None or not rep.iterations or not layers:
        return None
    calls = rep.iterations * sequence_model.fragment_steps(ctx) * layers
    need = sambay_model.xattn_step_bytes(config, sequence_model.envs(ctx))
    peak = flops.load_peaks(ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * need / peak / (got / calls)
