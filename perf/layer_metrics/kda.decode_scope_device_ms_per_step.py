"""Device time of the Kimi Delta Attention mixers in ONE decode step of
the fused lane: the leaf operations under the lane's ``rollout/act``
whose path goes on through the model's ``kda`` scope (the projections,
``kda/gate``, ``kda/conv``, ``kda/rule``, ``kda/out`` of every KDA
layer; the feed-forwards and the latent layer are not in it), per traced
iteration and per step of the fragment. The two scopes are matched in
order on an operation's ``tf_op`` path, as
``ssm.decode_scope_device_ms_per_step`` matches its own. ``None`` for a
program without them."""

from perf import program_trace, sequence_model, ssm_moe_model


def read(ctx):
    rep = program_trace.report(ctx)
    got = ssm_moe_model.act_seconds_under(rep, "/kda/")
    if got is None or not rep.iterations:
        return None
    return 1e3 * got / (rep.iterations * sequence_model.fragment_steps(ctx))
