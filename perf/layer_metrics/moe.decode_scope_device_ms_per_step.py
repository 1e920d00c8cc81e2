"""Device time of the expert blocks in ONE decode step of the fused
lane: the leaf operations under the lane's ``rollout/act`` whose path
goes on through the model's ``moe`` scopes (``moe/route``,
``moe/experts``, ``moe/shared`` of every expert block; the mixers are
not in it), per traced iteration and per step of the fragment. The two
scopes are matched in order on an operation's ``tf_op`` path, as
``ssm.decode_scope_device_ms_per_step`` matches its own. ``None`` for a
program without them."""

from perf import program_trace, sequence_model, ssm_moe_model


def seconds(rep):
    return ssm_moe_model.act_seconds_under(rep, "/moe/")


def read(ctx):
    rep = program_trace.report(ctx)
    got = seconds(rep)
    if got is None or not rep.iterations:
        return None
    return 1e3 * got / (rep.iterations * sequence_model.fragment_steps(ctx))
