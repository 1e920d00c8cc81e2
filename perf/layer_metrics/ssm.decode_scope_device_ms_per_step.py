"""Device time of the state-space mixers in ONE decode step of the
fused lane: the leaf operations under the lane's ``rollout/act`` whose
path goes on through the model's ``ssm`` scope (``ssm/in``,
``ssm/conv``, ``ssm/step``, ``ssm/out`` of every state-space layer; the
feed-forwards and the attention layer are not in it), per traced
iteration and per step of the fragment. A run of layers is a scan, so
the loop's own frames stand between the two scopes on an operation's
``tf_op`` path (``rollout/act/while/body/closed_call/ssm/step/...``):
the two are matched in order, not as one string. ``None`` for a program
without them."""

from perf import program_trace, sequence_model


def seconds(rep):
    if rep is None or not rep.op_scopes:
        return None
    total, seen = 0.0, False
    for op, d in program_trace._leaf_ops(rep.op_scopes, rep.trace.bounds):
        at = op[0].find("rollout/act/")
        if at >= 0 and "/ssm/" in op[0][at:]:
            total += d / 1e9
            seen = True
    return total if seen else None


def read(ctx):
    rep = program_trace.report(ctx)
    got = seconds(rep)
    if got is None or not rep.iterations:
        return None
    return 1e3 * got / (rep.iterations * sequence_model.fragment_steps(ctx))
