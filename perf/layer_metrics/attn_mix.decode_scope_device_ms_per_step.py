"""Device time of the attention layers' mixers in ONE decode step of
the fused lane: the leaf operations under the lane's ``rollout/act``
whose path goes on through the model's ``attn`` or ``swa`` scope (the
projections, q/k norms and RoPE, the cache's scatter, the scores over
the full cache or the ring, the gate and the output projection of every
attention layer; the feed-forwards and the head are not in it), per
traced iteration and per step of the fragment. The scopes are matched in
order on an operation's ``tf_op`` path, not as one string, so a loop's
frames may stand between them and the learn program's ``learn/attn`` is
not counted. ``None`` for a program without them."""

from perf import program_trace, sequence_model


def seconds(rep):
    if rep is None or not rep.op_scopes:
        return None
    total, seen = 0.0, False
    for op, d in program_trace._leaf_ops(rep.op_scopes, rep.trace.bounds):
        at = op[0].find("rollout/act/")
        if at >= 0 and {"attn", "swa"} & set(op[0][at:].split("/")):
            total += d / 1e9
            seen = True
    return total if seen else None


def read(ctx):
    rep = program_trace.report(ctx)
    got = seconds(rep)
    if got is None or not rep.iterations:
        return None
    return 1e3 * got / (rep.iterations * sequence_model.fragment_steps(ctx))
