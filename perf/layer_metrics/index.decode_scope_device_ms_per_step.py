"""Device time of the learned index in ONE decode step of the fused
lane: the leaf operations under the lane's ``rollout/act`` whose path
goes on through the attention layers' ``attn/index/`` scopes (the three
projections, LN and RoPE; the scores of every slot of the stream's
index cache; the exact top-k as a mask, 32 counting passes) or
``attn/select`` (rows fetched by number: none today), of every layer, per traced
iteration and per step of the fragment. The scopes are matched in order
on an operation's ``tf_op`` path, so a loop's frames may stand between
them and the learn program's ``learn/attn/index`` is not counted.
``None`` for a configuration without ``sa_config`` or a program without
the scopes."""

from perf import program_trace, sequence_model


def seconds(rep):
    if rep is None or not rep.op_scopes:
        return None
    total, seen = 0.0, False
    for op, d in program_trace._leaf_ops(rep.op_scopes, rep.trace.bounds):
        at = op[0].find("rollout/act/")
        if at >= 0 and any(n in op[0][at:] for n in ("attn/index/", "attn/select")):
            total += d / 1e9
            seen = True
    return total if seen else None


def read(ctx):
    if "sa_config" not in ctx.cell.config:
        return None
    rep = program_trace.report(ctx)
    got = seconds(rep)
    if got is None or not rep.iterations:
        return None
    return 1e3 * got / (rep.iterations * sequence_model.fragment_steps(ctx))
