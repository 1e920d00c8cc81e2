"""Device time of the cross-attention layers (the READERS of the shared
cache: the query projection, ``xattn/scores``, ``xattn/out``,
``xattn/diff``, the output projection; the full layer that OWNS the
cache is under ``attn`` and not in it) in ONE decode step of the fused
lane: the leaf operations under the lane's ``rollout/act`` whose path
goes on through the model's ``xattn`` scope, per traced iteration and
per step of the fragment. The two scopes are matched in order on an
operation's ``tf_op`` path (``perf/ssm_moe_model.act_seconds_under``),
not as one string, so a loop's frames may stand between them and the
learn program's ``learn/xattn`` is not counted. ``None`` for a program
without them."""

from perf import program_trace, sequence_model, ssm_moe_model


def read(ctx):
    rep = program_trace.report(ctx)
    got = ssm_moe_model.act_seconds_under(rep, "/xattn/")
    if got is None or not rep.iterations:
        return None
    return 1e3 * got / (rep.iterations * sequence_model.fragment_steps(ctx))
