"""Device time of one decode step of the fused lane: the leaf
operations under the program's ``rollout/act`` (the model's one-token
form: ``rollout/act/linear_attn``, ``.../attn``, ``.../moe/*``,
``.../head``; one tail forward a fragment included),
``rollout/env_step`` and ``rollout/state_reset`` scopes, per traced
iteration and per step of the fragment."""

from perf import program_trace, sequence_model


def read(ctx):
    rep = program_trace.report(ctx)
    seconds = sequence_model.decode_seconds(rep)
    if seconds is None or not rep.iterations:
        return None
    return 1e3 * seconds / (rep.iterations * sequence_model.fragment_steps(ctx))
