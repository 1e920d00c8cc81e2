"""Device time of the standalone rollout program per traced iteration:
``XLA Modules`` events named ``jit_jax_rollout*`` (the family of the
``sharded_jit`` label ``jax_rollout[...]``), mean over the cell's
chips. ``None`` where no program of that family ran."""

from perf import program_trace


def read(ctx):
    rep = program_trace.report(ctx)
    if rep is None:
        return None
    return rep.family_ms("jax_rollout", rep.iterations)
