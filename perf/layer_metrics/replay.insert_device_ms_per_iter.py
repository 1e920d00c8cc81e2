"""Device time of the ring insert per traced iteration: the programs
``jit_replay_insert*``, plus the executions of ``jit_tree_update*``
that were dispatched inside a ``replay:insert`` span (the new rows'
priorities entering the trees). The whole-ring layout copies that the
insert program owns are in it."""

from perf import program_trace


def read(ctx):
    rep = program_trace.report(ctx)
    if rep is None:
        return None
    return rep.family_ms(
        "replay_insert", rep.iterations,
        also_s=program_trace.seconds_dispatched_under(
            ctx.trace, "tree_update", "replay:insert"
        ),
    )
