"""Device time per optimizer update of the leaf operations under the
program's ``learn/*`` scopes (``learn/minibatch``, ``learn/loss_grad``,
``learn/allreduce``, ``learn/optimizer``, ``learn/grad_norm``,
``learn/commit``, ``learn/td_error``). Scopes are read from the
``tf_op`` stat of each operation's metadata in the run's
``.xplane.pb``."""

from perf import program_trace


def read(ctx):
    rep = program_trace.report(ctx)
    if rep is None:
        return None
    return rep.scope_ms("learn/", rep.updates)
