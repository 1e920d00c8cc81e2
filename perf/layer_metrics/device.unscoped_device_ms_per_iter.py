"""Device time per traced iteration of the leaf operations under none
of the program's scopes: what the compiler put in itself (layout
copies of whole buffers carry no ``tf_op``) and the few operations
the program leaves unnamed. ``None`` where the program has no scopes
at all (a program from before PR 25)."""

from perf import program_trace


def read(ctx):
    rep = program_trace.report(ctx)
    if rep is None:
        return None
    return rep.scope_ms("", rep.iterations)
