"""Traces of any ``sharded_jit`` program during the measured window
(``compile_stats()["traces"]`` after - before): 0 expected; a retrace
in the window costs throughput and shows in ``iter_p95_ms``."""


def read(ctx):
    return float(ctx.window.delta("traces"))
