"""Seconds the program's own compiles spent tracing to a jaxpr and
lowering it to a module, since the process began: the sum over the
program families of ``trace_s + lower_s`` in
``compile_stats()["families"]`` (family ``other``, what no
``ShardedFunction`` compiled, is left out). The part of ``setup_s``
that no compile cache takes away."""


def read(ctx):
    from ray_tpu.sharding.compile import compile_stats

    families = compile_stats().get("families")
    if not families:
        return None
    return float(sum(
        row["trace_s"] + row["lower_s"]
        for name, row in families.items() if name != "other"
    ))
