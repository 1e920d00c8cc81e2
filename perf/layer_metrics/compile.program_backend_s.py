"""Seconds the program's own compiles spent in the backend (the
compile, or the retrieval on a persistent-cache hit): the sum over
the program families of ``backend_s`` in
``compile_stats()["families"]``, family ``other`` left out. The
system's share of ``compile.backend_s``, which times the whole
process from outside."""


def read(ctx):
    from ray_tpu.sharding.compile import compile_stats

    families = compile_stats().get("families")
    if not families:
        return None
    return float(sum(
        row["backend_s"] for name, row in families.items() if name != "other"
    ))
