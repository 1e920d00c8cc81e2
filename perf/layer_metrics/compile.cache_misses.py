"""Programs of the system that the persistent compile cache did not
hold (``cache_misses`` summed over the program families of
``compile_stats()["families"]``, family ``other`` left out): 0 on a
warm cache; after a change of side, the programs whose key moved."""


def read(ctx):
    from ray_tpu.sharding.compile import compile_stats

    families = compile_stats().get("families")
    if not families:
        return None
    return float(sum(
        row["cache_misses"] for name, row in families.items() if name != "other"
    ))
