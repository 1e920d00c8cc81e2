"""Bytes of keys, values and INDEX KEYS one stream carries between
steps, read from the live rollout carry's own leaves (those shaped
``(streams, rows, row)``): 71,303,168 at four layers and episodes of
8,192 (a position is 1,024 key/value numbers and 64 index numbers in
bfloat16, 2,176 B; 4,194,304 of the total are the index's; 142,606,336
at episodes of 16,384). It grows
with the episode: the index chooses what a query reads, not what a
stream keeps. ``None`` without a device lane or for a configuration
without ``sa_config``."""

from perf import sparse_attention_model


def read(ctx):
    if "sa_config" not in ctx.cell.config:
        return None
    eng = getattr(ctx.algo, "__dict__", {}).get("_jax_rollout_engine")
    state = (getattr(eng, "_carry", None) or {}).get("state")
    if not state:
        return None
    return sparse_attention_model.cache_bytes_per_stream(state)
