"""Bytes of the two stores one stream carries between steps, read from
the live rollout carry's own leaves (those shaped ``(streams, rows,
row)``): 44,040,192 at a window of 2,048, chunks of 16 and episodes of
10,240 over four layers of 8 heads held, of which 33,554,432 are the
window stores' 2,048 rows and 10,485,760 the summary stores' 640;
full-depth caches of the same episode would read 167,772,160. ``None``
without a device lane or for a configuration without ``attention_class:
eva``."""

from perf import eva_model


def read(ctx):
    if not eva_model.is_eva(ctx.cell.config):
        return None
    eng = getattr(ctx.algo, "__dict__", {}).get("_jax_rollout_engine")
    state = (getattr(eng, "_carry", None) or {}).get("state")
    if not state:
        return None
    return eva_model.cache_bytes_per_stream(state)
