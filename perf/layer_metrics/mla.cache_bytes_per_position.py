"""Bytes of attention state a stream holds per position and layer, read
from the live rollout carry's own leaves (those shaped ``(streams,
positions, row)``): 1,152 for a latent cache of 512 + 64 bfloat16
numbers a row; a cache of expanded keys and values of 32 heads would
read 20,480. ``None`` without a device lane or an attention cache."""

from perf import latent_model


def read(ctx):
    eng = getattr(ctx.algo, "__dict__", {}).get("_jax_rollout_engine")
    state = (getattr(eng, "_carry", None) or {}).get("state")
    if not state:
        return None
    return latent_model.cache_bytes_per_position(state, ctx.cell.config)
