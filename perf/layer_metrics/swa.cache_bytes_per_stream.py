"""Bytes of keys and values one stream carries between steps, read from
the live rollout carry's own leaves (those shaped ``(streams, rows,
row)``): 41,943,040 at episodes of 8,192 and a window of 4,096, of which
16,777,216 are the full layer's 8,192 rows and 25,165,824 the three
rings' 4,096 each; three more full-depth caches would read 67,108,864.
It does not grow with the episode past the window. ``None`` without a
device lane or for a configuration without window layers."""

from perf import window_model


def read(ctx):
    if "sliding_window_size" not in ctx.cell.config:
        return None
    eng = getattr(ctx.algo, "__dict__", {}).get("_jax_rollout_engine")
    state = (getattr(eng, "_carry", None) or {}).get("state")
    if not state:
        return None
    return window_model.cache_bytes_per_stream(state)
