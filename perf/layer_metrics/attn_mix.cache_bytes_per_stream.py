"""Bytes of keys and values one stream carries between steps, read from
the live rollout carry's own leaves (those shaped ``(streams, rows,
row)``): 39,845,888 at episodes of 4,096 and a window of 512, of which
33,554,432 are the two full layers' 4,096 rows and 6,291,456 the three
rings' 512 each (all five layers at full depth would read 83,886,080).
The rows are 8 KV heads x 128 whatever the layer's query heads. ``None``
without a device lane or for a configuration whose layers share one
geometry."""

from perf import mixed_attention_model


def read(ctx):
    if "num_attention_heads_per_layer" not in ctx.cell.config:
        return None
    eng = getattr(ctx.algo, "__dict__", {}).get("_jax_rollout_engine")
    state = (getattr(eng, "_carry", None) or {}).get("state")
    if not state:
        return None
    return mixed_attention_model.cache_bytes_per_stream(state)
