"""Bytes one stream carries between steps, read from the live rollout
carry's own leaves (those shaped ``(streams, rows, row)``): 45,342,720
at episodes of 8,192 over the six layers held, of which 41,943,040 are
the full layer's cache (8,192 rows of 1,280 bfloat16 keys and as many
values), 2,621,440 the window layer's ring of 512 rows and 2 x 389,120
the two scans' float32 matrices and convolution inputs. The full
layer's cache is there ONCE: the cross layer that reads it carries
nothing (a copy a reader would read 87,285,760). ``None`` without a
device lane or for a configuration that is not ``model_type:
phi4flash``."""

from perf import sambay_model


def read(ctx):
    if not sambay_model.is_sambay(ctx.cell.config):
        return None
    eng = getattr(ctx.algo, "__dict__", {}).get("_jax_rollout_engine")
    state = (getattr(eng, "_carry", None) or {}).get("state")
    if not state:
        return None
    return sambay_model.cache_bytes_per_stream(state)
