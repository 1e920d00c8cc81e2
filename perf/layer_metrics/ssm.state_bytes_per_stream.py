"""Bytes of model state one stream carries between steps, read from the
live rollout carry's own leaves (every leaf, the position among them):
23,538,692 for nine state-space layers (a ``64 x 64 x 128`` float32
matrix and a ``3 x 4,352`` convolution tail each) and one attention
layer's 2,048 bfloat16 key and value rows of 512; it does not grow with
the episode. ``None`` without a device lane or for a configuration
without state-space layers."""

from perf import ssm_model


def read(ctx):
    if "mamba_d_state" not in ctx.cell.config:
        return None
    eng = getattr(ctx.algo, "__dict__", {}).get("_jax_rollout_engine")
    state = (getattr(eng, "_carry", None) or {}).get("state")
    if not state:
        return None
    return ssm_model.state_bytes_per_stream(state)
