"""Bytes of delta-rule state one stream carries between steps, read from
the live rollout carry's own leaves: the float32 ``(heads, dk, dv)``
matrices and the three convolutions' last ``conv - 1`` inputs of every
Kimi Delta Attention layer: 13,467,648 for six layers of 32 heads of 128
x 128 with convolutions of width 4 over 4,096 channels; it does not grow
with the episode (the latent layer's rows do, and are not in it).
``None`` without a device lane or for a configuration that is not
``model_type: bailing_hybrid``."""

from perf import kda_latent_model


def read(ctx):
    if not kda_latent_model.is_kda_latent(ctx.cell.config):
        return None
    eng = getattr(ctx.algo, "__dict__", {}).get("_jax_rollout_engine")
    state = (getattr(eng, "_carry", None) or {}).get("state")
    if not state:
        return None
    return kda_latent_model.carried_kda_bytes_per_stream(state)
