"""Convolutional vision networks.

Counterpart of the reference's ``rllib/models/torch/visionnet.py`` with the
standard Atari "Nature CNN" filter stack (reference
``rllib/models/utils.py get_filter_config``). Convolutions run in bfloat16 by
default — conv FLOPs dominate Atari learner time and the MXU natively prefers
bf16 — with float32 heads for logits/value.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.base import RTModel, get_activation

# (out_channels, kernel, stride) — Nature CNN for 84x84
NATURE_FILTERS = ((32, (8, 8), (4, 4)), (64, (4, 4), (2, 2)), (64, (3, 3), (1, 1)))
# for 42x42 downsampled (reference get_filter_config)
SMALL_FILTERS = ((16, (4, 4), (2, 2)), (32, (4, 4), (2, 2)), (256, (11, 11), (1, 1)))


def get_filter_config(shape) -> Tuple:
    """Pick a conv stack for the obs resolution (reference models/utils.py)."""
    if len(shape) == 3 and shape[0] in (84, 80) :
        return NATURE_FILTERS
    if len(shape) == 3 and shape[0] == 42:
        return SMALL_FILTERS
    return NATURE_FILTERS


class VisionNet(RTModel):
    num_outputs: int
    conv_filters: Tuple = NATURE_FILTERS
    conv_activation: str = "relu"
    post_fcnet_hiddens: Sequence[int] = (512,)
    post_fcnet_activation: str = "relu"
    vf_share_layers: bool = True
    dtype_: str = "bfloat16"

    @nn.compact
    def __call__(self, obs, state=(), seq_lens=None):
        dtype = jnp.dtype(self.dtype_)
        act = get_activation(self.conv_activation)
        post_act = get_activation(self.post_fcnet_activation)

        x = obs.astype(dtype)
        if x.dtype == jnp.uint8 or obs.dtype == jnp.uint8:
            x = obs.astype(dtype) / 255.0
        # layer scopes with the activation inside (flax's own module
        # scope, `conv_<i>`, ends before it)
        for i, (ch, kernel, stride) in enumerate(self.conv_filters):
            with jax.named_scope(f"conv{i}"):
                x = act(
                    nn.Conv(
                        ch, kernel, strides=stride, padding="VALID",
                        name=f"conv_{i}", dtype=dtype,
                    )(x)
                )
        x = x.reshape(x.shape[0], -1)
        with jax.named_scope("fc"):
            for i, size in enumerate(self.post_fcnet_hiddens):
                x = post_act(
                    nn.Dense(size, name=f"post_fc_{i}", dtype=dtype)(x)
                )

        with jax.named_scope("head"):
            logits = nn.Dense(
                self.num_outputs, name="logits", dtype=jnp.float32,
                kernel_init=nn.initializers.variance_scaling(
                    0.01, "fan_in", "truncated_normal"),
            )(x.astype(jnp.float32))
        if self.vf_share_layers:
            value = nn.Dense(1, name="value", dtype=jnp.float32)(
                x.astype(jnp.float32)
            )
        else:
            y = obs.astype(dtype)
            if obs.dtype == jnp.uint8:
                y = obs.astype(dtype) / 255.0
            for i, (ch, kernel, stride) in enumerate(self.conv_filters):
                y = act(
                    nn.Conv(ch, kernel, strides=stride, padding="VALID",
                            name=f"vf_conv_{i}", dtype=dtype)(y)
                )
            y = y.reshape(y.shape[0], -1).astype(jnp.float32)
            value = nn.Dense(1, name="value", dtype=jnp.float32)(y)
        return logits, value.squeeze(-1), ()
