"""Dueling / distributional / noisy Q-network for the DQN family.

Counterpart of the reference's ``rllib/algorithms/dqn/dqn_torch_model.py``
(DQNTorchModel: advantage/value streams, C51 support heads, NoisyLayer).
One flax module owns the trunk (MLP, or Nature-CNN for image obs) and the
Q heads; ``q_dist`` exposes the per-action support logits the C51 loss
needs, while ``__call__`` returns expected Q values so the generic
epsilon-greedy action path works unchanged (argmax over expected Q is
correct for both dueling and distributional heads).

NoisyNet weight noise (Fortunato et al. 2018) is driven by an explicit
``noise_key`` argument rather than a flax rng collection, so the same
program works deterministically (``noise_key=None`` → mean weights) and
stochastically under jit without rng-collection plumbing.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models.base import RTModel, get_activation
from ray_tpu.models.cnn import get_filter_config


class NoisyDense(nn.Module):
    """Factorized-Gaussian noisy linear layer (reference
    ``rllib/models/torch/modules/noisy_layer.py``): w = μ_w + σ_w·(f(ε_in)
    f(ε_out)ᵀ), f(x) = sign(x)·√|x|; σ initialized to sigma0/√fan_in.
    ``noise_key=None`` uses the mean weights (evaluation mode)."""

    features: int
    sigma0: float = 0.5

    @nn.compact
    def __call__(self, x, noise_key=None):
        in_dim = x.shape[-1]
        sigma_init = self.sigma0 / np.sqrt(in_dim)
        w_mu = self.param(
            "w_mu",
            nn.initializers.variance_scaling(
                1.0 / 3.0, "fan_in", "uniform"
            ),
            (in_dim, self.features),
        )
        w_sigma = self.param(
            "w_sigma",
            nn.initializers.constant(sigma_init),
            (in_dim, self.features),
        )
        b_mu = self.param(
            "b_mu", nn.initializers.zeros, (self.features,)
        )
        b_sigma = self.param(
            "b_sigma",
            nn.initializers.constant(sigma_init),
            (self.features,),
        )
        if noise_key is None:
            return x @ w_mu + b_mu
        k_in, k_out = jax.random.split(noise_key)

        def f(eps):
            return jnp.sign(eps) * jnp.sqrt(jnp.abs(eps))

        eps_in = f(jax.random.normal(k_in, (in_dim, 1)))
        eps_out = f(jax.random.normal(k_out, (1, self.features)))
        w = w_mu + w_sigma * (eps_in @ eps_out)
        b = b_mu + b_sigma * eps_out[0]
        return x @ w + b


class DQNModel(RTModel):
    """Trunk + dueling/distributional Q heads. ``num_outputs`` is the
    number of discrete actions (catalog custom-model calling
    convention)."""

    num_outputs: int
    hiddens: Sequence[int] = (256, 256)
    activation: str = "tanh"
    use_conv: bool = False
    conv_filters: Optional[Tuple] = None
    conv_activation: str = "relu"
    # convs run in bf16 like VisionNet (MXU-native); heads stay float32
    conv_dtype: str = "bfloat16"
    num_atoms: int = 1
    v_min: float = -10.0
    v_max: float = 10.0
    dueling: bool = True
    noisy: bool = False
    sigma0: float = 0.5

    def setup(self):
        if self.use_conv:
            filters = self.conv_filters or get_filter_config((84, 84, 4))
            dtype = jnp.dtype(self.conv_dtype)
            self._convs = [
                nn.Conv(
                    out_ch, kernel, stride, padding="VALID", dtype=dtype
                )
                for out_ch, kernel, stride in filters
            ]
        self._fcs = [nn.Dense(h) for h in self.hiddens]
        head = (
            (lambda n: NoisyDense(n, sigma0=self.sigma0))
            if self.noisy
            else nn.Dense
        )
        self._adv_head = head(self.num_outputs * self.num_atoms)
        if self.dueling:
            self._value_head = head(self.num_atoms)

    def _head(self, layer, x, noise_key):
        if self.noisy:
            return layer(x, noise_key=noise_key)
        return layer(x)

    def features(self, obs: jnp.ndarray) -> jnp.ndarray:
        if self.use_conv:
            x = obs.astype(jnp.dtype(self.conv_dtype))
            if obs.dtype == jnp.uint8:  # raw pixels only (VisionNet)
                x = x / 255.0
            act = get_activation(self.conv_activation)
            # layer scopes with the activation inside (flax's own
            # module scope, `_convs_<i>`, ends before it)
            for i, conv in enumerate(self._convs):
                with jax.named_scope(f"conv{i}"):
                    x = act(conv(x))
            x = x.reshape((x.shape[0], -1)).astype(jnp.float32)
        else:
            x = obs.astype(jnp.float32).reshape((obs.shape[0], -1))
        act = get_activation(self.activation)
        with jax.named_scope("fc"):
            for fc in self._fcs:
                x = act(fc(x))
        return x

    def q_dist(self, obs, noise_key=None):
        """→ (q_values (B, A), support_logits (B, A, atoms),
        support_probs (B, A, atoms) or None when num_atoms == 1).
        Dueling combine happens per atom: support = V + A - mean_a(A)
        (reference dqn_torch_model.py get_q_value_distributions +
        get_state_value)."""
        k_a = k_v = None
        if noise_key is not None:
            k_a, k_v = jax.random.split(noise_key)
        feat = self.features(obs)
        with jax.named_scope("head"):
            adv = self._head(self._adv_head, feat, k_a).reshape(
                (-1, self.num_outputs, self.num_atoms)
            )
            if self.dueling:
                value = self._head(
                    self._value_head, feat, k_v
                ).reshape((-1, 1, self.num_atoms))
                support = (
                    value + adv - jnp.mean(adv, axis=1, keepdims=True)
                )
            else:
                support = adv
        if self.num_atoms > 1:
            probs = jax.nn.softmax(support, axis=-1)
            z = jnp.linspace(
                self.v_min, self.v_max, self.num_atoms
            )
            q = jnp.sum(probs * z, axis=-1)
            return q, support, probs
        q = support[..., 0]
        return q, support, None

    def __call__(self, obs, state=(), seq_lens=None, noise_key=None):
        q, _, _ = self.q_dist(obs, noise_key=noise_key)
        return q, jnp.max(q, axis=-1), ()


def categorical_projection(
    next_probs: jnp.ndarray,
    rewards: jnp.ndarray,
    bootstrap_discount: jnp.ndarray,
    not_done: jnp.ndarray,
    v_min: float,
    v_max: float,
) -> jnp.ndarray:
    """C51 Bellman projection (Bellemare et al. 2017; reference
    ``dqn_torch_policy.py`` QLoss distributional branch): shift the atom
    support by the n-step Bellman operator and redistribute probability
    mass onto the fixed grid. Fully vectorized — the scatter-add over
    floor/ceil bins is two one-hot contractions, so XLA sees dense
    (B, atoms, atoms) matmuls instead of per-sample scatters.

    next_probs: (B, atoms) target-net probs of the chosen next action.
    Returns the projected target distribution m: (B, atoms).
    """
    num_atoms = next_probs.shape[-1]
    z = jnp.linspace(v_min, v_max, num_atoms)
    dz = (v_max - v_min) / (num_atoms - 1)
    tz = (
        rewards[:, None]
        + (bootstrap_discount * not_done)[:, None] * z[None, :]
    )
    tz = jnp.clip(tz, v_min, v_max)
    b = (tz - v_min) / dz  # (B, atoms), in [0, atoms-1]
    low = jnp.floor(b)
    high = jnp.ceil(b)
    # mass to the lower bin; when b lands exactly on a bin (low == high)
    # all of it goes there
    w_low = (high - b) + (low == high).astype(b.dtype)
    w_high = b - low
    onehot_low = jax.nn.one_hot(low.astype(jnp.int32), num_atoms)
    onehot_high = jax.nn.one_hot(high.astype(jnp.int32), num_atoms)
    m = jnp.einsum("ba,bax->bx", next_probs * w_low, onehot_low)
    m = m + jnp.einsum("ba,bax->bx", next_probs * w_high, onehot_high)
    return m
