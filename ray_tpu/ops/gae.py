"""Generalized Advantage Estimation as XLA-friendly scans.

TPU-native counterpart of the reference's numpy GAE
(``rllib/evaluation/postprocessing.py:76`` compute_advantages and the
``discount_cumsum`` helper). The reference runs this per-episode in numpy on
rollout workers; here the fast path is a jit-compiled ``lax.scan`` over fixed
(B, T) fragments inside the learner step, with episode boundaries handled by
``dones`` masks so no dynamic shapes are ever needed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def discount_cumsum_np(x: np.ndarray, gamma: float) -> np.ndarray:
    """y[t] = sum_{k>=t} gamma^(k-t) x[k] (host/numpy golden version)."""
    out = np.zeros_like(x, dtype=np.float32)
    run = 0.0
    for t in range(len(x) - 1, -1, -1):
        run = x[t] + gamma * run
        out[t] = run
    return out


def discount_cumsum(x: jnp.ndarray, gamma: float) -> jnp.ndarray:
    """Reverse discounted cumsum along the last axis via associative scan.

    Uses a first-order linear recurrence composed associatively, so XLA can
    parallelize it (log-depth) instead of a sequential loop.
    """

    def combine(a, b):
        # Each element is (coeff, value): y = coeff * y_next + value
        ca, va = a
        cb, vb = b
        return ca * cb, va * cb + vb

    coeffs = jnp.full_like(x, gamma)
    _, y = jax.lax.associative_scan(
        combine, (coeffs, x), reverse=True, axis=x.ndim - 1
    )
    return y


def compute_gae_np(
    rewards: np.ndarray,
    values: np.ndarray,
    dones: np.ndarray,
    bootstrap_value: float,
    gamma: float = 0.99,
    lambda_: float = 1.0,
):
    """Host/numpy GAE over a single trajectory (golden version).

    Matches the semantics of reference ``postprocessing.py:76``: if the
    trajectory was terminated, ``bootstrap_value`` should be 0; if truncated,
    it is V(s_T).
    """
    T = len(rewards)
    values_tp1 = np.append(values[1:], bootstrap_value)
    not_done = 1.0 - dones.astype(np.float32)
    deltas = rewards + gamma * values_tp1 * not_done - values
    adv = np.zeros(T, dtype=np.float32)
    run = 0.0
    for t in range(T - 1, -1, -1):
        run = deltas[t] + gamma * lambda_ * not_done[t] * run
        adv[t] = run
    value_targets = adv + values
    return adv.astype(np.float32), value_targets.astype(np.float32)


def compute_gae(
    rewards: jnp.ndarray,
    values: jnp.ndarray,
    dones: jnp.ndarray,
    bootstrap_value: jnp.ndarray,
    gamma: float = 0.99,
    lambda_: float = 1.0,
):
    """GAE over fixed-shape (B, T) fragments; jit/TPU fast path.

    Args:
        rewards/values/dones: float/bool arrays of shape (B, T). ``dones``
            marks environment termination at step t (no bootstrap across it).
        bootstrap_value: (B,) value estimate of the observation *after* the
            fragment's last step (0 where the last step terminated).

    Returns:
        (advantages, value_targets), both (B, T) float32.

    Episode boundaries inside a fragment are handled by the ``dones`` mask:
    the recurrence resets because (1 - done) zeroes both the bootstrapped
    next-value and the accumulated advantage.
    """
    rewards = rewards.astype(jnp.float32)
    values = values.astype(jnp.float32)
    not_done = 1.0 - dones.astype(jnp.float32)

    values_tp1 = jnp.concatenate(
        [values[:, 1:], bootstrap_value[:, None]], axis=1
    )
    deltas = rewards + gamma * values_tp1 * not_done - values

    # adv[t] = delta[t] + (gamma*lambda*not_done[t]) * adv[t+1]
    coeffs = gamma * lambda_ * not_done

    def combine(a, b):
        ca, va = a
        cb, vb = b
        return ca * cb, va * cb + vb

    _, adv = jax.lax.associative_scan(
        combine, (coeffs, deltas), reverse=True, axis=deltas.ndim - 1
    )
    value_targets = adv + values
    return adv, value_targets


def compute_gae_fragment(
    rewards: jnp.ndarray,
    values: jnp.ndarray,
    next_values: jnp.ndarray,
    terminateds: jnp.ndarray,
    dones: jnp.ndarray,
    gamma: float = 0.99,
    lambda_: float = 1.0,
):
    """GAE over (B, T) fragments with the HOST lane's truncation
    semantics (``evaluation/postprocessing.py``): bootstrap 0 across a
    *terminated* step, bootstrap ``next_values`` (= V of the final,
    pre-reset observation) across a *truncated* one, and stop the
    advantage accumulation at EVERY episode boundary either way. This
    is the device rollout lane's postprocess
    (``execution/jax_rollout.py``); :func:`compute_gae` above keeps the
    simpler single-mask form for fragments without mid-stream
    truncation.

    Args:
        rewards/values: (B, T) float.
        next_values: (B, T) float — V(NEXT_OBS[t]) per row, i.e. the
            value of the observation AFTER step t *before* any
            auto-reset (for non-terminal steps this equals
            values[t+1]; at a truncation it is the terminal
            observation's value, exactly what the host lane's
            ``value_batch(last_obs)`` bootstrap uses).
        terminateds/dones: (B, T) bool; ``dones = terminateds |
            truncateds``.

    Returns (advantages, value_targets), both (B, T) float32.

    The reverse recurrence is the associative scan (log-depth,
    reassociated: within 1e-4 of the sequential order, the contract of
    docs/data_plane.md)."""
    rewards = rewards.astype(jnp.float32)
    values = values.astype(jnp.float32)
    next_values = next_values.astype(jnp.float32)
    not_term = 1.0 - terminateds.astype(jnp.float32)
    not_done = 1.0 - dones.astype(jnp.float32)

    deltas = rewards + gamma * next_values * not_term - values
    coeffs = gamma * lambda_ * not_done

    # Mosaic (jax 0.9.0, TPU v5e) refuses a sequential Pallas scan here: a
    # dynamic width-1 slice of the lane (time) axis does not lower (PR 21).
    def combine(a, b):
        ca, va = a
        cb, vb = b
        return ca * cb, va * cb + vb

    _, adv = jax.lax.associative_scan(
        combine, (coeffs, deltas), reverse=True, axis=deltas.ndim - 1
    )
    return adv, adv + values


def standardize(x: jnp.ndarray, eps: float = 1e-4) -> jnp.ndarray:
    """Zero-mean unit-variance normalization (reference ppo.py:415
    standardize_fields)."""
    return (x - x.mean()) / jnp.maximum(x.std(), eps)
