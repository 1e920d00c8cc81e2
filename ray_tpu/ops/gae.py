"""Generalized Advantage Estimation as XLA-friendly scans.

TPU-native counterpart of the reference's numpy GAE
(``rllib/evaluation/postprocessing.py:76`` compute_advantages and the
``discount_cumsum`` helper). The reference runs this per-episode in numpy on
rollout workers; here the fast path is a jit-compiled ``lax.scan`` over fixed
(B, T) fragments inside the learner step, with episode boundaries handled by
``dones`` masks so no dynamic shapes are ever needed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl

from ray_tpu.ops._pallas import kernel_selected


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


# Mosaic (jax 0.9.0, TPU v5e) refuses the kernel's dynamic width-1
# slice of the lane (time) axis: "Mosaic failed to compile TPU kernel:
# cannot statically prove that index in dimension 1 is a multiple of
# 128 ... vector.load ... memref<8x128xf32, #tpu.memory_space<vmem>>
# -> vector<8x1xf32>". So ``use_pallas=None`` (auto) resolves to the
# associative scan on every backend; the kernel runs only when forced
# or through the interpreter.
_COMPILES_ON_TPU = False


def _gae_scan_kernel(deltas_ref, coeffs_ref, adv_ref, *, t):
    """Reverse first-order recurrence over the time axis for one row
    block: adv[t] = delta[t] + coeff[t] * adv[t+1]. Sequential in T
    (the mathematically exact order — no reassociation), vectorized
    over the row block."""
    # ray-tpu: device-fn
    rows = adv_ref.shape[0]

    def body(i, run):
        col = t - 1 - i
        d = deltas_ref[:, pl.ds(col, 1)]
        c = coeffs_ref[:, pl.ds(col, 1)]
        run = d + c * run
        adv_ref[:, pl.ds(col, 1)] = run
        return run

    jax.lax.fori_loop(
        0, t, body, jnp.zeros((rows, 1), jnp.float32)
    )


def _gae_scan_pallas(deltas, coeffs, interpret):
    b, t = deltas.shape
    bq = min(b, 8) if b % 8 else 8
    pad = (-b) % bq
    if pad:
        deltas = jnp.pad(deltas, ((0, pad), (0, 0)))
        coeffs = jnp.pad(coeffs, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_gae_scan_kernel, t=t),
        grid=((b + pad) // bq,),
        in_specs=[
            pl.BlockSpec((bq, t), lambda i: (i, 0)),
            pl.BlockSpec((bq, t), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bq, t), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b + pad, t), jnp.float32),
        interpret=interpret,
    )(deltas, coeffs)
    return out[:b] if pad else out


def compute_gae_fragment(
    rewards: jnp.ndarray,
    values: jnp.ndarray,
    next_values: jnp.ndarray,
    terminateds: jnp.ndarray,
    dones: jnp.ndarray,
    gamma: float = 0.99,
    lambda_: float = 1.0,
    use_pallas=None,
    interpret: bool = False,
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

    ``use_pallas`` (True/False forces; None = auto, which is the
    associative scan — see ``_COMPILES_ON_TPU``) routes the reverse
    recurrence through the Pallas fragment-scan kernel: sequential in
    T per row block — the mathematically exact evaluation order — vs
    the associative scan's log-depth reassociation, so the two paths
    agree to float32 tolerance (~1e-5 rel), not bitwise; see
    docs/data_plane.md. ``interpret=True`` runs the kernel through the
    Pallas interpreter (the CPU parity path)."""
    rewards = rewards.astype(jnp.float32)
    values = values.astype(jnp.float32)
    next_values = next_values.astype(jnp.float32)
    not_term = 1.0 - terminateds.astype(jnp.float32)
    not_done = 1.0 - dones.astype(jnp.float32)

    deltas = rewards + gamma * next_values * not_term - values
    coeffs = gamma * lambda_ * not_done

    if kernel_selected(
        use_pallas, interpret, compiles_on_tpu=_COMPILES_ON_TPU
    ):
        adv = _gae_scan_pallas(deltas, coeffs, interpret)
        return adv, adv + values

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
