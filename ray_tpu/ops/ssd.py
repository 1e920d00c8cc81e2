"""The Mamba-2 state-space recurrence (Dao & Gu, arXiv:2405.21060) in
two forms that are the same function of the same inputs.

Per head, with a ``(P, N)`` state ``S`` (``P`` the head size, ``N`` the
state size), a step size ``dt_t > 0``, a scalar ``A < 0`` a head, and
``B_t``, ``C_t`` of ``N`` numbers shared by every head::

    S   <- exp(dt_t A) S + dt_t x_t B_t^T
    y_t  = S C_t

(the skip ``D x_t`` and the gate are the caller's).

- :func:`ssd_step` is that recurrence for ONE token: the rollout
  lane's decode step (state in, state out). Plain ``jax.numpy``: the
  write is elementwise and the read a reduction of its result, with no
  reduction BEFORE the write as the delta rule has, so there is no
  kernel; ``ray_tpu_ssm_step_lowerings_total{path="xla"}`` counts, at
  trace time, each traced one-token form.
- :func:`ssd_chunked` computes a fragment of ``T`` tokens from a start
  state in chunks of ``C``: inside a chunk token ``i`` reads token
  ``j <= i`` through ``exp(sum_{j < l <= i} dt_l A) (C_i . B_j) dt_j``
  (the semiseparable matrix, all matrix products) and only the
  chunk-end state is carried: the learn program's form.

**Resets.** ``resets`` (1.0 where a token begins a new episode) zero
the state before that token. In the chunked form a reset splits its
chunk into segments: products across a segment boundary are masked
out, and the start state reaches only the tokens before the first
reset. The one-token form has no argument for it; its caller zeroes
the rows first (``SequenceLM.reset_state``), as for ``ops/deltanet.py``.

Everything here is float32 at precision "highest": the state is an
accumulator over the whole episode, and the PPO ratio divides what the
chunked form says by what the recurrence said.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.telemetry import metrics as telemetry_metrics

_HI = jax.lax.Precision.HIGHEST


def ssd_step(state, x, dt, a, b, c):
    """One token. ``state`` ``(..., H, P, N)``; ``x`` ``(..., H, P)``;
    ``dt`` ``(..., H)``; ``a`` ``(H,)``; ``b``, ``c`` ``(..., N)``.
    Returns ``(state, y)`` with ``y`` ``(..., H, P)``."""
    telemetry_metrics.inc_ssm_step_lowering("xla")
    decay = jnp.exp(dt * a)[..., None, None]
    write = (dt[..., None] * x)[..., None] * b[..., None, None, :]
    state = decay * state + write
    y = jnp.sum(state * c[..., None, None, :], axis=-1)
    return state, y


def ssd_chunked(
    state,
    x,
    dt,
    a,
    b,
    c,
    resets: Optional[jnp.ndarray] = None,
    chunk: int = 256,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``T`` tokens from ``state``. ``x`` ``(B, T, H, P)``; ``dt`` ``(B,
    T, H)``; ``a`` ``(H,)``; ``b``, ``c`` ``(B, T, N)``; ``state`` ``(B,
    H, P, N)``; ``resets`` ``(B, T)`` or None. ``T`` is a multiple of
    ``chunk`` (or shorter than it). Returns ``(y (B, T, H, P), state)``.
    """
    bsz, t, h, p = x.shape
    size = min(int(chunk), t)
    if t % size:
        raise ValueError(f"fragment of {t} tokens is not a multiple of {size}")
    n = t // size
    if resets is None:
        resets = jnp.zeros((bsz, t), jnp.float32)

    def chunks(v):  # (B, T, ...) -> (n, B, C, ...)
        return jnp.moveaxis(v.reshape((bsz, n, size) + v.shape[2:]), 1, 0)

    row = jnp.arange(size)
    lower = row[:, None] >= row[None, :]

    def one_chunk(s, xs):
        xc, dtc, bc, cc, rc = xs  # (B, C, H, P), (B, C, H), (B, C, N) x 2, (B, C)
        g = jnp.moveaxis(dtc * a, 1, 2)  # (B, H, C) log-decays
        gcum = jnp.cumsum(g, axis=-1)
        seg = jnp.cumsum((rc > 0.5).astype(jnp.int32), axis=-1)[:, None]  # (B, 1, C)
        same = seg[..., :, None] == seg[..., None, :]  # (B, 1, C, C)
        # the start state reaches the tokens before the first reset
        reach = jnp.exp(gcum) * (seg == 0)  # (B, H, C)
        diff = gcum[..., :, None] - gcum[..., None, :]
        decay = jnp.exp(jnp.where(lower & same, diff, -jnp.inf))  # (B, H, C, C)
        cb = jnp.einsum("bin,bjn->bij", cc, bc, precision=_HI)  # (B, C, C)
        xdt = jnp.moveaxis(xc * dtc[..., None], 1, 2)  # (B, H, C, P)
        y = jnp.matmul(decay * cb[:, None], xdt, precision=_HI) + reach[
            ..., None
        ] * jnp.einsum("bhpn,bcn->bhcp", s, cc, precision=_HI)
        # what is left at the chunk's end: the carried state if no
        # reset fell in the chunk, and the writes of the last segment
        tail = jnp.exp(gcum[..., -1:] - gcum) * (seg == seg[..., -1:])
        s = s * reach[..., -1, None, None] + jnp.einsum(
            "bhcp,bcn->bhpn", xdt * tail[..., None], bc, precision=_HI
        )
        return s, jnp.moveaxis(y, 1, 2)  # (B, C, H, P)

    state, ys = jax.lax.scan(
        one_chunk, state, tuple(chunks(v) for v in (x, dt, b, c, resets))
    )
    return jnp.moveaxis(ys, 0, 1).reshape(bsz, t, h, p), state
