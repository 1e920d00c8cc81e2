"""Gated delta rule (Gated DeltaNet, Yang et al. 2024) in two forms
that are the same function of the same inputs.

Per head, with a ``(dk, dv)`` state ``S``, a log-decay ``g_t <= 0``
and a write strength ``beta_t`` in (0, 1)::

    S   <- exp(g_t) * S
    d_t  = beta_t * (v_t - S^T k_t)
    S   <- S + k_t d_t^T
    o_t  = S^T q_t

- :func:`gated_delta_step` is that recurrence for ONE token: the
  rollout lane's decode step (state in, state out).
- :func:`gated_delta_chunked` computes a fragment of ``T`` tokens from
  a start state in chunks of ``C``: inside a chunk the ``C`` rank-one
  writes are solved at once (``(I + tril(K_beta K^T * decay, -1))^-1``,
  by the nilpotent product ``(I - A)(I + A^2)(I + A^4)...``, all matrix
  products), and only the chunk-end state is carried: the learn
  program's form, whose backward pass keeps ``T / C`` states instead
  of ``T``.

``resets`` (1.0 where a token begins a new episode) zero the state
before that token. In the chunked form a reset splits its chunk into
segments: products across a segment boundary are masked out, and the
start state reaches only the tokens before the first reset.

Everything here is float32 at precision "highest": the state is an
accumulator over the whole episode, and the PPO ratio divides what the
chunked form says by what the recurrence said.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def gated_delta_step(state, q, k, v, g, beta):
    """One token of the recurrence. ``state`` ``(..., dk, dv)``; ``q``,
    ``k`` ``(..., dk)``; ``v`` ``(..., dv)``; ``g``, ``beta`` ``(...)``.
    Returns ``(state, o)`` with ``o`` ``(..., dv)``."""
    state = state * jnp.exp(g)[..., None, None]
    read = jnp.einsum("...kv,...k->...v", state, k, precision=_HI)
    delta = beta[..., None] * (v - read)
    state = state + k[..., :, None] * delta[..., None, :]
    out = jnp.einsum("...kv,...k->...v", state, q, precision=_HI)
    return state, out


def _unit_lower_inverse(a):
    """``(I + a)^-1`` for strictly lower-triangular ``a`` ``(..., C, C)``:
    ``a`` is nilpotent (``a^C = 0``), so the inverse is the finite
    product ``(I - a)(I + a^2)(I + a^4)...`` — log2(C) matrix products
    and no triangular solve."""
    c = a.shape[-1]
    eye = jnp.eye(c, dtype=a.dtype)
    inv = eye - a
    power = a
    span = 2
    while span < c:
        power = jnp.matmul(power, power, precision=_HI)
        inv = jnp.matmul(inv, eye + power, precision=_HI)
        span *= 2
    return inv


def gated_delta_chunked(
    state,
    q,
    k,
    v,
    g,
    beta,
    resets: Optional[jnp.ndarray] = None,
    chunk: int = 64,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``T`` tokens from ``state``. ``q``, ``k`` ``(B, T, H, dk)``; ``v``
    ``(B, T, H, dv)``; ``g``, ``beta`` ``(B, T, H)``; ``state`` ``(B, H,
    dk, dv)``; ``resets`` ``(B, T)`` or None. ``T`` is a multiple of
    ``chunk`` (or shorter than it). Returns ``(o (B, T, H, dv), state)``.
    """
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    c = min(int(chunk), t)
    if t % c:
        raise ValueError(f"fragment of {t} tokens is not a multiple of {c}")
    n = t // c
    if resets is None:
        resets = jnp.zeros((b, t), jnp.float32)

    def chunks(x):  # (B, T, H, ...) -> (n, B, H, C, ...)
        x = x.reshape((b, n, c) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    qs, ks, vs = chunks(q), chunks(k), chunks(v)
    gs, betas = chunks(g), chunks(beta)
    rs = jnp.moveaxis(resets.reshape(b, n, c), 1, 0)  # (n, B, C)
    row = jnp.arange(c)
    lower = row[:, None] >= row[None, :]
    strictly_lower = row[:, None] > row[None, :]

    def one_chunk(s, x):
        qc, kc, vc, gc, bc, rc = x
        gcum = jnp.cumsum(gc, axis=-1)  # (B, H, C)
        seg = jnp.cumsum((rc > 0.5).astype(jnp.int32), axis=-1)[:, None]
        same = seg[..., :, None] == seg[..., None, :]  # (B, 1, C, C)
        # the start state reaches the tokens before the first reset
        reach = jnp.exp(gcum) * (seg == 0)
        diff = gcum[..., :, None] - gcum[..., None, :]
        decay = jnp.exp(jnp.where(lower & same, diff, -jnp.inf))
        kb = kc * bc[..., None]
        a = jnp.einsum("bhik,bhjk->bhij", kb, kc, precision=_HI)
        solve = _unit_lower_inverse(jnp.where(strictly_lower, a * decay, 0.0))
        u = jnp.matmul(solve, vc * bc[..., None], precision=_HI)
        w = jnp.matmul(solve, kb * reach[..., None], precision=_HI)
        v_new = u - jnp.matmul(w, s, precision=_HI)  # (B, H, C, dv)
        qk = jnp.einsum("bhik,bhjk->bhij", qc, kc, precision=_HI) * decay
        out = jnp.matmul(qc * reach[..., None], s, precision=_HI) + jnp.matmul(
            qk, v_new, precision=_HI
        )
        # what is left at the chunk's end: the carried state if no
        # reset fell in the chunk, and the writes of the last segment
        tail = jnp.exp(gcum[..., -1:] - gcum) * (seg == seg[..., -1:])
        s = s * reach[..., -1, None, None] + jnp.einsum(
            "bhjk,bhjv->bhkv", kc * tail[..., None], v_new, precision=_HI
        )
        return s, out

    state, outs = jax.lax.scan(
        one_chunk, state, (qs, ks, vs, gs, betas, rs)
    )
    # (n, B, H, C, dv) -> (B, T, H, dv)
    outs = jnp.moveaxis(jnp.moveaxis(outs, 0, 1), 2, 3)
    return outs.reshape(b, t, h, dv), state
