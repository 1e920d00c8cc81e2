"""The selective scan of Mamba-1 (Gu & Dao, arXiv:2312.00752, section
3.2 and algorithm 2) as ONE op in two forms that are the same function:
per channel ``c`` and state ``n``

    S_t[n, c] = exp(dt_t[c] A[n, c]) S_{t-1}[n, c] + dt_t[c] B_t[n] u_t[c]
    y_t[c]    = sum_n S_t[n, c] C_t[n]

a decay a (channel, state), which is why ``ops/ssd.py`` does not serve:
its chunked form turns ONE scalar decay a head into ``(chunk, chunk)``
matrices, and a decay that differs along both axes of the state has no
such form. Everything here is float32 and element-wise (no product
enters the MXU): the state is an accumulator over the episode, and the
PPO ratio divides one form by the other.

Layout. The state is ``(streams, N, channels)``: the CHANNELS on the
lanes (5,120 = 40 whole tiles) and the ``N`` states on the sublanes, so
that the device pads nothing (``(streams, channels, 16)`` would pad 16
lanes to 128, eight times the bytes); ``A`` lies the same way, ``(N,
channels)``.

- :func:`selective_step`: one token, state in and state out.
- :func:`selective_scan`: a fragment from a stored state with ``resets``
  inside. A fragment's states, ``(streams, T, N, channels)``, are 1.34 GB
  a layer at 16 x 256 x 16 x 5,120 and are never alive: the state rides
  the carry of a scan over the tokens, ``chunk`` tokens under one
  ``jax.checkpoint``, so that the backward pass holds the states at the
  chunks' starts (``T / chunk``) and recomputes one chunk's (``chunk``)
  at a time.

Both are XLA's text on every backend: one token moves the state once
each way, which is the form's floor, and the fragment's loop moves it
once a token (a kernel that held a tile of channels in VMEM over a
tile's tokens would move it once a fragment: ROADMAP).
``ray_tpu_selective_scan_lowerings_total{form}`` counts the traced
forms."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.telemetry import metrics

# tokens whose states the backward pass holds at once
_CHUNK = 16


def _token(state, u, dt, a, b, c):
    """``state`` ``(B, N, C)``; ``u``, ``dt`` ``(B, C)``; ``a`` ``(N,
    C)``; ``b``, ``c`` ``(B, N)``."""
    new = jnp.exp(dt[:, None, :] * a) * state + (dt * u)[:, None, :] * b[:, :, None]
    return new, jnp.sum(new * c[:, :, None], axis=1)


def selective_step(state, u, dt, a, b, c):
    """One token of every stream: ``(state after, y (B, C))``."""
    metrics.inc_selective_scan_lowering("step")
    return _token(state, u, dt, a, b, c)


def selective_scan(state, u, dt, a, b, c, resets, chunk: int = _CHUNK):
    """A fragment from the stored ``state`` ``(B, N, C)``: ``u``, ``dt``
    ``(B, T, C)``, ``b``, ``c`` ``(B, T, N)``, ``resets`` ``(B, T)`` (1.0
    where a token opens an episode: its state starts from nothing).
    Returns ``(y (B, T, C), state after)``."""
    metrics.inc_selective_scan_lowering("fragment")
    t = u.shape[1]
    chunk = max(k for k in range(1, min(chunk, t) + 1) if t % k == 0)
    # time-major, a chunk a leading row
    xs = jax.tree_util.tree_map(
        lambda v: jnp.moveaxis(v, 1, 0).reshape((t // chunk, chunk) + v.shape[:1]
                                                + v.shape[2:]),
        (u, dt, b, c, resets > 0.5))

    def token(s, x):
        u_t, dt_t, b_t, c_t, fresh = x
        s = jnp.where(fresh[:, None, None], 0.0, s)
        return _token(s, u_t, dt_t, a, b_t, c_t)

    @jax.checkpoint
    def some_tokens(s, x):
        return jax.lax.scan(token, s, x)

    state, y = jax.lax.scan(some_tokens, state, xs)
    return jnp.moveaxis(y.reshape((t,) + y.shape[2:]), 0, 1), state
