"""Manifold-constrained hyper-connections (arXiv:2512.24880): the
residual stream is ``n`` lanes of the hidden width, and each sublayer
``F`` reads a mix of the lanes and writes back into all of them,

    X <- H_res X + H_post^T F(H_pre X),

with the three maps made from the token's own stream: ``x' =
rms(vec(X))``, ``H~ = a * (x' phi) + b``, ``H_pre = sigmoid(H~_pre)``
``(1 x n)``, ``H_post = 2 sigmoid(H~_post)`` ``(1 x n)``, and ``H_res``
``(n x n)`` brought towards the doubly stochastic matrices by
Sinkhorn-Knopp rounds on ``exp(clamp(H~_res))``.

The stream is held flat, ``(..., n * D)`` with lane ``i`` at ``[i D, (i
+ 1) D)``: a ``(n, D)`` pair of minor dimensions would pad ``n`` to a
whole tile of 8 rows on the device. Everything is float32; what reads
the stream is elementwise over lanes, so the compiler fuses a mix into
one pass, forward and (the mixes state their own reverse mode, lane by
lane) backward. ``phi`` is ``(n D, 2 n + n^2)``, columns ``[pre | post |
res]``, ``a`` ``(3,)`` and ``b`` ``(2 n + n^2,)`` likewise.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def sinkhorn(logits, rounds: int, eps: float, lo: float, hi: float,
             unroll: bool = True):
    """``(..., n, n)``: ``exp(clamp(logits))``, then ``rounds`` times
    (each column over its sum + eps, each row over its sum + eps).
    ``unroll``: the rounds as one straight line (2 x ``rounds``
    elementwise steps on 16 numbers a token fuse into one pass: the
    one-token form, where a loop's 20 turns a sublayer and step would
    cost more than the arithmetic); else a loop of ``rounds`` turns
    (the fragment form, whose reverse mode through the straight line
    was 3.5 s of compiling a layer)."""

    def one(m, _):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
        return m / (jnp.sum(m, axis=-1, keepdims=True) + eps), None

    rounds = int(rounds)
    m, _ = jax.lax.scan(
        one, jnp.exp(jnp.clip(logits, lo, hi)), None, length=rounds,
        unroll=rounds if unroll else 1,
    )
    return m


def maps(x, norm, phi, a, b, n: int, norm_eps: float, rounds: int, eps: float,
         lo: float, hi: float, unroll: bool = True):
    """``(H_pre (..., n), H_post (..., n), H_res (..., n, n))`` of the
    flat stream ``x`` ``(..., n D)``; ``norm`` is the zero-centred
    weight of the RMSNorm over all ``n D`` numbers; ``unroll`` as
    :func:`sinkhorn`'s."""
    x = x.astype(jnp.float32)
    scaled = x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + norm_eps
    ) * (1.0 + norm)
    h = jnp.dot(scaled, phi, precision=_HI)
    pre = jax.nn.sigmoid(a[0] * h[..., :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * h[..., n : 2 * n] + b[n : 2 * n])
    res = sinkhorn(
        (a[2] * h[..., 2 * n :] + b[2 * n :]).reshape(h.shape[:-1] + (n, n)),
        rounds, eps, lo, hi, unroll,
    )
    return pre, post, res


def lanes_of(x, n: int):
    d = x.shape[-1] // n
    return [x[..., i * d : (i + 1) * d] for i in range(n)]


def _over_width(a, b):
    return jnp.sum(a * b, axis=-1)


@jax.custom_vjp
def mix_in(x, pre):
    """``H_pre X``: ``(..., D)`` of the flat stream ``x`` ``(..., n D)``
    and ``pre`` ``(..., n)``."""
    n = pre.shape[-1]
    return sum(pre[..., i : i + 1] * lane for i, lane in enumerate(lanes_of(x, n)))


def _mix_in_bwd(saved, g):
    # lane by lane: reverse mode through the slices would pad every
    # lane's cotangent to the stream's width and add n of them
    x, pre = saved
    n = pre.shape[-1]
    return (
        jnp.concatenate([pre[..., i : i + 1] * g for i in range(n)], axis=-1),
        jnp.stack([_over_width(g, lane) for lane in lanes_of(x, n)], axis=-1),
    )


mix_in.defvjp(lambda x, pre: (mix_in(x, pre), (x, pre)), _mix_in_bwd)


@jax.custom_vjp
def mix_out(x, y, post, res):
    """``H_res X + H_post^T y``, flat like ``x``: ``y`` ``(..., D)``,
    ``post`` ``(..., n)``, ``res`` ``(..., n, n)``."""
    n = post.shape[-1]
    lanes = lanes_of(x, n)
    return jnp.concatenate([
        sum(res[..., i, j, None] * lanes[j] for j in range(n))
        + post[..., i : i + 1] * y
        for i in range(n)
    ], axis=-1)


def _mix_out_bwd(saved, g):
    x, y, post, res = saved
    n = post.shape[-1]
    lanes, gs = lanes_of(x, n), lanes_of(g, n)
    return (
        jnp.concatenate([
            sum(res[..., i, j, None] * gs[i] for i in range(n)) for j in range(n)
        ], axis=-1),
        sum(post[..., i : i + 1] * gs[i] for i in range(n)),
        jnp.stack([_over_width(gi, y) for gi in gs], axis=-1),
        jnp.stack([
            jnp.stack([_over_width(gi, lane) for lane in lanes], axis=-1)
            for gi in gs
        ], axis=-2),
    )


mix_out.defvjp(
    lambda x, y, post, res: (mix_out(x, y, post, res), (x, y, post, res)),
    _mix_out_bwd,
)
