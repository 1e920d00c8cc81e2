"""A learned index over a cache's rows (the lightning indexer of
DeepSeek Sparse Attention, DeepSeek-V3.2-Exp's technical report, as
``sa_config`` states it): every query scores every row it may see and
attends to the ``top_k`` best of them. The scores, the EXACT choice in
both of its forms and nothing else; the rows' cache, the masks and the
attention itself are ``ops/cached_attention``'s.

``I[t, s] = sum_j w[t, j] * relu(q[t, j] . key[s])`` over the index's
heads ``j``, ONE key a row for all of them. The score product takes its
operands as handed (the layer hands bfloat16) and accumulates in
float32; the relu, the weights and the sum over the heads are float32
on the vector unit (an einsum over the heads would round ``w`` and the
relu to bfloat16 on the MXU). No factor: a positive one changes no
choice.

The choice is the ``min(rows seen, top_k)`` seen rows of the largest
score, ties to the LOWER slot, which is what ``jax.lax.top_k`` keeps (a
stable sort); no approximate top-k, no pooling of rows into blocks. It
is made WITHOUT a sort and handed on as a MASK over the rows where they
lie: the ``top_k``-th largest score is found bit by bit
(:func:`kth_largest`, 32 counting passes over the scores), and the rows
above it and the leading ones at it are the choice. On the v5e (PR 65)
``lax.top_k`` of 2,048 of a (128, 16,640) tile took 1.7 ms where the 32
passes take 0.2, and gathering a step's chosen rows by slot number
(32,768 rows of 1 KB a leaf and layer) took 0.44 ms a leaf, more than
reading every row of the cache under the mask does.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def scores(q, w, keys):
    """``q`` ``(B, T, heads, D)``, ``w`` ``(B, T, heads)`` float32,
    ``keys`` ``(B, S, D)``: ``(B, T, S)`` float32."""
    s = jnp.einsum("bthd,bsd->bths", q, keys, preferred_element_type=jnp.float32)
    index = jnp.sum(jax.nn.relu(s) * w.astype(jnp.float32)[..., None], axis=2)
    # where no head fired the sum is 0.0 or -0.0 by the weights' signs:
    # one number, so that such rows tie and the lower slot wins
    return jnp.where(index == 0.0, 0.0, index)


def _sign_magnitude(bits):
    """A float32's bits (int32) to the int32 whose SIGNED order is the
    floats', and back: its own inverse (the sign bit stays)."""
    return bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))


_TOP_BIT = jnp.uint32(0x80000000)  # signed order <-> unsigned order


def kth_largest(x, k: int):
    """The ``k``-th largest number of each row of ``x`` ``(..., S)``
    float32, duplicates counted (``k <= S``), as ``(..., 1)``: EXACT, by
    a radix select over the numbers' bit patterns in an order that is the
    floats' (-inf lowest; the caller has made its zeros one number), the
    high bit first: a bit stays set iff at least ``k`` numbers lie at or
    above the pattern so far with it set."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    u = jax.lax.bitcast_convert_type(_sign_magnitude(bits), jnp.uint32) ^ _TOP_BIT

    def one_bit(i, found):
        trial = found | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        enough = jnp.sum(u >= trial, axis=-1, keepdims=True, dtype=jnp.int32) >= k
        return jnp.where(enough, trial, found)

    # zero, typed as ``u`` is (under ``shard_map`` a loop's carry must
    # vary over the mesh axes its result does)
    found = jax.lax.fori_loop(0, 32, one_bit, u[..., :1] & jnp.uint32(0))
    return jax.lax.bitcast_convert_type(_sign_magnitude(
        jax.lax.bitcast_convert_type(found ^ _TOP_BIT, jnp.int32)), jnp.float32)


def select(index, seen, top_k: int):
    """The choice as a MASK over the slots: ``index`` ``(..., S)``
    float32, ``seen`` ``(..., S)`` bool; True at the ``min(seen, top_k)``
    seen slots of the largest score, ties to the lower slot. The slots
    above the ``top_k``-th largest score, and of those AT it the leading
    ones that fill the count: no row is gathered, a fragment's queries
    keep walking the rows where they lie."""
    if top_k >= index.shape[-1]:
        return seen
    masked = jnp.where(seen, index, -jnp.inf)
    least = kth_largest(masked, top_k)
    above = masked > least  # where fewer than top_k are seen: all of them
    ties = seen & (masked == least)
    room = top_k - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
    return above | (ties & (jnp.cumsum(ties, axis=-1, dtype=jnp.int32) <= room))
