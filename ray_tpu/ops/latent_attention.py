"""Multi-head latent attention (DeepSeek-V2/V3, arXiv:2405.04434 and
2412.19437) over a cache of latent rows: a one-token form on two
lowerings and two fragment forms.

A position's cache row is ``[c_kv | k_pe]``: the RMS-normed key/value
latent (``kv_lora_rank`` numbers) and the roped key part that all heads
share (``qk_rope_head_dim``). Per head, ``W_kvb`` (``kv_b``, columns
``[k_nope | v]`` a head) would expand a latent into a ``qk_nope_head_dim``
key part and a ``v_head_dim`` value: the query/key product is
``qk_nope + qk_rope`` wide and the value product ``v_head_dim`` wide.

- :func:`absorbed_step` is the one-token form. The key half of ``W_kvb``
  is absorbed into the query (``q~_h = q_nope_h W_UK_h^T``), the scores
  and the weighted sum run against the latent rows as the cache holds
  them, and the value half is applied after (``o_h = (P_h C) W_UV_h``):
  no key or value of an earlier position is ever rebuilt. Where the
  step kernel's lowering exists
  (``ops/flash_attention.step_kernel_applies``: bfloat16 on a TPU, a
  cache of whole key blocks, a latent of whole lane tiles) the scores,
  the softmax and the weighted sum are
  ``ops/flash_attention.step_attention`` over ONE 576-wide key head
  whose value is the row's leading ``kv_lora_rank`` lanes: the latent
  of a stream's key blocks below its position crosses HBM once (and
  every slot's roped lanes, a ninth as much); everywhere else (the CPU,
  float32) they are XLA's text over every slot under a mask, which is
  also the kernel's oracle and its backward pass.
- :func:`absorbed_fragment` is the fragment form where the tiled
  fragment kernel's lowering exists
  (``ops/flash_attention.fragment_kernel_applies``: bfloat16 on a TPU):
  the same absorbed product for ``T`` tokens a stream, the 32 heads'
  absorbed queries against ONE 576-wide key head that is the latent
  rows as they lie (the stored cache and the fragment's own) with the
  value their leading ``kv_lora_rank`` lanes. No stored key is rebuilt,
  no score matrix is written, stored blocks past a stream's depth are
  skipped, and ``W_kvb`` gets its gradient through the two absorbed
  halves.
- :func:`expanded_fragment` is the fragment form everywhere else (the
  CPU, float32) and the kernel's test oracle: keys and values of the
  stored rows and of the fragment's own are rebuilt through ``W_kvb``
  for a block of streams at a time, and the scores are the masked
  ``(T, S + T)`` matrix, with ``seg`` marking episodes that open inside
  the fragment.

:func:`latent_attention` is the layer's one entry: it writes the
fragment's rows into the cache and picks among them from what the
call sees. ``ray_tpu_mla_decode_lowerings_total{form}`` counts which a
traced layer took (``absorbed`` | ``absorbed_kernel`` |
``absorbed_fragment`` | ``expanded``).
All take
``dtype`` operands (the cache's) and accumulate in float32; masks and
softmax are float32. :func:`yarn_inv_freq` and
:func:`yarn_softmax_scale` are YaRN (arXiv:2309.00071) as DeepSeek-V3's
``modeling_deepseek.py`` applies it: the inverse frequencies blend
``theta^(-2i/d)`` and that over ``factor`` by a linear ramp between the
two correction dimensions, and the softmax scale carries ``mscale^2``.
"""

from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops import cached_attention, flash_attention
from ray_tpu.telemetry import metrics

# streams of a fragment whose expanded scores are alive at once: the
# keys and values of 32 heads are rebuilt for the block as well (0.27 GB
# for 8 streams, and as much again for their cotangents), so a constant
_ENV_BLOCK = 4


def yarn_inv_freq(dim: int, theta: float, scaling: Optional[Dict]) -> np.ndarray:
    """``(dim / 2,)`` float32 inverse frequencies: plain RoPE without a
    ``scaling`` block or at ``factor`` 1."""
    plain = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    factor = float((scaling or {}).get("factor", 1.0))
    if factor == 1.0:
        return plain.astype(np.float32)
    original = float(scaling["original_max_position_embeddings"])

    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(correction_dim(float(scaling.get("beta_fast", 32)))), 0)
    high = min(math.ceil(correction_dim(float(scaling.get("beta_slow", 1)))),
               dim - 1)
    ramp = np.clip(
        (np.arange(dim // 2, dtype=np.float64) - low) / max(high - low, 1e-3), 0, 1)
    return (plain / factor * ramp + plain * (1.0 - ramp)).astype(np.float32)


def yarn_softmax_scale(qk_head_dim: int, scaling: Optional[Dict]) -> float:
    """``qk_head_dim^-1/2 * m^2``, ``m = 0.1 mscale_all_dim ln(factor) +
    1`` (1 without scaling, or where ``mscale_all_dim`` is 0)."""
    scale = qk_head_dim ** -0.5
    s = scaling or {}
    factor, all_dim = float(s.get("factor", 1.0)), float(s.get("mscale_all_dim", 0))
    if factor > 1.0 and all_dim:
        scale *= (0.1 * all_dim * math.log(factor) + 1.0) ** 2
    return scale


def rope(x, positions, inv_freq, interleave: bool = False):
    """Rotate every dimension of ``x`` ``(B, T, H, R)``; ``positions``
    ``(B, T)``. Frequency ``i`` turns the pair ``(x[i], x[i + R / 2])``
    (the ``[first half | second half]`` layout), or with ``interleave``
    the adjacent pair ``(x[2 i], x[2 i + 1])`` (a config's
    ``rope_interleave``)."""
    half = x.shape[-1] // 2
    angle = positions.astype(jnp.float32)[..., None] * jnp.asarray(inv_freq)
    cos, sin = jnp.cos(angle)[:, :, None], jnp.sin(angle)[:, :, None]
    if interleave:
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def latent_attention(q_nope, q_pe, rows_new, cache, kv_b, rows, *, scale, dtype):
    """A latent layer's attention over its cache of latent rows.
    ``q_nope`` ``(B, T, H, dn)`` and ``q_pe`` ``(B, T, H, R)`` float32
    (roped); ``rows_new`` ``(B, T, C + R)`` the fragment's own rows;
    ``cache`` ``(B, positions, C + R)``; ``kv_b`` ``(C, H * (dn +
    dv))``; ``rows`` the fragment's ``seg``, ``positions`` ``(B, T)``
    and ``pos0`` ``(B,)``. Returns ``(o (B, T, H, dv) float32, the cache
    after the fragment, stats)``: one token the absorbed product over
    what the cache then holds, its own row included, on the step kernel
    where ``step_kernel_applies`` says so (one key head, the latent rows
    as they lie, a stream's held key blocks only), else as XLA's text
    over every slot; a fragment the same product on the tiled kernel
    where ``fragment_kernel_applies`` says so, else the expanded text.
    ``stats``: a fragment's key blocks skipped and walked, and those
    the one-token kernel would at each of the fragment's positions, as
    ``ops/cached_attention`` counts them."""
    seg, positions, pos0 = rows["seg"], rows["positions"], rows["pos0"]
    t, heads = q_nope.shape[1:3]
    depth = cache.shape[1]
    new_cache = cached_attention.scatter_rows(cache, rows_new, rows)
    step_kernel = flash_attention.step_kernel_applies(
        heads, 1, cache.shape[2], depth, dtype, value_dim=kv_b.shape[0])
    if t == 1:
        metrics.inc_mla_decode_lowering(
            "absorbed_kernel" if step_kernel else "absorbed")
        metrics.inc_attention_step_lowering("kernel" if step_kernel else "xla")
        o = absorbed_step(
            q_nope[:, 0], q_pe[:, 0], new_cache, kv_b, pos0, scale, dtype,
            kernel=step_kernel)[:, None]
        return o, new_cache, {}
    if flash_attention.fragment_kernel_applies(
            t, heads, 1, cache.shape[2], depth, dtype):
        metrics.inc_mla_decode_lowering("absorbed_fragment")
        metrics.inc_attention_fragment_lowering("kernel")
        skipped, walked = flash_attention.fragment_key_blocks(pos0, depth)
        o = absorbed_fragment(
            q_nope, q_pe, rows_new, cache, kv_b, seg, positions, pos0, scale, dtype)
    else:
        metrics.inc_mla_decode_lowering("expanded")
        metrics.inc_attention_fragment_lowering("xla")
        skipped, walked = jnp.int32(0), 0
        o = expanded_fragment(
            q_nope, q_pe, rows_new, cache, kv_b, seg, pos0, scale, dtype,
            block=_ENV_BLOCK)
    decode = flash_attention.step_key_blocks(
        positions + 1, depth) if step_kernel else (jnp.int32(0), 0)
    return o, new_cache, {
        "attn_key_blocks_skipped": skipped,
        "attn_key_blocks_walked": jnp.int32(walked),
        "attn_decode_key_blocks_skipped": decode[0],
        "attn_decode_key_blocks_walked": jnp.int32(decode[1])}


def _kv_b_by_head(kv_b, heads: int, dtype):
    """``(latent, heads, nope + v)`` view of ``W_kvb`` in ``dtype``."""
    return kv_b.astype(dtype).reshape(kv_b.shape[0], heads, -1)


def absorbed_step(q_nope, q_pe, cache, kv_b, pos0, scale: float, dtype,
                  kernel: bool = False, **spellings):
    """One token a stream against the latent rows. ``q_nope`` ``(B, H,
    dn)`` and ``q_pe`` ``(B, H, R)`` float32 (roped); ``cache`` ``(B,
    S, C + R)`` with the step's own row already at slot ``pos0``;
    ``kv_b`` ``(C, H * (dn + dv))``. Returns ``(B, H, dv)`` float32.
    Its three parts open the scopes ``absorb``, ``scores`` and ``out``
    under the caller's. ``kernel``: scores, softmax and weighted sum on
    ``flash_attention.step_attention`` (under ``scores``), the ``H``
    absorbed queries the rows of one tile over one key head, the value
    the key block's leading ``C`` lanes; ``spellings``: the tests' of
    that function (``block_k``, ``interpret``)."""
    heads, dn = q_nope.shape[1], q_nope.shape[2]
    latent = kv_b.shape[0]
    w = _kv_b_by_head(kv_b, heads, dtype)
    with jax.named_scope("absorb"):
        q_lat = jnp.einsum(
            "bhd,chd->bhc", q_nope.astype(dtype), w[..., :dn],
            preferred_element_type=jnp.float32,
        )
        q_row = (jnp.concatenate([q_lat, q_pe], axis=-1) * scale).astype(dtype)
    if kernel:
        with jax.named_scope("scores"):
            mixed = flash_attention.step_attention(
                q_row[:, None, None], cache, None, pos0 + 1, value_dim=latent,
                **spellings)[:, 0, 0]
    else:
        with jax.named_scope("scores"):
            scores = jnp.einsum(
                "bhr,bsr->bhs", q_row, cache, preferred_element_type=jnp.float32
            )
            seen = jnp.arange(cache.shape[1])[None, None] <= pos0[:, None, None]
            weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        with jax.named_scope("out"):
            # over the whole row: the 64 roped numbers cost an eighth more
            # products and spare a sliced copy of the cache
            mixed = jnp.einsum(
                "bhs,bsr->bhr", weights.astype(dtype), cache,
                preferred_element_type=jnp.float32,
            )[..., :latent]
    with jax.named_scope("out"):
        return jnp.einsum(
            "bhc,chv->bhv", mixed.astype(dtype), w[..., dn:],
            preferred_element_type=jnp.float32,
        )


def absorbed_fragment(q_nope, q_pe, rows_new, cache, kv_b, seg, positions,
                      pos0, scale: float, dtype, **kernel):
    """A fragment against the latent rows as they lie, on the tiled
    fragment kernel. Operands as :func:`expanded_fragment`'s, and
    ``positions`` ``(B, T)``; ``cache`` as the carry holds it BEFORE the
    fragment's scatter, and it gets no gradient (the kernel's contract).
    Returns ``(B, T, H, dv)`` float32. Its three parts open the scopes
    ``absorb``, ``scores`` and ``out`` under the caller's, as
    :func:`absorbed_step`'s do. ``kernel``: the tests' spellings of
    ``fragment_attention`` (``block_k``, ``head_tile``, ``interpret``)."""
    heads, dn = q_nope.shape[2:]
    latent = kv_b.shape[0]
    w = _kv_b_by_head(kv_b, heads, dtype)
    with jax.named_scope("absorb"):
        q_lat = jnp.einsum(
            "bthd,chd->bthc", q_nope.astype(dtype), w[..., :dn],
            preferred_element_type=jnp.float32,
        )
        q_row = (jnp.concatenate([q_lat, q_pe], axis=-1) * scale).astype(dtype)
    with jax.named_scope("scores"):
        # one key head for all the query heads; the value is the row's
        # leading lanes, read out of the cache's own blocks
        mixed = flash_attention.fragment_attention(
            q_row[:, :, None], rows_new[:, :, None],
            rows_new[:, :, None, :latent], cache, cache, pos0, seg, positions,
            **kernel,
        )[:, :, 0]
    with jax.named_scope("out"):
        return jnp.einsum(
            "bthc,chv->bthv", mixed.astype(dtype), w[..., dn:],
            preferred_element_type=jnp.float32,
        )


def expanded_fragment(q_nope, q_pe, rows_new, cache, kv_b, seg, pos0,
                      scale: float, dtype, block: int = 8):
    """A fragment from its stored rows. ``q_nope`` ``(B, T, H, dn)``,
    ``q_pe`` ``(B, T, H, R)`` float32 (roped); ``rows_new`` ``(B, T, C +
    R)`` the fragment's own latent rows and ``cache`` ``(B, S, C + R)``
    the stored ones, both ``dtype``; ``seg`` ``(B, T)`` counts episodes
    opened inside the fragment; ``pos0`` ``(B,)`` the rows stored.
    Returns ``(B, T, H, dv)`` float32. Scores are alive for ``block``
    streams at a time, each block recomputed in the backward pass."""
    b, t, heads, dn = q_nope.shape
    latent = kv_b.shape[0]
    w = _kv_b_by_head(kv_b, heads, dtype)

    def expand(rows):
        kv = jnp.einsum(
            "bsc,chd->bshd", rows[..., :latent], w,
            preferred_element_type=jnp.float32,
        ).astype(dtype)
        return kv[..., :dn], kv[..., dn:], rows[..., latent:]

    def scores(qn, qp, k_nope, k_pe):
        return jnp.einsum(
            "bthd,bshd->bhts", qn, k_nope, preferred_element_type=jnp.float32
        ) + jnp.einsum(
            "bthr,bsr->bhts", qp, k_pe, preferred_element_type=jnp.float32
        )

    def attend(qn, qp, new, old, sege, pos0e):
        qn, qp = (qn * scale).astype(dtype), (qp * scale).astype(dtype)
        k_old, v_old, pe_old = expand(old)
        k_new, v_new, pe_new = expand(new)
        see_old, see_new = cached_attention.fragment_masks(
            sege, pos0e, None, cache.shape[1], None)
        s = jnp.concatenate([
            jnp.where(see_old[:, None], scores(qn, qp, k_old, pe_old), -jnp.inf),
            jnp.where(see_new[:, None], scores(qn, qp, k_new, pe_new), -jnp.inf),
        ], axis=-1)
        p = jax.nn.softmax(s, axis=-1).astype(dtype)
        stored = old.shape[1]
        return jnp.einsum(
            "bhts,bshv->bthv", p[..., :stored], v_old,
            preferred_element_type=jnp.float32,
        ) + jnp.einsum(
            "bhts,bshv->bthv", p[..., stored:], v_new,
            preferred_element_type=jnp.float32,
        )

    nb = max(1, b // block)
    if b % nb:
        nb = 1
    args = (q_nope, q_pe, rows_new, cache, seg, pos0)
    if nb == 1:
        return jax.checkpoint(attend)(*args)
    blocked = tuple(a.reshape((nb, b // nb) + a.shape[1:]) for a in args)
    out = jax.lax.map(lambda xs: jax.checkpoint(attend)(*xs), blocked)
    return out.reshape((b,) + out.shape[2:])
