"""Fused attention (flash-attention style) as a Pallas TPU kernel.

The hot op of the attention model family (``models/attention.py`` GTrXL;
reference ``rllib/models/torch/attention_net.py:37`` materializes the
full (T, S) score matrix through torch softmax). This kernel computes
``softmax(q kᵀ / √d + mask) v`` with the online-softmax recurrence:
scores for one (query-block, key-block) tile at a time live in VMEM and
the running (max, sum, accumulator) statistics are carried across key
blocks — the (T, S) attention matrix never touches HBM. Accumulation is
float32 regardless of input dtype (MXU-native bf16 inputs welcome).

Masking is the banded-causal form both call sites need, parameterized by
a static ``causal_offset`` M: query i attends key j iff ``j <= i + M``
(GTrXL's [memory | fragment] window uses M = memory_len; plain causal
self-attention is M = 0; ``None`` disables masking). Shapes stay static:
the wrapper pads T/S up to block multiples and the kernel masks the
padded tail, so XLA compiles one program per shape.

Differentiation: ``jax.custom_vjp`` with the backward pass rematerialized
through the XLA reference implementation — the forward avoids the O(T·S)
HBM intermediate; the backward recomputes it inside one fused XLA
program (the standard remat trade: FLOPs for memory). The reference
path doubles as the CPU fallback, so the op is portable: Pallas on TPU,
XLA elsewhere, and ``interpret=True`` exercises the kernel in tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_BLOCK_Q = 128
_BLOCK_K = 128
_NEG_INF = -1e30


def _reference_attention(q, k, v, causal_offset):
    """XLA reference: identical math with the (T, S) matrix materialized
    (used for the backward pass, the CPU path, and golden tests).
    q: (N, T, D), k/v: (N, S, D)."""
    scale = 1.0 / jnp.sqrt(jnp.float32(q.shape[-1]))
    scores = jnp.einsum(
        "ntd,nsd->nts", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if causal_offset is not None:
        T, S = scores.shape[-2:]
        i = jnp.arange(T)[:, None]
        j = jnp.arange(S)[None, :]
        valid = j <= i + causal_offset
        scores = jnp.where(valid, scores, _NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        # rows with zero valid keys are defined as zero output (matches
        # the kernel's l=0 → 0 convention), not softmax-of-all-masked
        probs = jnp.where(valid.any(-1, keepdims=True), probs, 0.0)
    else:
        probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("nts,nsd->ntd", probs, v.astype(jnp.float32)).astype(
        q.dtype
    )


def _online_softmax_stream(
    q_ref, k_ref, v_ref, row, offset, s_actual, block_k
):
    """The shared online-softmax recurrence: stream key blocks through
    VMEM carrying (m, l, acc). ``offset`` may be a static int or a
    traced scalar (key j valid iff ``j <= row + offset``); ``None``
    disables the band. Returns float32 (m (BQ,1), l (BQ,1),
    acc (BQ,D) UNNORMALIZED)."""
    q = q_ref[0].astype(jnp.float32)  # (BQ, D)
    bq, d = q.shape
    q = q * (1.0 / jnp.sqrt(jnp.float32(d)))
    num_kb = k_ref.shape[1] // block_k

    def body(kb, carry):
        m_prev, l_prev, acc = carry
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(
            jnp.float32
        )
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(
            jnp.float32
        )
        s = q @ k_blk.T  # (BQ, BK)
        col = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1
        )
        valid = col < s_actual
        if offset is not None:
            valid = valid & (col <= row + offset)
        s = jnp.where(valid, s, _NEG_INF)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # masked columns contribute exactly zero mass (exp(s - m) would
        # be 1 for rows whose scores are ALL masked)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = corr * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc = corr * acc + p @ v_blk
        return m_new, l_new, acc

    m0 = jnp.full((bq, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)
    return jax.lax.fori_loop(0, num_kb, body, (m0, l0, acc0))


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, *, s_actual, causal_offset, block_k
):
    """One (batch·head, query-block) program producing NORMALIZED
    attention output (static banded offset)."""
    qi = pl.program_id(1)
    bq = q_ref.shape[1]
    row = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    _, l, acc = _online_softmax_stream(
        q_ref, k_ref, v_ref, row, causal_offset, s_actual, block_k
    )
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _block_kernel(
    off_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, *,
    s_actual, block_k,
):
    """Stats-returning variant for ring attention: the same shared
    online-softmax stream, but the banded-causal offset is a RUNTIME
    scalar (SMEM) — inside a shard_map ring the offset depends on the
    traced device index — and the per-row (max, sum) statistics are
    emitted so ring hops can merge partial results exactly."""
    qi = pl.program_id(1)
    bq = q_ref.shape[1]
    row = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    m, l, acc = _online_softmax_stream(
        q_ref, k_ref, v_ref, row, off_ref[0], s_actual, block_k
    )
    o_ref[0] = acc  # UNNORMALIZED accumulator (caller merges/divides)
    m_ref[0] = m
    l_ref[0] = l


def flash_block_attention_stats(q, k, v, offset, *, interpret=False):
    """One attention block with running statistics, for ring attention.

    q: (N, T, D); k, v: (N, S, D); offset: int32 scalar array — key j
    is visible to query i iff ``j <= i + offset`` (pass S for "no
    mask"). Returns (acc (N, T, D) float32 UNNORMALIZED, m (N, T), l
    (N, T)) — exactly the quantities the flash merge combines across
    blocks. Forward-only (ring-level callers own differentiation)."""
    setup = _pallas_setup(q, k, v)
    n, t, d = q.shape
    bq, bk, qp, kp, vp, tp, grid, vmem = setup
    smem = {"memory_space": pltpu.SMEM}
    acc, m, l = pl.pallas_call(
        functools.partial(
            _block_kernel, s_actual=k.shape[1], block_k=bk
        ),
        out_shape=[
            jax.ShapeDtypeStruct((n, tp, d), jnp.float32),
            jax.ShapeDtypeStruct((n, tp, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, tp, 1), jnp.float32),
        ],
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda b, i: (0,), **smem),
            *_qkv_specs(bq, kp.shape[1], d, vmem),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0), **vmem),
            pl.BlockSpec((1, bq, 1), lambda b, i: (b, i, 0), **vmem),
            pl.BlockSpec((1, bq, 1), lambda b, i: (b, i, 0), **vmem),
        ],
        interpret=interpret,
    )(jnp.asarray(offset, jnp.int32).reshape(1), qp, kp, vp)
    return acc[:, :t], m[:, :t, 0], l[:, :t, 0]


def _ceil_to(x, m):
    return ((x + m - 1) // m) * m


def _pallas_setup(q, k, v):
    """Shared block-size / padding / grid scaffolding for both
    pallas_call wrappers. Block sizes are rounded up to multiples of 8
    so the (sublane, lane) tiles Mosaic carves out of each block stay
    aligned to the TPU's native (8, 128) vreg tiling — an unaligned
    block (e.g. bq=20 from a T=20 GTrXL unroll) would force Mosaic to
    retile on every load. Padding (below) absorbs the rounding."""
    n, t, d = q.shape
    s = k.shape[1]
    bq = min(_BLOCK_Q, _ceil_to(max(8, t), 8))
    bk = min(_BLOCK_K, _ceil_to(max(8, s), 8))
    qp = _pad_to(q, 1, bq)
    kp = _pad_to(k, 1, bk)
    vp = _pad_to(v, 1, bk)
    tp = qp.shape[1]
    grid = (n, tp // bq)
    vmem = {"memory_space": pltpu.VMEM}
    return bq, bk, qp, kp, vp, tp, grid, vmem


def _qkv_specs(bq, s_pad, d, vmem):
    """The q (blocked) + k/v (full) input BlockSpecs both wrappers use."""
    return [
        pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0), **vmem),
        pl.BlockSpec((1, s_pad, d), lambda b, i: (b, 0, 0), **vmem),
        pl.BlockSpec((1, s_pad, d), lambda b, i: (b, 0, 0), **vmem),
    ]


def _pad_to(x, axis, multiple):
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _flash_fwd_pallas(q, k, v, causal_offset, interpret):
    t, d = q.shape[1:]
    bq, bk, qp, kp, vp, tp, grid, vmem = _pallas_setup(q, k, v)
    out = pl.pallas_call(
        functools.partial(
            _fwd_kernel,
            s_actual=k.shape[1],
            causal_offset=causal_offset,
            block_k=bk,
        ),
        out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
        grid=grid,
        in_specs=_qkv_specs(bq, kp.shape[1], d, vmem),
        out_specs=pl.BlockSpec(
            (1, bq, d), lambda b, i: (b, i, 0), **vmem
        ),
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :t]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_attention(q, k, v, causal_offset, interpret):
    return _flash_fwd_pallas(q, k, v, causal_offset, interpret)


def _flash_fwd_rule(q, k, v, causal_offset, interpret):
    return _flash_fwd_pallas(q, k, v, causal_offset, interpret), (q, k, v)


def _flash_bwd_rule(causal_offset, interpret, residuals, g):
    q, k, v = residuals
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _reference_attention(
            q_, k_, v_, causal_offset
        ),
        q, k, v,
    )
    return vjp(g)


_flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(
    q, k, v, *, causal_offset=None, use_pallas=None, interpret=False
):
    """Fused multi-head attention.

    q: (B, H, T, D); k, v: (B, H, S, D) → (B, H, T, D).
    ``causal_offset=M`` masks key j for query i unless ``j <= i + M``
    (None = full attention). ``use_pallas=None`` auto-selects: the
    Pallas kernel on TPU backends, the XLA reference elsewhere.
    ``interpret=True`` forces the kernel through the Pallas interpreter
    (CPU testing of the real kernel)."""
    B, H, T, D = q.shape
    S = k.shape[2]
    if use_pallas is None:
        # the kernel compiled and matched the XLA reference on a TPU v5e
        # at the shapes of tests/test_tpu_hardware.py (jax 0.9.0)
        use_pallas = interpret or jax.default_backend() == "tpu"
    qf = q.reshape(B * H, T, D)
    kf = k.reshape(B * H, S, D)
    vf = v.reshape(B * H, S, D)
    if use_pallas:
        out = _flash_attention(qf, kf, vf, causal_offset, interpret)
    else:
        out = _reference_attention(qf, kf, vf, causal_offset)
    return out.reshape(B, H, T, D)
