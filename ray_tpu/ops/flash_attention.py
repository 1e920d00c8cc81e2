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

from ray_tpu.ops import backend

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
        use_pallas = interpret or backend.is_tpu()
    qf = q.reshape(B * H, T, D)
    kf = k.reshape(B * H, S, D)
    vf = v.reshape(B * H, S, D)
    if use_pallas:
        out = _flash_attention(qf, kf, vf, causal_offset, interpret)
    else:
        out = _reference_attention(qf, kf, vf, causal_offset)
    return out.reshape(B, H, T, D)


# -- a fragment over a stored cache (the sequence models' learn form) -------
#
# ``ops/cached_attention.cached_attention``'s fragment form: T queries of
# every stream over the rows its cache holds and the fragment's own
# keys. One grid step is one stream, one key head and one block of keys;
# the ``group`` query heads that share the key head are rows of the one
# query tile, so a key block crosses HBM once a key head; where the
# backward pass cannot hold them all (the latent rows' one key head for
# 32 query heads of 576 lanes), a further grid axis walks tiles of query
# heads, each over the stream's key blocks. The masks are
# built in the kernel from the stream's start position (a scalar-prefetch
# operand, which also keeps the blocks past it off the grid's work) and
# the queries' episode numbers and positions.

_FRAGMENT_BLOCK_K = 512
# of the v5e's 128 MiB of VMEM, what a call may take (the default is 16)
_FRAGMENT_VMEM_BYTES = 64 * 2 ** 20
_LANES = 128
# a masked score: finite, so that a row a block masks whole carries
# ``exp(0)`` sums that the first real key's correction ``exp(_MASKED -
# m)`` = 0 wipes out exactly (every query sees its own key, last)
_MASKED = -0.7 * float(jnp.finfo(jnp.float32).max)
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_FAR = 2 ** 30  # a position no query's reaches


def fragment_block_k(depth: int, block_k: int | None = None) -> int:
    """Keys of one stored block: the largest of 512, 256, 128 that
    divides ``depth`` (0 where none does)."""
    if block_k is not None:
        return block_k if depth % block_k == 0 else 0
    for size in (_FRAGMENT_BLOCK_K, 256, _LANES):
        if depth % size == 0:
            return size
    return 0


def _heads_packed(head_dim: int, kv_heads: int) -> int:
    """Key heads that share one block of 128 lanes: ``128 / D`` of a
    head narrower than the lanes where the key heads divide so, else 1."""
    pack = _LANES // head_dim if _LANES % head_dim == 0 else 1
    return pack if kv_heads % pack == 0 else 1


def fragment_head_tile(tokens, heads, kv_heads, head_dim, own=None,
                       selected: bool = False) -> int:
    """Query heads of one block of keys in a query tile: the most (a
    divisor of the group) that the backward pass can hold in VMEM, 0
    where not even one fits. Per row and lane ``q`` and ``dq`` in
    bfloat16 and ``o`` and ``do`` in float32, each twice for the
    pipeline, and the float32 ``dq`` accumulator (28 bytes), and three
    one-lane statistics that occupy whole 128-lane rows; beside the
    rows a head's float32 tiles of scores, weights and their gradient
    over the widest key block (the stored 512, or the fragment's ``own``
    keys: as many as its tokens, twice that in a noisy pass); under a
    selection (``selected``) the choice's stored and own blocks, a byte
    a (query, key) pair, each twice for the pipeline, and the wider one
    widened to 32 bits."""
    pack = _heads_packed(head_dim, kv_heads)
    group = pack * (heads // kv_heads)
    row = 28 * _ceil_to(head_dim * pack, _LANES) + 12 * _LANES
    own = own or tokens
    room = _FRAGMENT_VMEM_BYTES // 2 - 12 * tokens * max(own, _FRAGMENT_BLOCK_K)
    if selected:
        room -= tokens * (2 * (_FRAGMENT_BLOCK_K + own)
                          + 4 * max(own, _FRAGMENT_BLOCK_K))
    return next((tile for tile in range(group, 0, -1)
                 if group % tile == 0 and tile * tokens * row <= room), 0)


def fragment_kernel_applies(
        tokens, heads, kv_heads, head_dim, depth, dtype, own=None,
        selected: bool = False) -> bool:
    """The fragment kernel's lowering exists on a TPU
    (``ops/backend.is_tpu``) for bfloat16 operands, a fragment of
    whole 128-lane tiles of tokens (the own keys' episode
    numbers lie along the lanes, and a tile of weights is turned for the
    own keys' gradients), a cache of whole key blocks, a key that is
    whole lane tiles, packs into one (64: two key heads a block) or is
    the one key head's (its block is the cache's whole minor dimension:
    the latent row of 576, whole half tiles), and a query tile of at
    least one head (:func:`fragment_head_tile`). A call with a selection
    (``selected``: a learned index chose each query's rows,
    ``ops/cached_attention.Selection``) takes the same rule with the
    choice's blocks in the tile's room: the kernels take the choice as
    one more operand, a byte a (query, row) pair
    (:func:`fragment_attention`'s ``chosen``), and it counts under
    ``path="selected_kernel"``, where the rule says no under
    ``path="selected_xla"``."""
    pack = _heads_packed(head_dim, kv_heads)
    return (
        backend.is_tpu()
        and dtype == jnp.bfloat16
        and tokens % _LANES == 0
        and fragment_block_k(depth) > 0
        and (head_dim * pack % _LANES == 0
             or kv_heads == 1 and head_dim % (_LANES // 2) == 0)
        and fragment_head_tile(
            tokens, heads, kv_heads, head_dim, own, selected) > 0
    )


def _blocks_held(pos0, block_k: int, stored: int):
    """Of a stream's ``stored`` key blocks, those with a slot below its
    start position."""
    return jnp.minimum((pos0 + block_k - 1) // block_k, stored)


def fragment_key_blocks(pos0, depth: int, block_k: int | None = None):
    """``(skipped, all)`` key blocks of one key head of a fragment over
    the streams ``pos0`` ``(B,)``: a stream's stored blocks at or past
    its start position are skipped, the own block never."""
    bk = fragment_block_k(depth, block_k)
    stored = depth // bk
    held = _blocks_held(pos0, bk, stored)
    return jnp.sum(stored - held), pos0.shape[0] * (stored + 1)


def _stored_mask(pos0, seg_q, pos_q, first, block_k, depth, window):
    """``(T, block_k)``: which of the stored slots ``first ..`` each
    query sees. No window: the queries before the first reset see the
    slots below ``pos0``. A ring of ``depth`` slots: slot ``s`` holds
    position ``held = last - (last - s) mod depth`` (``last = pos0 -
    1``; nothing where that is negative), seen from less than
    ``window`` positions ahead. One comparison of a row of slots with a
    column of queries: the other conditions move the row or the column
    out of reach."""
    slot = first + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    if window is None:
        return slot < jnp.where(seg_q == 0, pos0, 0)
    last = pos0 - 1
    turn = jax.lax.rem(last + depth, depth)  # last mod depth; last >= -1
    top = last - turn
    held = slot + jnp.where(slot <= turn, top, top - depth)
    held = jnp.where(held >= 0, held, -_FAR)
    return held > jnp.where(seg_q == 0, pos_q - window, _FAR)


def _own_mask(seg_q, seg_k, window, block=1):
    """``(T, own keys)``: causal, same episode, inside the window.
    ``block`` (a power of two): causal by blocks of that many tokens, a
    key seen from its own block on
    (``ops/cached_attention.fragment_masks``). ``2 T`` own keys are a
    CLEAN pass's rows, then the queries' own pass's: of the first a
    query sees the strictly earlier blocks, of the second its own block
    (``ops/cached_attention.noisy_masks``)."""
    t, keys = seg_q.shape[0], seg_k.shape[1]
    row = jax.lax.broadcasted_iota(jnp.int32, (t, keys), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (t, keys), 1)
    if keys != t:
        # the clean rows below the first step of the query's block (all
        # of them among the first ``t`` columns), its own pass's from
        # there to the block's last step
        first, own = row & ~(block - 1), col - t
        return (seg_q == seg_k) & (
            (col < first) | ((own >= first) & (own <= (row | (block - 1)))))
    if block > 1:  # the last step of the query's block
        row = row | (block - 1)
    behind = row - col
    mask = (behind >= 0) & (seg_q == seg_k)
    if window is not None:
        mask = mask & (behind < window)
    return mask


def _split_choice(refs, selected):
    """``(the choice's stored and own blocks, the further references)``:
    under a selection the two lead the kernel's references after the
    masks' operands; without one there is none."""
    return (refs[:2], refs[2:]) if selected else ((None, None), refs)


def _chosen(mask, chosen_ref):
    """``mask`` ``(T, keys)`` and the choice's block of the same pairs
    (``(1, T, keys)`` int8, a byte a pair: Mosaic loads it packed four
    rows a sublane and widens it to the 32-bit layout of the scores'
    tiles, where a comparison makes it a mask); ``mask`` as it is
    without a selection."""
    if chosen_ref is None:
        return mask
    return mask & (chosen_ref[0].astype(jnp.int32) != 0)


def _each_head(heads, body, rolled, carry=None):
    """``carry = body(g, carry)`` for each query head ``g`` of a tile:
    unrolled, a copy of the body a head, or (``rolled``) one body under
    a loop. The kernels under a selection roll it: a program keeps a
    copy of both kernels a call site (three a layer), and eight heads
    unrolled were 2.1 MB of the learned-index cell's compiled program
    (61.9 MB for 59.8, compressed) for 16% of a layer's 13.0 ms on the
    chip, 1% of the cell's rate (PR 66). Without a selection the bodies
    are unrolled as they were: nine cells' programs hold them so."""
    if rolled:
        return jax.lax.fori_loop(0, heads, body, carry)
    for g in range(heads):
        carry = body(g, carry)
    return carry


def _fragment_fwd_kernel(
    pos0_ref, q_ref, kc_ref, vc_ref, k_ref, v_ref, seg_q_ref, pos_q_ref,
    seg_k_ref, *refs, window, depth, block_k, tiles, block, selected,
):
    """One stream, one key head, one tile of its query heads, one block
    of keys: the stored blocks in turn, then the fragment's own.
    ``q_ref`` ``(1, 1, heads of the tile, T, D)``; the running max, sum
    and accumulator of every query row live in scratch across the key
    blocks. ``selected``: a learned index's choice of each query's rows
    is ``&``-ed onto both masks (:func:`_split_choice`); every query has
    a chosen row (it sees its own key and the choice takes at least one
    of those seen), so the blocks a choice masks whole fold as the
    blocks the positions mask whole do."""
    (chosen_stored, chosen_own), (o_ref, lse_ref, m_ref, l_ref, acc_ref) = (
        _split_choice(refs, selected))
    b, kb = pl.program_id(0), pl.program_id(2 + (tiles > 1))
    stored = depth // block_k
    pos0 = pos0_ref[b]
    group = q_ref.shape[2]

    @pl.when(kb == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, _MASKED, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def fold(keys, values, mask):
        def head(g, _):
            s = jax.lax.dot_general(
                q_ref[0, 0, g], keys, _NT, preferred_element_type=jnp.float32)
            s = jnp.where(mask, s, _MASKED)
            m_prev = m_ref[g]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_ref[g] = alpha * l_ref[g] + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[g] = alpha * acc_ref[g] + jnp.dot(
                p.astype(values.dtype), values,
                preferred_element_type=jnp.float32)
            m_ref[g] = m_new

        _each_head(group, head, selected)

    # a stored block at or past the start position holds nothing any
    # query may see (in a ring too: the slots from ``pos0`` on are empty
    # until the ring has turned once)
    @pl.when((kb < stored) & (kb * block_k < pos0))
    def _():
        fold(kc_ref[0], vc_ref[0], _chosen(_stored_mask(
            pos0, seg_q_ref[0], pos_q_ref[0], kb * block_k, block_k, depth,
            window), chosen_stored))

    @pl.when(kb == stored)
    def _():
        fold(k_ref[0], v_ref[0], _chosen(
            _own_mask(seg_q_ref[0], seg_k_ref[0], window, block), chosen_own))

        def result(g, _):
            l = l_ref[g]
            o_ref[0, 0, g] = (acc_ref[g] / l).astype(o_ref.dtype)
            lse_ref[0, 0, g] = m_ref[g] + jnp.log(l)

        _each_head(group, result, selected)


def _fragment_bwd_kernel(
    pos0_ref, q_ref, kc_ref, vc_ref, k_ref, v_ref, seg_q_ref, pos_q_ref,
    seg_k_ref, *refs, window, depth, block_k, tiles, block, selected,
):
    """The same walk under the same masks; every score tile is computed
    again from the row statistics. ``dq`` gathers over all key blocks,
    the own keys' ``dk`` and ``dv`` are made in the last step (summed in
    the float32 ``own_acc`` where the key head's query heads come in
    several tiles); a stored row gets nothing."""
    (chosen_stored, chosen_own), refs = _split_choice(refs, selected)
    (o_ref, do_ref, lse_ref, dq_ref, dk_ref, dv_ref, dq_acc, delta_ref,
     *own_acc) = refs
    b, kb = pl.program_id(0), pl.program_id(2 + (tiles > 1))
    tile = pl.program_id(2) if tiles > 1 else 0
    stored = depth // block_k
    pos0 = pos0_ref[b]
    group = q_ref.shape[2]

    @pl.when(kb == 0)
    def _():
        dq_acc[...] = jnp.zeros(dq_acc.shape, jnp.float32)

        def delta(g, _):
            delta_ref[g] = jnp.sum(
                o_ref[0, 0, g] * do_ref[0, 0, g], axis=-1, keepdims=True)

        _each_head(group, delta, selected)

    def fold(keys, values, mask, own):
        def head(g, grads):
            dk, dv = grads
            q = q_ref[0, 0, g]
            do = do_ref[0, 0, g].astype(values.dtype)
            s = jax.lax.dot_general(
                q, keys, _NT, preferred_element_type=jnp.float32)
            p = jnp.exp(jnp.where(mask, s, _MASKED) - lse_ref[0, 0, g])
            dp = jax.lax.dot_general(
                do, values, _NT, preferred_element_type=jnp.float32)
            ds = p * (dp - delta_ref[g])
            dq_acc[g] += jnp.dot(
                ds.astype(keys.dtype), keys, preferred_element_type=jnp.float32)
            if own:
                dv_g = jnp.dot(
                    p.T.astype(do.dtype), do, preferred_element_type=jnp.float32)
                dk_g = jnp.dot(
                    ds.T.astype(q.dtype), q, preferred_element_type=jnp.float32)
                dv = dv_g if dv is None else dv + dv_g
                dk = dk_g if dk is None else dk + dk_g
            return dk, dv

        if selected and own:  # a loop's carry has a value from the start
            return _each_head(group, head, True, tuple(
                jnp.zeros(a.shape, jnp.float32) for a in (keys, values)))
        return _each_head(group, head, selected, (None, None))

    @pl.when((kb < stored) & (kb * block_k < pos0))
    def _():
        fold(kc_ref[0], vc_ref[0], _chosen(_stored_mask(
            pos0, seg_q_ref[0], pos_q_ref[0], kb * block_k, block_k, depth,
            window), chosen_stored), False)

    @pl.when(kb == stored)
    def _():
        dk, dv = fold(
            k_ref[0], v_ref[0], _chosen(
                _own_mask(seg_q_ref[0], seg_k_ref[0], window, block),
                chosen_own), True)
        if tiles > 1:
            dk_acc, dv_acc = own_acc

            @pl.when(tile == 0)
            def _():
                dk_acc[...] = dk
                dv_acc[...] = dv

            @pl.when(tile > 0)
            def _():
                dk_acc[...] += dk
                dv_acc[...] += dv

            # the own keys' block stays where it is until the last tile
            @pl.when(tile == tiles - 1)
            def _():
                dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
                dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)
        else:
            dk_ref[0] = dk.astype(dk_ref.dtype)
            dv_ref[0] = dv.astype(dv_ref.dtype)

        def result(g, _):
            dq_ref[0, 0, g] = dq_acc[g].astype(dq_ref.dtype)

        _each_head(group, result, selected)


def _fragment_call(kernel, operands, rows, outs, scratch, *, window, block_k,
                   tile, interpret, block, name):
    """One pass over the grid ``(streams, key heads, stored blocks +
    1)``, with an axis of ``group / tile`` query tiles before the blocks
    where a tile holds fewer query heads than the group. ``operands``:
    ``q`` ``(B, kv, group, T, D)``, the own ``k``, ``v`` ``(B, T, kv *
    D)``, the caches ``(B, depth, kv * D)``, ``pos0`` ``(B,)``, ``seg``,
    ``positions`` ``(B, T)``, ``chosen`` (``None``, or a choice's stored
    ``(B, T, depth)`` and own ``(B, T, own keys)`` parts, int8: the
    stored one blocked by the caches' index map, so that a skipped
    step fetches none of it, the own one whole; a block serves every
    key head and query tile of its stream); ``rows``: further operands
    blocked like ``q``; ``outs``: ``(shape, dtype)`` of each result,
    blocked like ``q`` at five axes and like the own keys at three."""
    from ray_tpu import sharding as sharding_lib

    q, k, v, k_cache, v_cache, pos0, seg, positions, chosen = operands
    bsz, kv, group, t, d = q.shape
    dv = v.shape[-1] // kv
    depth = k_cache.shape[1]
    stored = depth // block_k
    tiles = group // tile
    # the own keys: the fragment's, or a clean pass's and then its own
    own_keys = k.shape[1]
    seg_k = seg if own_keys == t else jnp.concatenate([seg, seg], axis=1)

    def step(ids):  # (stream, key head, query tile, key block, pos0) of a step
        b, n, *rest, kb, pos0 = ids
        return b, n, rest[0] if rest else 0, kb, pos0

    def held(ids):
        # past the last block a stream holds the index stays where it
        # is, so nothing is fetched for the steps that are skipped
        b, n, _, kb, pos0 = step(ids)
        last = jnp.maximum(_blocks_held(pos0[b], block_k, stored) - 1, 0)
        return b, jnp.minimum(kb, last), n

    def cached(width):
        return pl.BlockSpec((1, block_k, width), lambda *ids: held(ids))

    def heads(shape):
        def index(*ids):
            b, n, part, _, _ = step(ids)
            return b, n, part, 0, 0
        return pl.BlockSpec((1, 1, tile) + tuple(shape[3:]), index)

    def own(width):
        def index(*ids):
            b, n, _, _, _ = step(ids)
            return b, 0, n
        return pl.BlockSpec((1, own_keys, width), index)

    def per_stream(*shape):
        return pl.BlockSpec((1,) + shape, lambda *ids: (ids[0], 0, 0))

    choice = () if chosen is None else (
        pl.BlockSpec((1, t, block_k), lambda *ids: (ids[0], 0, held(ids)[1])),
        per_stream(t, own_keys))
    # inside a ``shard_map`` the results vary over the axes the operands do
    vma = sharding_lib.vma_of(operands)
    return pl.pallas_call(
        functools.partial(
            kernel, window=window, depth=depth, block_k=block_k, tiles=tiles,
            block=block, selected=chosen is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bsz, kv) + (tiles,) * (tiles > 1) + (stored + 1,),
            in_specs=[
                heads(q.shape), cached(d), cached(dv), own(d), own(dv),
                per_stream(t, 1), per_stream(t, 1), per_stream(1, own_keys),
                *choice, *(heads(r.shape) for r in rows),
            ],
            out_specs=[
                heads(shape) if len(shape) == 5 else own(shape[-1] // kv)
                for shape, _ in outs
            ],
            scratch_shapes=scratch,
        ),
        out_shape=[
            jax.ShapeDtypeStruct(shape, dtype, vma=vma) for shape, dtype in outs
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            # the own keys' gradients are summed over the query tiles
            dimension_semantics=("parallel", "parallel")
            + ("arbitrary",) * (1 + (tiles > 1)),
            vmem_limit_bytes=_FRAGMENT_VMEM_BYTES,
        ),
        name=name,
    )(pos0.astype(jnp.int32), q, k_cache, v_cache, k, v,
      seg[:, :, None], positions[:, :, None], seg_k[:, None, :],
      *(chosen or ()), *rows)


# A ``jit`` of their own, so that a program with many call sites (five
# layers, the forward pass, its recomputation and the backward pass, the
# standalone learn program and the fused one) traces and lowers the
# kernels once a shape.
_STATIC = ("window", "block_k", "tile", "interpret", "block")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _fragment_fwd(operands, *, window, block_k, tile, interpret, block):
    q, _, v = operands[:3]
    bsz, kv, group, t, _ = q.shape
    dv = v.shape[-1] // kv
    stat = lambda: pltpu.VMEM((tile, t, 1), jnp.float32)
    return _fragment_call(
        _fragment_fwd_kernel, operands, (),
        [((bsz, kv, group, t, dv), jnp.float32),
         ((bsz, kv, group, t, 1), jnp.float32)],
        [stat(), stat(), pltpu.VMEM((tile, t, dv), jnp.float32)],
        window=window, block_k=block_k, tile=tile, interpret=interpret,
        block=block, name="fragment_attention_fwd",
    )


@functools.partial(jax.jit, static_argnames=_STATIC)
def _fragment_bwd(operands, o, do, lse, *, window, block_k, tile, interpret,
                  block):
    q, k, v = operands[:3]
    kv, group, t, d = q.shape[1:]
    own_acc = [pltpu.VMEM((a.shape[1], a.shape[-1] // kv), jnp.float32)
               for a in (k, v)]
    return _fragment_call(
        _fragment_bwd_kernel, operands, (o, do, lse),
        [(q.shape, q.dtype), (k.shape, k.dtype), (v.shape, v.dtype)],
        [pltpu.VMEM((tile, t, d), jnp.float32),
         pltpu.VMEM((tile, t, 1), jnp.float32)] + own_acc * (tile < group),
        window=window, block_k=block_k, tile=tile, interpret=interpret,
        block=block, name="fragment_attention_bwd",
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10, 11, 12, 13))
def _fragment_attention(q, k, v, k_cache, v_cache, pos0, seg, positions,
                        chosen, window, block_k, tile, interpret, block):
    return _fragment_fwd(
        (q, k, v, k_cache, v_cache, pos0, seg, positions, chosen),
        window=window, block_k=block_k, tile=tile, interpret=interpret,
        block=block)[0]


def _fragment_fwd_rule(*args):
    operands, static = args[:9], dict(zip(_STATIC, args[9:]))
    o, lse = _fragment_fwd(operands, **static)
    return o, (operands, o, lse)


def _fragment_bwd_rule(window, block_k, tile, interpret, block, residuals, do):
    operands, o, lse = residuals
    dq, dk, dv = _fragment_bwd(
        operands, o, do, lse, window=window, block_k=block_k, tile=tile,
        interpret=interpret, block=block)
    # the stored rows are the rollout's, handed over as data: no
    # gradient (``None`` is a zero cotangent), nor for the integers and
    # the choice
    return (dq, dk, dv) + (None,) * 6


_fragment_attention.defvjp(_fragment_fwd_rule, _fragment_bwd_rule)


def fragment_attention(q, k, v, k_cache, v_cache, pos0, seg, positions, *,
                       window=None, block=1, clean=None, chosen=None,
                       block_k=None, head_tile=None, interpret=False):
    """A fragment's causal attention over its streams' stored keys and
    values and its own, as one tiled kernel with an online softmax in
    both directions: no ``(T, rows)`` matrix of scores or weights
    reaches HBM in the forward pass, its recomputation or the backward
    pass.

    ``q`` ``(B, T, kv, group, D)``, scaled already, in the products'
    type; ``k``, ``v`` ``(B, T, kv, D)`` the fragment's own (``v`` may
    be of another width than ``k``); ``k_cache``, ``v_cache`` ``(B,
    depth, kv * D)`` as the carry holds them BEFORE the fragment's
    scatter (where ONE key head's value is its key's leading ``Dv``
    lanes, as in a latent row, the key cache itself is the value cache:
    the value's block is those lanes of it, and no sliced copy is
    made); ``pos0`` ``(B,)`` the streams' start positions; ``seg``,
    ``positions`` ``(B, T)`` each query's episode number inside the
    fragment and position. Returns ``o`` ``(B, T, kv, group, Dv)``
    float32. Scores, masks, running max and sum and the accumulators
    are float32; the weights enter the value product in ``v``'s type
    and are normalised after it.

    The masks are ``ops/cached_attention.fragment_masks``'s, built in
    the kernel; stored blocks at or past ``pos0`` are skipped whole.
    ``block`` (a power of two, no ``window``) is that function's: the
    fragment's own keys are seen by blocks of that many tokens.
    ``clean``: ``(k, v)`` of a clean pass over the same fragment; the
    own keys are then those rows and the queries' own pass's, two blocks
    of one key operand under ``ops/cached_attention.noisy_masks``, and
    the gradient reaches the clean rows too.
    ``chosen``: a learned index's choice (``ops/cached_attention``'s
    ``select``) in two parts, ``(B, T, depth)`` and ``(B, T, own
    keys)``: which of the stored slots and which of the own keys each
    query attends to, of those the masks let it see (a query with no
    chosen row at all has no softmax). A byte a pair (int8: the
    narrowest type Mosaic loads on a v5e, widened in the kernel), not
    zero where chosen. It reaches both kernels as one more operand (a
    stream's stored part is blocked with its key blocks and skipped with
    them), is ``&``-ed onto the masks, and has no derivative. Without
    it the operands, the blocks and the kernels' bodies are the ones of
    a call that has none.

    Differentiable in ``q``, ``k`` and ``v``. The caches get NO
    gradient (zeros): they are the rollout's rows, handed over as data,
    and the learn lane truncates at the fragment's start; no caller
    differentiates through them (``sharding/superstep.py``,
    ``policy/jax_policy._grouped_loss_grad`` and ``perf/checks`` take
    gradients in the parameters, with the state a column of the batch).

    The query heads of a key head are one tile where the backward pass
    can hold them (:func:`fragment_head_tile`: every softmax layer of
    the cells), else several, each walking the stream's key blocks, with
    the own keys' gradients summed over them. ``block_k``, ``head_tile``
    and ``interpret`` are the tests' spellings."""
    bsz, t, kv, group, d = q.shape
    dv = v.shape[-1]
    depth = k_cache.shape[1]
    block_k = fragment_block_k(depth, block_k)
    if not block_k:
        raise ValueError(f"a cache of {depth} rows is not whole key blocks")
    if block & (block - 1) or block > 1 and window is not None:
        raise ValueError(f"no block rule of {block} tokens (window {window})")
    pack = _heads_packed(d, kv) if d == dv else 1
    if clean is not None:
        k = jnp.concatenate([clean[0].astype(k.dtype), k], axis=1)
        v = jnp.concatenate([clean[1].astype(v.dtype), v], axis=1)
    if chosen is not None:
        if [c.shape for c in chosen] != [(bsz, t, depth), (bsz, t, k.shape[1])]:
            raise ValueError(
                f"a choice of {[c.shape for c in chosen]} for {(bsz, t)} "
                f"queries over {depth} and {k.shape[1]} rows")
        chosen = tuple(c.astype(jnp.int8) for c in chosen)
    tile = head_tile or fragment_head_tile(
        t, kv * group, kv, d, k.shape[1], chosen is not None)
    if not tile or pack * group % tile:
        raise ValueError(
            f"no tile of the {pack * group} query heads of a key block fits")
    # a head narrower than the lanes: ``pack`` key heads a block, each
    # of their query heads zero outside its own head's lanes, so that
    # the products over the whole block are the head's own
    own_lanes = jnp.eye(pack).reshape(pack, 1, pack, 1)

    def spread(x):  # (B, T, kv, group, D) -> (B, kv', pack * group, T, pack * D)
        x = x.reshape(bsz, t, kv // pack, pack, group, 1, -1)
        if pack > 1:
            x = x * own_lanes.astype(x.dtype)
        return x.transpose(0, 2, 3, 4, 1, 5, 6).reshape(
            bsz, kv // pack, pack * group, t, -1)

    def gather(x):  # and back, each head from its own lanes
        x = x.reshape(bsz, kv // pack, pack, group, t, pack, -1)
        x = jnp.stack([x[:, :, a, :, :, a] for a in range(pack)], axis=2)
        return x.reshape(bsz, kv, group, t, -1).transpose(0, 3, 1, 2, 4)

    return gather(_fragment_attention(
        spread(q), k.reshape(bsz, -1, kv * d), v.reshape(bsz, -1, kv * dv),
        k_cache, v_cache, pos0, seg, positions, chosen, window, block_k, tile,
        interpret, block))


# -- one token over a stored cache (the sequence models' rollout form) ------
#
# ``ops/cached_attention.cached_attention``'s one-token form: a stream's
# query heads over the rows its cache holds, its own (written by the
# step's scatter) among them; a ring's are its leading slots as well,
# whatever positions they hold, and a softmax has no order. One grid step
# is one stream, which walks the key blocks it holds in a loop, all key
# heads of a block together; the query heads of a key head are the rows
# of one small tile. A stream's depth is a scalar-prefetch operand: the
# blocks past it are never fetched (nor is a grid step spent on them:
# as a BlockSpec pipeline over (streams, key blocks) the skipped steps
# of a static grid cost 0.25 us each and a working step 0.15 us beside
# its 1 MB, 502 us for the 401 of this form at 32 streams of 8,192 rows;
# one key head a grid step, 128 KB, was slower than the XLA text), and
# the mask inside the last held block comes from the slot numbers.
# ``ops/latent_attention``'s one-token form is the same walk over ONE
# cache: one key head that is the latent row as it lies, its value the
# row's leading lanes, read out of the key block's own buffer
# (:func:`_step_one_cache_kernel`, which says why it is a body of its own).

# the key and value blocks in flight and in use: two slots of each
_STEP_VMEM_BYTES = 16 * 2 ** 20
_SUBLANES = 8


# key blocks the one-cache kernel keeps in flight beside the one in use
# (on the chip, a latent layer of 32 streams 64 positions apart: 103 us
# with one, 90 with two, 89 with three)
_STEP_AHEAD = 2


def _tail_lanes(head_dim: int, value_dim: int) -> int:
    """Lanes of the block that holds a row's lanes past its value, whole
    lane tiles: 128 for the latent row's 64 roped numbers."""
    return _ceil_to(head_dim - value_dim, _LANES)


def step_kernel_applies(heads, kv_heads, head_dim, depth, dtype,
                        value_dim=None, selected: bool = False) -> bool:
    """Never for a call with a selection (``selected``: a learned index
    chose the query's rows): the kernel walks the CONTIGUOUS key blocks
    below a stream's depth, masks by position alone and fetches no row
    by number; such a call runs the one-token text over every slot under
    the selection's mask, counted under ``path="selected_xla"``.
    Otherwise the step kernel's lowering
    exists on a TPU
    (``ops/backend.is_tpu``) for bfloat16 operands, a cache of whole
    key blocks, a key
    that is whole lane tiles or packs into one (64: two key heads a
    block), and a block of all key heads that fits VMEM four times.
    With ``value_dim`` the rule is the ONE-CACHE form's (one key head
    whose value is its row's leading ``value_dim`` lanes: the latent
    row of 576 with its 512): a row of whole half lane tiles, a value of
    whole lane tiles that the row's further lanes follow as one block of
    whole tiles, and the blocks in flight with two streams' further
    lanes in VMEM."""
    block_k = fragment_block_k(depth)
    if selected or not (backend.is_tpu() and dtype == jnp.bfloat16 and block_k > 0
                        and heads % kv_heads == 0):
        return False
    if value_dim is not None:
        tail = _tail_lanes(head_dim, value_dim)
        return (
            kv_heads == 1
            and head_dim % (_LANES // 2) == 0
            and 0 < value_dim < head_dim
            and value_dim % _LANES == 0 and value_dim % tail == 0
            and 2 * ((_STEP_AHEAD + 1) * block_k * value_dim + 2 * depth * tail)
            <= _STEP_VMEM_BYTES
        )
    pack = _heads_packed(head_dim, kv_heads)
    return (
        head_dim * pack % _LANES == 0
        and 4 * block_k * kv_heads * head_dim * 2 <= _STEP_VMEM_BYTES
    )


def step_key_blocks(rows_held, depth: int, block_k: int | None = None):
    """``(skipped, all)`` key blocks of one key head of a step over the
    streams ``rows_held`` (any shape): the blocks with no slot below a
    stream's depth are skipped."""
    bk = fragment_block_k(depth, block_k)
    stored = depth // bk
    return (jnp.sum(stored - _blocks_held(rows_held, bk, stored)),
            rows_held.size * stored)


def _step_fold(s, mask, state, values_ref, values_at):
    """A block's scores ``s`` into a query tile's running ``(max, sum,
    accumulator)``: the online softmax's update, the weights into the
    value product in the values' type. The values are read where the
    product takes them: ``values_ref[values_at]``."""
    m_prev, l_prev, acc = state
    s = jnp.where(mask, s, _MASKED)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    return (
        m_new,
        alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True),
        alpha * acc + jnp.dot(
            p.astype(values_ref.dtype), values_ref[values_at],
            preferred_element_type=jnp.float32),
    )


def _step_kernel(held_ref, q_ref, kc_ref, vc_ref, o_ref, k_buf, v_buf, sem,
                 slot_ref, *, block_k):
    """One stream a grid step: its held key blocks in a loop, each
    fetched by the step before it (a stream's first by the last step of
    the stream before), so that no step is spent on a block that is
    skipped. ``kc_ref``, ``vc_ref`` the whole caches in HBM; ``k_buf``,
    ``v_buf`` ``(2, block_k, heads * lanes)``."""
    b = pl.program_id(0)
    heads, rows, lanes = q_ref.shape[1:]
    stored = kc_ref.shape[1] // block_k
    held = held_ref[b]
    # at least the first: the stream before has started its fetch
    blocks = jnp.maximum(_blocks_held(held, block_k, stored), 1)

    def copies(stream, kb, slot):
        at = pl.ds(pl.multiple_of(kb * block_k, block_k), block_k)
        return (
            pltpu.make_async_copy(
                kc_ref.at[stream, at], k_buf.at[slot], sem.at[0, slot]),
            pltpu.make_async_copy(
                vc_ref.at[stream, at], v_buf.at[slot], sem.at[1, slot]),
        )

    def start(stream, kb, slot):
        for copy in copies(stream, kb, slot):
            copy.start()

    @pl.when(b == 0)
    def _():
        slot_ref[0] = 0
        start(0, 0, 0)

    first = slot_ref[0]

    def block(kb, carry):
        slot = (first + kb) % 2

        @pl.when(kb + 1 < blocks)
        def _():
            start(b, kb + 1, 1 - slot)

        @pl.when((kb + 1 == blocks) & (b + 1 < pl.num_programs(0)))
        def _():
            start(b + 1, 0, 1 - slot)

        for copy in copies(b, kb, slot):
            copy.wait()
        mask = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1) < held
        out = []
        for n, state in enumerate(carry):
            at = pl.ds(n * lanes, lanes)
            s = jax.lax.dot_general(
                q_ref[0, n], k_buf[slot, :, at], _NT,
                preferred_element_type=jnp.float32)
            out.append(_step_fold(s, mask, state, v_buf, (slot, slice(None), at)))
        return tuple(out)

    init = (jnp.full((rows, 1), _MASKED, jnp.float32),
            jnp.zeros((rows, 1), jnp.float32),
            jnp.zeros((rows, lanes), jnp.float32))
    done = jax.lax.fori_loop(0, blocks, block, (init,) * heads)
    slot_ref[0] = (first + blocks) % 2
    for n, (_, l, acc) in enumerate(done):
        o_ref[0, n] = acc / l


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def _step_fwd(q, k_cache, v_cache, rows_held, *, block_k, interpret):
    """``q`` ``(B, key heads' lane blocks, rows, lanes)`` over the caches
    ``(B, depth, blocks * lanes)``, which stay in HBM: a grid step a
    stream. A ``jit`` of its own: traced once a shape, not once a
    layer."""
    from ray_tpu import sharding as sharding_lib

    bsz, kv, rows, lanes = q.shape
    of_stream = pl.BlockSpec((1, kv, rows, lanes), lambda b, held: (b, 0, 0, 0))
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    operands = (q, k_cache, v_cache, rows_held)
    return pl.pallas_call(
        functools.partial(_step_kernel, block_k=block_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bsz,),
            in_specs=[of_stream, in_hbm, in_hbm],
            out_specs=of_stream,
            scratch_shapes=[
                pltpu.VMEM((2, block_k, kv * lanes), k_cache.dtype),
                pltpu.VMEM((2, block_k, kv * lanes), v_cache.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(
            q.shape, jnp.float32, vma=sharding_lib.vma_of(operands)),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_FRAGMENT_VMEM_BYTES,
        ),
        name="step_attention",
    )(rows_held.astype(jnp.int32), q, k_cache, v_cache)


def _step_one_cache_kernel(first_ref, blocks_ref, stream_ref, block_ref,
                           held_ref, q_ref, tail_ref, kc_ref, o_ref, buf, sem,
                           *, block_k, tail):
    """One stream a grid step, for ONE key head whose value is its
    row's leading lanes (as many as ``o_ref``'s): :func:`_step_kernel`'s
    walk and arithmetic with another fetch. Mosaic refuses that kernel's
    copy of a block of a 576-lane cache (a slice of an HBM reference has
    to be whole lane tiles wide, even one of the whole minor dimension),
    so the value's lanes of a held block, whole tiles, come by the
    kernel's own copies and are the operand of both products, and the
    ``tail`` lanes after them come through the grid's pipeline, a whole
    stream's at a time, as ONE block of whole lane tiles that reaches
    past the row's end (``tail_ref`` ``(1, depth, tiles)``: lanes past
    ``tail`` hold nothing and are zeroed; all slots, 11% of a full
    cache's bytes). The copies run over the FLAT list of the (stream,
    block) pairs held (``stream_ref``, ``block_ref``; a stream's first
    entry ``first_ref[b]``, its ``blocks_ref[b]`` blocks), as many ahead
    as ``buf`` has slots but one, whatever stream the next ones are."""
    b, streams = pl.program_id(0), pl.num_programs(0)
    slots = buf.shape[0]
    width = o_ref.shape[-1]
    total = first_ref[streams - 1] + blocks_ref[streams - 1]
    held, first = held_ref[b], first_ref[b]

    def copy(i):
        at = pl.ds(pl.multiple_of(block_ref[i] * block_k, block_k), block_k)
        slot = i % slots
        return pltpu.make_async_copy(
            kc_ref.at[stream_ref[i], at, pl.ds(0, width)], buf.at[slot],
            sem.at[slot])

    @pl.when(b == 0)
    def _():
        for i in range(slots - 1):
            @pl.when(i < total)
            def _():
                copy(i).start()

    q = q_ref[0, 0, :, pl.ds(0, width)]
    q_tail = q_ref[0, 0, :, pl.ds(width, tail_ref.shape[-1])]
    in_row = jax.lax.broadcasted_iota(
        jnp.int32, (1, tail_ref.shape[-1]), 1) < tail

    def block(kb, state):
        i = first + kb

        @pl.when(i + slots - 1 < total)
        def _():
            copy(i + slots - 1).start()

        k_tail = tail_ref[0, pl.ds(pl.multiple_of(kb * block_k, block_k), block_k)]
        s = jax.lax.dot_general(
            q_tail, jnp.where(in_row, k_tail, jnp.zeros_like(k_tail)), _NT,
            preferred_element_type=jnp.float32)
        copy(i).wait()
        s = s + jax.lax.dot_general(
            q, buf[i % slots], _NT, preferred_element_type=jnp.float32)
        mask = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1) < held
        return _step_fold(s, mask, state, buf, (i % slots,))

    rows = q_ref.shape[2]
    init = (jnp.full((rows, 1), _MASKED, jnp.float32),
            jnp.zeros((rows, 1), jnp.float32),
            jnp.zeros((rows, width), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, blocks_ref[b], block, init)
    o_ref[0, 0] = acc / l


@functools.partial(
    jax.jit, static_argnames=("value_dim", "block_k", "interpret"))
def _step_one_cache_fwd(q, cache, rows_held, *, value_dim, block_k, interpret):
    """``q`` ``(B, 1, rows, D)`` over ``cache`` ``(B, depth, D)``, which
    the kernel gets twice: in HBM for its own copies of the value's
    lanes, and blocked for the lanes after them. The flat list of the
    key blocks held is made here."""
    from ray_tpu import sharding as sharding_lib

    bsz, _, rows, d = q.shape
    depth = cache.shape[1]
    stored = depth // block_k
    tail = _tail_lanes(d, value_dim)
    # at least a stream's first block: its own row is in it
    blocks = jnp.maximum(_blocks_held(rows_held, block_k, stored), 1).astype(jnp.int32)
    first = jnp.cumsum(blocks) - blocks
    # past the list's end the entries repeat its last, and are not fetched
    stream = jnp.repeat(
        jnp.arange(bsz, dtype=jnp.int32), blocks,
        total_repeat_length=bsz * stored + _STEP_AHEAD)
    block = jnp.minimum(
        jnp.arange(stream.shape[0], dtype=jnp.int32) - first[stream],
        blocks[stream] - 1)
    of_stream = lambda *shape: pl.BlockSpec(
        (1,) + shape, lambda b, *_: (b,) + (0,) * len(shape))
    return pl.pallas_call(
        functools.partial(
            _step_one_cache_kernel, block_k=block_k, tail=d - value_dim),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(bsz,),
            in_specs=[
                of_stream(1, rows, value_dim + tail),
                pl.BlockSpec((1, depth, tail),
                             lambda b, *_: (b, 0, value_dim // tail)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=of_stream(1, rows, value_dim),
            scratch_shapes=[
                pltpu.VMEM((_STEP_AHEAD + 1, block_k, value_dim), cache.dtype),
                pltpu.SemaphoreType.DMA((_STEP_AHEAD + 1,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(
            (bsz, 1, rows, value_dim), jnp.float32,
            vma=sharding_lib.vma_of((q, cache, rows_held))),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_FRAGMENT_VMEM_BYTES,
        ),
        name="step_attention_one_cache",
    )(first, blocks, stream, block, rows_held.astype(jnp.int32),
      _pad_to(q, 3, value_dim + tail), cache, cache)


def step_attention_text(q, k_cache, v_cache, see, value_dim=None):
    """One token's attention as XLA writes it, every slot under a mask:
    THE one-token text, which ``ops/cached_attention`` runs where no
    kernel's lowering exists and which is the step kernel's backward
    pass and oracle. ``q`` ``(B, 1, kv, group, D)`` scaled, in the
    products' type; the caches ``(B, depth, kv * D)`` after the step's
    scatter (no ``v_cache``: the ONE key head's first ``value_dim``
    lanes are its value); ``see`` ``(B, depth)`` the
    slots each stream's query sees (at full depth those below its rows
    held; in a ring by the positions they hold). Returns ``(B, 1, kv,
    group, D)`` float32, its parts under the scopes ``scores`` and
    ``out``."""
    kv, d = q.shape[2], q.shape[-1]
    kc = k_cache.reshape(k_cache.shape[:2] + (kv, d))
    vc = kc[..., :value_dim] if v_cache is None else v_cache.reshape(
        v_cache.shape[:2] + (kv, d))
    with jax.named_scope("scores"):
        s = jnp.einsum("btngd,bsnd->bngts", q, kc, preferred_element_type=jnp.float32)
        w = jax.nn.softmax(
            jnp.where(see[:, None, None, None], s, -jnp.inf), axis=-1).astype(q.dtype)
    with jax.named_scope("out"):
        return jnp.einsum("bngts,bsnd->btngd", w, vc,
                          preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _step_attention(q, k_cache, v_cache, rows_held, value_dim, block_k, interpret):
    bsz, _, kv, group, d = q.shape
    pack = _heads_packed(d, kv)
    rows = _ceil_to(pack * group, _SUBLANES)
    if v_cache is None:  # one key head: its query heads the tile's rows
        o = _step_one_cache_fwd(
            _pad_to(q[:, 0], 2, rows), k_cache, rows_held,
            value_dim=value_dim, block_k=block_k, interpret=interpret)
        return o[:, :, :group][:, None]
    # a head narrower than the lanes: ``pack`` key heads a block, each
    # of their query heads zero outside its own head's lanes, so that
    # the products over the whole block are the head's own
    x = q.reshape(bsz, kv // pack, pack, group, 1, d)
    if pack > 1:
        x = x * jnp.eye(pack, dtype=q.dtype).reshape(pack, 1, pack, 1)
    x = x.reshape(bsz, kv // pack, pack * group, pack * d)
    o = _step_fwd(
        _pad_to(x, 2, rows), k_cache, v_cache, rows_held,
        block_k=block_k, interpret=interpret)
    # and back, each head from its own lanes
    o = o[:, :, :pack * group].reshape(bsz, kv // pack, pack, group, pack, d)
    o = jnp.stack([o[:, :, a, :, a] for a in range(pack)], axis=2)
    return o.reshape(bsz, 1, kv, group, d)


def _step_fwd_rule(q, k_cache, v_cache, rows_held, *static):
    return (_step_attention(q, k_cache, v_cache, rows_held, *static),
            (q, k_cache, v_cache, rows_held))


def _step_bwd_rule(value_dim, block_k, interpret, residuals, do):
    *operands, rows_held = residuals
    see = jnp.arange(operands[1].shape[1])[None] < rows_held[:, None]
    _, vjp = jax.vjp(
        lambda *a: step_attention_text(*a, see, value_dim), *operands)
    return vjp(do) + (None,)


_step_attention.defvjp(_step_fwd_rule, _step_bwd_rule)


def step_attention(q, k_cache, v_cache, rows_held, *, value_dim=None,
                   block_k=None, interpret=False):
    """One token's attention over its stream's stored keys and values
    as one tiled kernel, forward only: only the key blocks with a slot
    below the stream's depth cross HBM (of a ring: below the rows it has
    written, all of them from its first turn on), a key block once a key
    head and the value block once.

    ``q`` ``(B, 1, kv, group, D)``, scaled already, in the products'
    type; ``k_cache``, ``v_cache`` ``(B, depth, kv * D)`` AFTER the
    step's scatter, so that the own key sits among the ``rows_held``
    ``(B,)`` leading slots a stream sees (at least one). Returns ``o``
    ``(B, 1, kv, group, D)`` float32. Scores, masks, running max and sum
    and the accumulator are float32; the weights enter the value product
    in the cache's type and are normalised after it.

    Where ONE key head's value is its key's leading ``value_dim`` lanes,
    as in a latent row (``ops/latent_attention.absorbed_step``: 32 query
    heads over rows of 576 lanes, the value their first 512), there is
    no ``v_cache`` (``None``): a held key block crosses HBM once and is
    the operand of both products, no second fetch and no sliced copy;
    ``o`` is then ``value_dim`` wide. The same walk and arithmetic in
    another kernel body (:func:`_step_one_cache_kernel`).

    Differentiable in ``q`` and the caches: the backward pass is
    :func:`step_attention_text`'s (rollout takes no gradient).
    ``block_k`` and ``interpret`` are the tests' spellings."""
    block_k = fragment_block_k(k_cache.shape[1], block_k)
    if not block_k:
        raise ValueError(
            f"a cache of {k_cache.shape[1]} rows is not whole key blocks")
    if (v_cache is None) != (value_dim is not None) or (
            v_cache is None and q.shape[2] != 1):
        raise ValueError(
            "a key's leading lanes are its value for ONE key head, with no "
            f"value cache and their number as value_dim ({value_dim})")
    return _step_attention(
        q, k_cache, v_cache, rows_held, value_dim, block_k, interpret)
