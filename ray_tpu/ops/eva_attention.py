"""EVA attention (Zheng, Yuan, Wang, Kong, "Efficient Attention via
Control Variates", ICLR 2023, arXiv:2302.04542) in the causal,
learned-proposal parameterisation of the EvaByte release, over TWO
stores on two clocks under ONE softmax (:func:`eva_attention`).

Per head, with learned ``phi``, ``mu`` ``(D,)``, window ``W`` and chunk
``c`` (``c`` divides ``W``); positions count from an episode's start:

- chunk ``j`` is the positions ``c j .. c j + c - 1``; when its last
  token is written it is SUMMARISED (:func:`summarise`): ``kbar_j = sum_i
  softmax_i(phi . k_i) k_i``, ``vbar_j = sum_i softmax_i(mu . k_i) v_i``
  over the chunk's rows (``k`` after RoPE): EVA's self-normalised
  random-feature estimate of the chunk's value with the proposal's mean
  ``mu`` in place of a sample, and the chunk's control-variate key;
- a query at ``t`` sees EXACTLY the rows of its own window, ``S_t = {i :
  i // W == t // W, i <= t}`` (:func:`window_visible`), and the
  summaries of every chunk of every EARLIER window, ``R_t = {j : c j + c
  - 1 < W (t // W)}`` (:func:`summary_visible`); a chunk of the query's
  own window is never read as a summary;
- ``o_t = softmax over S_t and R_t together of (s q_t . k_i | s q_t .
  kbar_j)`` times ``(v_i | vbar_j)``.

The stores, a stream: the WINDOW store, one row a token, ``min(W,
positions)`` rows, position ``p`` in slot ``p mod W`` (a buffer that its
mask empties at every window boundary: a slot past ``t mod W`` holds the
window before's row and is not seen; nothing is flushed), and the
SUMMARY store, one row a chunk, row ``j`` written at position ``c j + c -
1`` and seen from position ``W (j c // W + 1)`` on. Keys and values
apart: four leaves, in the products' type.

The forms. One token (``T == 1``): the row is written, the chunk it ends
(if it ends one: by predicate) pooled out of the window store and
written, and the scores run over both stores: where
:func:`step_kernel_applies` says so as ONE tiled kernel
(:func:`step_attention`) with one running max and sum that walks the
window store's key blocks up to ``t mod W`` and the summary store's up to
``(W / c) (t // W)``, up to eight consecutive blocks a copy and a trip,
and fetches no other block; elsewhere as XLA's text
over every slot under the two masks (:func:`step_text`: the CPU's path,
the kernel's oracle and its backward pass). A fragment (``T > 1``) from
stored start states: the text over the stored window rows, the stored
summaries, the fragment's own rows and the summaries of the chunks it
completes (one that began in the stored rows too), a block of streams at
a time; its backward reaches ``mu``, ``phi``, ``k`` and ``v`` through
the summaries made in the fragment; stored rows carry no gradient.
``ray_tpu_eva_lowerings_total{form}`` counts the choice (``step`` |
``kernel`` | ``fragment``).

Precision: scores and value products over both stores on ``dtype``
operands, accumulated in float32; masks and the joint softmax float32;
the pooling logits at precision highest and the two pooling softmaxes in
float32 (a summary is written once and read for the rest of the episode,
and the PPO ratio divides the fragment form by the step form).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import backend, cached_attention
from ray_tpu.ops.flash_attention import (
    _FRAGMENT_VMEM_BYTES, _LANES, _MASKED, _NT, _SUBLANES, _pad_to, _step_fold)
from ray_tpu.telemetry import metrics

HI = jax.lax.Precision.HIGHEST
# rows of one key block of either store: summaries become visible a
# window's worth (W / c = 128 at the published sizes) at a time
STEP_BLOCK = 128
# key blocks of one span at most: consecutive blocks of one store and one
# stream that one copy a leaf fetches and one trip of the kernel's walk
# folds; and the spans in flight beside the one in use (on the chip, the
# cell's 16 streams 640 apart, microseconds a call: a block a trip 155;
# spans of 2, 4, 8, 16 with three in flight 166, 134, 122, -; 8 with two
# and one 123, 141; 16 with two and one 137, 153)
_SPAN_BLOCKS = 8
_AHEAD = 3


# -- the two masks, in positions -------------------------------------------


def window_visible(key_pos, query_pos, window: int):
    """An exact row at ``key_pos`` (negative: none) from a query of its
    episode at ``query_pos``: of the query's own window, not after it."""
    return (key_pos >= 0) & (key_pos <= query_pos) & (
        key_pos // window == query_pos // window)


def summary_visible(chunk_end, query_pos, window: int):
    """The summary of the chunk whose last position is ``chunk_end``
    from a query of its episode at ``query_pos``: every chunk of every
    EARLIER window."""
    return chunk_end < window * (query_pos // window)


def rows_seen(positions, window: int, chunk: int):
    """``(window rows, summary rows)`` inside the two masks of a query
    at ``positions``, its own row among the first."""
    return positions % window + 1, (window // chunk) * (positions // window)


def summarise(k, v, phi, mu):
    """A chunk's two pooled rows. ``k``, ``v`` ``(..., c, H, D)`` (the
    chunk's rows, keys after RoPE), ``phi``, ``mu`` ``(H, D)``. Returns
    ``(kbar, vbar)`` ``(..., H, D)`` float32."""
    k, v = k.astype(jnp.float32), v.astype(jnp.float32)
    by_phi = jnp.einsum("...chd,hd->...ch", k, phi.astype(jnp.float32), precision=HI)
    by_mu = jnp.einsum("...chd,hd->...ch", k, mu.astype(jnp.float32), precision=HI)
    kbar = jnp.sum(jax.nn.softmax(by_phi, axis=-2)[..., None] * k, axis=-3)
    vbar = jnp.sum(jax.nn.softmax(by_mu, axis=-2)[..., None] * v, axis=-3)
    return kbar, vbar


# -- the one-token kernel --------------------------------------------------


def step_kernel_applies(heads, head_dim, window_rows, summary_rows, dtype) -> bool:
    """The two-store step kernel's lowering exists on a TPU for
    bfloat16 stores of whole key blocks and heads of one lane tile."""
    return (backend.is_tpu() and dtype == jnp.bfloat16 and head_dim == _LANES
            and window_rows % STEP_BLOCK == 0 and summary_rows % STEP_BLOCK == 0)


def step_blocks(window_rows_seen, summary_rows_seen, block: int = STEP_BLOCK):
    """Key blocks of each store with a row inside its mask: what a step
    fetches, and all it fetches."""
    return (-(-window_rows_seen // block)).astype(jnp.int32), (
        -(-summary_rows_seen // block)).astype(jnp.int32)


def step_key_blocks(positions, window: int, chunk: int, window_rows: int,
                    summary_rows: int, block: int = STEP_BLOCK):
    """``{store: (skipped, all)}`` key blocks of one-token steps at
    ``positions`` (any shape)."""
    seen = rows_seen(positions, window, chunk)
    out = {}
    for name, held, rows in zip(("window", "summary"), step_blocks(*seen, block),
                                (window_rows, summary_rows)):
        every = -(-rows // block)
        out[name] = (jnp.sum(every - held), positions.size * every)
    return out


def step_spans(window_rows_seen, summary_rows_seen, block: int = STEP_BLOCK):
    """``(window spans, summary spans)`` a step fetches: a store's key
    blocks inside its mask (:func:`step_blocks`) in runs of up to
    ``_SPAN_BLOCKS``, each run one copy a leaf and one trip of the
    kernel's walk."""
    return tuple(-(-held // _SPAN_BLOCKS)
                 for held in step_blocks(window_rows_seen, summary_rows_seen, block))


def step_fetches(positions, window: int, chunk: int, block: int = STEP_BLOCK):
    """``(copies, rows fetched a step)`` of one-token steps at
    ``positions`` (any shape): the spans of both stores over all steps
    (a span's keys and values counted once) and the mean rows of whole
    key blocks a step fetches, both stores."""
    seen = rows_seen(positions, window, chunk)
    copies = sum(jnp.sum(n) for n in step_spans(*seen, block))
    rows = block * sum(jnp.mean(n.astype(jnp.float32)) for n in step_blocks(*seen, block))
    return copies, rows


def _by_halves(index, branches, *operands):
    """``lax.switch`` as a tree of two-way branches: this lowering turns
    a switch into a CHAIN of as many nested branches as it has cases,
    and the chip's compiler falls over a chain of sixteen."""
    if len(branches) == 1:
        return branches[0](*operands)
    half = len(branches) // 2
    return jax.lax.cond(
        index < half,
        lambda *xs: _by_halves(index, branches[:half], *xs),
        lambda *xs: _by_halves(index - half, branches[half:], *xs),
        *operands)


def _step_kernel(first_ref, count_ref, stream_ref, code_ref, row_ref, seen_ref,
                 q_ref, wk_ref, wv_ref, sk_ref, sv_ref, o_ref, k_buf, v_buf, sem,
                 *, block):
    """One stream a grid step: ``ops/flash_attention._step_kernel``'s
    arithmetic (one running max, sum and accumulator a head) over the
    FLAT list of the SPANS inside the masks: a span is one to
    ``_SPAN_BLOCKS`` consecutive key blocks of one store and one stream
    (``stream_ref``; ``row_ref`` its first row; ``code_ref`` the store,
    0 the window store and 1 the summary store, times ``_SPAN_BLOCKS``
    plus its blocks less one; ``seen_ref`` the rows from its first that
    are inside the mask; a stream's first entry ``first_ref[b]``, its
    ``count_ref[b]`` entries, the window store's first), fetched by ONE
    copy a leaf and folded in ONE trip. A copy's size is static, so a
    trip takes the branch of its span's length: the copy, its wait and
    the fold all over exactly the span's rows, and no row of a slot
    that this trip's copy did not write is read. As many spans are in
    flight as the buffers have slots but one, whatever stream or store
    the next ones are of. The four stores stay in HBM; a block outside
    a mask is in no span and is not fetched."""
    b, streams = pl.program_id(0), pl.num_programs(0)
    slots = k_buf.shape[0]
    sizes = [n * block for n in range(1, _SPAN_BLOCKS + 1)]
    # a slot is as deep as the longest span the stores can give
    held_sizes = [size for size in sizes if size <= k_buf.shape[1]]
    heads, rows, lanes = q_ref.shape[1:]
    total = first_ref[streams - 1] + count_ref[streams - 1]

    def copies(i, keys, values, size):
        at = pl.ds(pl.multiple_of(row_ref[i], block), size)
        slot = i % slots
        return (
            pltpu.make_async_copy(
                keys.at[stream_ref[i], at], k_buf.at[slot, pl.ds(0, size)],
                sem.at[0, slot]),
            pltpu.make_async_copy(
                values.at[stream_ref[i], at], v_buf.at[slot, pl.ds(0, size)],
                sem.at[1, slot]),
        )

    def start(i):
        def begin(keys, values, size):
            if size <= keys.shape[1]:  # no span is longer than its store
                for copy in copies(i, keys, values, size):
                    copy.start()

        _by_halves(code_ref[i], [
            functools.partial(begin, keys, values, size)
            for keys, values in ((wk_ref, wv_ref), (sk_ref, sv_ref)) for size in sizes])

    @pl.when(b == 0)
    def _():
        for i in range(slots - 1):
            @pl.when(i < total)
            def _():
                start(i)

    first = first_ref[b]

    def fold(i, size, carry):
        # a wait reads the semaphore and the span's size, which the two
        # stores share
        for copy in copies(i, wk_ref, wv_ref, size):
            copy.wait()
        slot, held = i % slots, pl.ds(0, size)
        mask = jax.lax.broadcasted_iota(jnp.int32, (1, size), 1) < seen_ref[i]
        out = []
        for n, state in enumerate(carry):
            at = pl.ds(n * lanes, lanes)
            s = jax.lax.dot_general(
                q_ref[0, n], k_buf[slot, held, at], _NT,
                preferred_element_type=jnp.float32)
            out.append(_step_fold(s, mask, state, v_buf, (slot, held, at)))
        return tuple(out)

    def trip(e, carry):
        i = first + e

        @pl.when(i + slots - 1 < total)
        def _():
            start(i + slots - 1)

        return _by_halves(
            code_ref[i] % _SPAN_BLOCKS,
            [functools.partial(fold, i, size) for size in held_sizes], carry)

    init = (jnp.full((rows, 1), _MASKED, jnp.float32),
            jnp.zeros((rows, 1), jnp.float32),
            jnp.zeros((rows, lanes), jnp.float32))
    done = jax.lax.fori_loop(0, count_ref[b], trip, (init,) * heads)
    for n, (_, l, acc) in enumerate(done):
        o_ref[0, n] = acc / l


def _span_list(win_seen, sum_seen, depths, block):
    """The flat list :func:`_step_kernel` walks, ``_AHEAD`` entries
    longer than the most spans ``depths`` (the window store's rows, the
    summary store's) can give: ``(first, count)`` a stream and
    ``(stream, code, row, seen)`` an entry."""
    bsz = win_seen.shape[0]
    span = _SPAN_BLOCKS * block
    held = step_blocks(win_seen, sum_seen, block)
    in_window, in_summary = step_spans(win_seen, sum_seen, block)
    count = (in_window + in_summary).astype(jnp.int32)
    first = jnp.cumsum(count) - count
    # past the list's end the entries repeat its last, and are not fetched
    stream = jnp.repeat(
        jnp.arange(bsz, dtype=jnp.int32), count,
        total_repeat_length=bsz * sum(-(-rows // span) for rows in depths) + _AHEAD)
    entry = jnp.minimum(
        jnp.arange(stream.shape[0], dtype=jnp.int32) - first[stream],
        count[stream] - 1)
    store = (entry >= in_window[stream]).astype(jnp.int32)
    row = (entry - store * in_window[stream]) * span
    of_store = lambda pair: jnp.where(store == 0, pair[0][stream], pair[1][stream])
    blocks = jnp.minimum(of_store(held) - row // block, _SPAN_BLOCKS)
    seen = of_store((win_seen, sum_seen)).astype(jnp.int32) - row
    return (first, count), (stream, store * _SPAN_BLOCKS + blocks - 1, row, seen)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _step_fwd(q, stores, win_seen, sum_seen, *, block, interpret):
    """``q`` ``(B, H, rows, D)`` over the four stores ``(B, slots, H *
    D)``. The flat list of the spans inside the masks is made here."""
    from ray_tpu import sharding as sharding_lib

    bsz, heads, rows, lanes = q.shape
    of_stream, entries = _span_list(
        win_seen, sum_seen, [s.shape[1] for s in stores[::2]], block)
    per_stream = pl.BlockSpec((1, heads, rows, lanes), lambda b, *_: (b, 0, 0, 0))
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    # a slot holds the longest span the stores can give
    depth = min(_SPAN_BLOCKS * block, max(s.shape[1] for s in stores))
    slot = (_AHEAD + 1, depth, heads * lanes)
    return pl.pallas_call(
        functools.partial(_step_kernel, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(bsz,),
            in_specs=[per_stream] + [in_hbm] * 4,
            out_specs=per_stream,
            scratch_shapes=[
                pltpu.VMEM(slot, stores[0].dtype),
                pltpu.VMEM(slot, stores[1].dtype),
                pltpu.SemaphoreType.DMA((2, _AHEAD + 1)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(
            q.shape, jnp.float32,
            vma=sharding_lib.vma_of((q, win_seen, sum_seen) + tuple(stores))),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_FRAGMENT_VMEM_BYTES,
        ),
        name="eva_step_attention",
    )(*of_stream, *entries, q, *stores)


def step_text(q, stores, positions, window: int, chunk: int):
    """One token's attention over both stores as XLA writes it, every
    slot under its mask. ``q`` ``(B, H, D)`` scaled, in the products'
    type; ``stores`` the four leaves AFTER the step's writes;
    ``positions`` ``(B,)``. Returns ``(B, H, D)`` float32."""
    win_k, win_v, sum_k, sum_v = stores
    b, h, d = q.shape
    heads = lambda x: x.reshape(x.shape[:2] + (h, d))
    depth = win_k.shape[1]
    # the position of the row each slot holds: the latest one at or
    # below the query's that is the slot's mod the buffer's depth
    p = positions[:, None]
    held = p - (p - jnp.arange(depth)[None]) % depth
    ends = chunk * jnp.arange(sum_k.shape[1])[None] + chunk - 1
    masks = (window_visible(held, p, window),
             (ends <= p) & summary_visible(ends, p, window))
    with jax.named_scope("scores"):
        s = jnp.concatenate([
            jnp.where(m[:, None], jnp.einsum(
                "bhd,bshd->bhs", q, heads(x), preferred_element_type=jnp.float32),
                -jnp.inf)
            for m, x in zip(masks, (win_k, sum_k))], axis=-1)
        w = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    with jax.named_scope("out"):
        return sum(
            jnp.einsum("bhs,bshd->bhd", part, heads(x),
                       preferred_element_type=jnp.float32)
            for part, x in ((w[..., :depth], win_v), (w[..., depth:], sum_v)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _step_attention(q, stores, positions, window, chunk, block, interpret):
    rows = _SUBLANES  # a head's one query row, padded to a tile's
    seen = rows_seen(positions, window, chunk)
    o = _step_fwd(_pad_to(q[:, :, None], 2, rows), tuple(stores), *seen,
                  block=block, interpret=interpret)
    return o[:, :, 0]


def _step_fwd_rule(q, stores, positions, *static):
    return _step_attention(q, stores, positions, *static), (q, stores, positions)


def _step_bwd_rule(window, chunk, block, interpret, residuals, do):
    q, stores, positions = residuals
    _, vjp = jax.vjp(lambda q, s: step_text(q, s, positions, window, chunk), q, stores)
    return vjp(do) + (None,)


_step_attention.defvjp(_step_fwd_rule, _step_bwd_rule)


def step_attention(q, stores, positions, *, window: int, chunk: int,
                   block: int = STEP_BLOCK, interpret: bool = False):
    """:func:`step_text` as one tiled kernel, forward only (its backward
    pass is the text's; rollout takes no gradient): of the window store
    only the key blocks with a slot at or below ``t mod W`` cross HBM,
    of the summary store only those below ``(W / c) (t // W)``, each
    once, in spans of up to ``_SPAN_BLOCKS`` blocks.
    ``block`` and ``interpret`` are the tests' spellings."""
    if any(s.shape[1] % block for s in stores) or q.shape[-1] != _LANES:
        raise ValueError("stores of whole key blocks and heads of one lane tile")
    return _step_attention(q, tuple(stores), positions, window, chunk, block, interpret)


# -- the layer's one entry -------------------------------------------------


def _chunk_ends(rows, chunk: int):
    """The chunks a fragment completes, a stream: ``(token index of each
    chunk's last token (B, n), which of the n are chunks)``, ``n = T //
    chunk + 1``; a slot that is none points at the last token."""
    positions = rows["positions"]
    b, t = positions.shape
    n = t // chunk + 1
    is_end = positions % chunk == chunk - 1
    order = jnp.cumsum(is_end, axis=1) - 1
    ends = jnp.full((b, n), t, jnp.int32).at[
        jnp.arange(b)[:, None], jnp.where(is_end, order, n)
    ].set(jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t)), mode="drop")
    return jnp.minimum(ends, t - 1), ends < t


def eva_attention(q, k, v, phi, mu, state, rows, *, scale, window: int, chunk: int,
                  dtype, scope: str):
    """EVA attention of a fragment's ``q``, ``k``, ``v`` ``(B, T, H, D)``
    (after RoPE) from the stored ``state`` (window keys, window values,
    summary keys, summary values); ``rows`` holds the fragment's
    ``seg``, ``positions`` ``(B, T)`` and ``pos0`` ``(B,)``. Returns
    ``(o (B, T, H, D) float32, the four leaves after the fragment,
    stats)``, its parts under ``scope``'s ``/scatter``, ``/summarise``,
    ``/scores`` and ``/out``."""
    win_k, win_v, sum_k, sum_v = state
    b, t, h, d = q.shape
    depth, summaries = win_k.shape[1], sum_k.shape[1]
    seg, positions, pos0 = rows["seg"], rows["positions"], rows["pos0"]
    part = lambda name: jax.named_scope(f"{scope}/{name}")
    every = jnp.arange(b)
    k, v = k.astype(dtype), v.astype(dtype)
    qs = (q * scale).astype(dtype)

    with part("scatter"):
        new_win_k = cached_attention.scatter_rows(win_k, k.reshape(b, t, h * d), rows, True)
        new_win_v = cached_attention.scatter_rows(win_v, v.reshape(b, t, h * d), rows, True)

    stats = {
        "eva_fragments_crossing_a_window": jnp.sum(jnp.any(
            (positions[:, 1:] % window == 0) & (positions[:, 1:] > 0), axis=1
        ).astype(jnp.float32)),
        "eva_chunks_summarised": jnp.sum(
            (positions % chunk == chunk - 1).astype(jnp.float32)),
    }
    for store, (skipped, walked) in step_key_blocks(
            positions, window, chunk, depth, summaries).items():
        stats[f"eva_{store}_key_blocks_skipped"] = skipped
        stats[f"eva_{store}_key_blocks_walked"] = jnp.int32(walked)
    copies, fetched = step_fetches(positions, window, chunk)
    stats["eva_step_copies"] = copies.astype(jnp.float32)
    stats["eva_step_rows_fetched_mean"] = fetched

    if t == 1:
        p = positions[:, 0]
        with part("summarise"):
            # the chunk the token is in, out of the window store it was
            # just written to; a row of the summary store only where
            # the token is the chunk's last
            # (a slice a stream: one gather over the streams has the
            # compiler lay the whole store out anew, every step)
            first = p % depth - p % chunk
            rows_of = lambda x: jnp.concatenate([
                jax.lax.dynamic_slice(x, (n, first[n], 0), (1, chunk, h * d))
                for n in range(b)]).reshape(b, chunk, h, d)
            kbar, vbar = summarise(rows_of(new_win_k), rows_of(new_win_v), phi, mu)
            row = jnp.where(p % chunk == chunk - 1, p // chunk, summaries)
            new_sum_k = sum_k.at[every, row].set(
                kbar.reshape(b, h * d).astype(dtype), mode="drop")
            new_sum_v = sum_v.at[every, row].set(
                vbar.reshape(b, h * d).astype(dtype), mode="drop")
        new = (new_win_k, new_win_v, new_sum_k, new_sum_v)
        seen = rows_seen(p, window, chunk)
        stats["eva_window_rows_seen_mean"] = jnp.mean(seen[0].astype(jnp.float32))
        stats["eva_summary_rows_seen_mean"] = jnp.mean(seen[1].astype(jnp.float32))
        if step_kernel_applies(h, d, depth, summaries, dtype):
            metrics.inc_eva_lowering("kernel")
            with part("scores"):
                o = step_attention(qs[:, 0], new, p, window=window, chunk=chunk)
        else:
            metrics.inc_eva_lowering("step")
            with jax.named_scope(scope):
                o = step_text(qs[:, 0], new, p, window, chunk)
        return o[:, None], new, stats

    metrics.inc_eva_lowering("fragment")
    with part("summarise"):
        # a chunk's rows: the fragment's own and, of one that began in
        # the stored rows, the window store's last ``chunk - 1``
        back = pos0[:, None] - (chunk - 1) + jnp.arange(chunk - 1)[None]

        def with_stored(store, own):
            stored = jnp.take_along_axis(store, (back % depth)[..., None], axis=1)
            return jnp.concatenate([stored, own.reshape(b, t, h * d)], axis=1)

        ends, is_chunk = _chunk_ends(rows, chunk)
        at = (ends[:, :, None] + jnp.arange(chunk)[None, None]).reshape(b, -1, 1)
        rows_of = lambda x: jnp.take_along_axis(x, at, axis=1).reshape(
            b, -1, chunk, h, d)
        kbar, vbar = summarise(
            rows_of(with_stored(win_k, k)), rows_of(with_stored(win_v, v)), phi, mu)
        kbar, vbar = kbar.astype(dtype), vbar.astype(dtype)
        end_pos = jnp.take_along_axis(positions, ends, axis=1)
        end_seg = jnp.take_along_axis(seg, ends, axis=1)
        # the last episode's chunks, each in its row
        row = jnp.where(is_chunk & (end_seg == seg[:, -1:]), end_pos // chunk, summaries)
        new_sum_k = sum_k.at[every[:, None], row].set(
            kbar.reshape(b, -1, h * d), mode="drop")
        new_sum_v = sum_v.at[every[:, None], row].set(
            vbar.reshape(b, -1, h * d), mode="drop")

    def attend(qe, ke, ve, kbare, vbare, wk, wv, sk, sv, sege, pose, pos0e,
               end_pose, end_sege, is_chunke):
        """A block of streams: the masked scores over the stored window
        rows, the stored summaries, the fragment's own rows and its own
        summaries in one softmax."""
        heads = lambda x: x.reshape(x.shape[:2] + (h, d))
        with part("scores"):
            before = (sege == 0)[:, :, None]  # stored rows: the first episode's
            query = pose[:, :, None]
            last = pos0e[:, None] - 1
            held = last - (last - jnp.arange(depth)[None]) % depth  # (b, depth)
            stored_ends = chunk * jnp.arange(summaries)[None, None] + chunk - 1
            same = sege[:, :, None] == sege[:, None, :]
            masks = (
                before & window_visible(held[:, None], query, window),
                before & (stored_ends < pos0e[:, None, None] - pos0e[:, None, None] % chunk)
                & summary_visible(stored_ends, query, window),
                same & window_visible(pose[:, None, :], query, window),
                (is_chunke[:, None] & (sege[:, :, None] == end_sege[:, None])
                 & (end_pose[:, None] <= query)
                 & summary_visible(end_pose[:, None], query, window)),
            )
            keys = (heads(wk), heads(sk), ke, kbare)
            values = (heads(wv), heads(sv), ve, vbare)
            w = jax.nn.softmax(jnp.concatenate([
                jnp.where(m[:, None], jnp.einsum(
                    "bthd,bshd->bhts", qe, x, preferred_element_type=jnp.float32),
                    -jnp.inf)
                for m, x in zip(masks, keys)], axis=-1), axis=-1).astype(dtype)
        with part("out"):
            out, lo = 0.0, 0
            for x in values:
                out = out + jnp.einsum(
                    "bhts,bshd->bthd", w[..., lo:lo + x.shape[1]], x,
                    preferred_element_type=jnp.float32)
                lo += x.shape[1]
        count = lambda m: jnp.sum(m, axis=(1, 2), dtype=jnp.float32)
        return out, count(masks[0]) + count(masks[2]), count(masks[1]) + count(masks[3])

    keys_seen = depth + summaries + t + ends.shape[1]
    nb = max(1, b // cached_attention.env_block(h, t, keys_seen))
    if b % nb:
        nb = 1
    args = (qs, k, v, kbar, vbar, win_k, win_v, sum_k, sum_v, seg, positions, pos0,
            end_pos, end_seg, is_chunk)
    blocked = jax.tree_util.tree_map(
        lambda a: a.reshape((nb, b // nb) + a.shape[1:]), args)
    o, exact, pooled = jax.lax.map(lambda xs: jax.checkpoint(attend)(*xs), blocked)
    stats["eva_window_rows_seen_mean"] = jnp.sum(exact) / (b * t)
    stats["eva_summary_rows_seen_mean"] = jnp.sum(pooled) / (b * t)
    return (o.reshape(b, t, h, d), (new_win_k, new_win_v, new_sum_k, new_sum_v), stats)
