"""Causal softmax attention over a stored cache as ONE op
(:func:`cached_attention`), the way ``ops/ssd.ssd_step`` and
``ops/deltanet.gated_delta_step`` are: the cache's format, the masks,
the XLA text of both forms and the choice of a kernel live here, and a
model's layer sees none of them.

The cache. A stream's keys and values lie one row a position, ``(B,
depth, kv heads x head)`` in the products' type. At FULL DEPTH (no
``window``) position ``p`` lies in slot ``p`` and the slots below the
stream's position are its episode so far. With a ``window`` the cache
is a RING of ``depth = min(window, positions)`` slots, position ``p``
in slot ``p mod depth``: the row a stream at ``pos0`` holds in slot
``s`` is the one of the largest position below ``pos0`` that is ``s
mod depth`` (none where that is negative), and a query sees the rows
whose position is less than ``window`` behind its own. The masks come
from those positions, never from slot numbers.
"""

from __future__ import annotations

import functools
import itertools
import operator
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ray_tpu.ops import flash_attention, sparse_index
from ray_tpu.telemetry import metrics

# streams of a fragment whose attention scores are alive at once: at
# most 8, fewer (a power of two) where 8 streams' float32 scores of
# every head over the rows a block sees pass ``_ATTN_SCORE_BYTES`` (8 at
# 2,304 rows of 32 heads x 256 tokens; 2 at 8,448 rows of 28 x 256, 4 at
# 4,352)
_ATTN_SCORE_BYTES = 5 * 2 ** 27


def env_block(heads: int, tokens: int, rows: int) -> int:
    """Streams of a fragment whose float32 scores (every head, every
    token against ``rows`` keys) are alive at once."""
    fit = _ATTN_SCORE_BYTES // (4 * heads * tokens * rows)
    return min(8, 1 << max(0, int(fit).bit_length() - 1))


def query_tile(heads: int, tokens: int, rows: int) -> int:
    """Queries of ONE stream whose float32 scores (every head against
    ``rows`` keys) are alive at once: the fragment's, halved while they
    pass ``_ATTN_SCORE_BYTES`` (128 of 256 at 48 heads over 16,640
    rows)."""
    tile = tokens
    while tile % 2 == 0 and tile > 8 and 4 * heads * tile * rows > _ATTN_SCORE_BYTES:
        tile //= 2
    return tile


class Selection(NamedTuple):
    """A learned index's operands of one call (``ops/sparse_index``):
    the fragment's index queries ``q`` ``(B, T, heads, D)`` and keys
    ``k`` ``(B, T, D)`` in the products' type, the heads' weights ``w``
    ``(B, T, heads)`` float32, the stored index keys ``cache`` ``(B,
    depth, D)`` (a third cache leaf, one row a position, written by the
    same scatter as keys and values) and ``top_k``, the rows a query
    attends to."""

    q: jax.Array
    w: jax.Array
    k: jax.Array
    cache: jax.Array
    top_k: int


def scatter_rows(cache, new, rows, ring: bool = False):
    """``cache`` ``(B, depth, W)`` after a fragment whose tokens' rows
    are ``new`` ``(B, T, W)``: the last episode's tokens, each at its
    position (positions of one episode are distinct; earlier episodes'
    tokens are dropped); in a ``ring`` the last ``depth`` of them, each
    at its position mod ``depth``. ``rows``: the fragment's ``seg`` and
    ``positions`` ``(B, T)``."""
    seg, positions = rows["seg"], rows["positions"]
    depth = cache.shape[1]
    kept = seg == seg[:, -1:]
    if ring:
        kept = kept & (positions > positions[:, -1:] - depth)
        positions = positions % depth
    slot = jnp.where(kept, positions, depth)
    return cache.at[jnp.arange(cache.shape[0])[:, None], slot].set(
        new.astype(cache.dtype), mode="drop")


def fragment_masks(seg, pos0, positions, depth: int, window, block: int = 1):
    """``(see_old (B, T, depth), see (B, T, T))``: a stored key is seen
    by the tokens before the fragment's first reset, below the start
    position (in a ring: where the slot holds a row, less than
    ``window`` behind the query); the fragment's own causally, within an
    episode and the window. ``block``: the fragment's own by BLOCKS of
    that many tokens, a key of the query's block or an earlier one
    (block-causal; a fragment and an episode start on a block's first
    token, so a step's block is its position's)."""
    steps = jnp.arange(seg.shape[1])
    if block > 1:
        steps = steps // block
    see_old = (seg == 0)[:, :, None]
    if window is None:
        see_old = see_old & (jnp.arange(depth)[None, None] < pos0[:, None, None])
    else:
        # the position of the row in each slot (negative: none yet)
        last = pos0[:, None] - 1
        held = last - (last - jnp.arange(depth)[None]) % depth  # (B, depth)
        see_old = see_old & (held >= 0)[:, None] & (
            positions[:, :, None] - held[:, None] < window)
    see = (steps[:, None] >= steps[None, :])[None] & (
        seg[:, :, None] == seg[:, None, :])
    if window is not None:
        see = see & (steps[:, None] - steps[None, :] < window)[None]
    return see_old, see


def noisy_masks(seg, block: int):
    """``(see_clean, see_own)`` ``(B, T, T)`` of a block-diffusion noisy
    pass over a fragment: of the CLEAN pass's rows a query sees those of
    strictly earlier blocks of its episode, of its own pass's rows those
    of its own block (BD3-LM's block-causal and block-diagonal masks)."""
    blocks = jnp.arange(seg.shape[1]) // block
    same = seg[:, :, None] == seg[:, None, :]
    return (same & (blocks[:, None] > blocks[None, :])[None],
            same & (blocks[:, None] == blocks[None, :])[None])


def pairs_seen(see_old, see):
    """(query, key) pairs under the masks, a stream (exact in float32)."""
    return (jnp.sum(see_old, axis=(1, 2), dtype=jnp.float32)
            + jnp.sum(see, axis=(1, 2), dtype=jnp.float32))


def cached_attention(q, k, v, caches, rows, *, scale, window, dtype, scope,
                     block: int = 1, scatter: bool = True, select=None):
    """Attention of a fragment's ``q`` ``(B, T, heads, D)`` over the
    stored keys and values ``caches`` and the fragment's own ``k``, ``v``
    ``(B, T, kv heads, D)``; ``rows`` holds the fragment's ``seg``,
    ``positions`` ``(B, T)`` and ``pos0`` ``(B,)``. Returns ``(o (B, T,
    heads, D) float32, (keys, values) after the fragment, stats)``, its
    parts under ``scope``'s ``/scatter``, ``/scores`` and ``/out``.

    ``scatter=False``: ``caches`` are ANOTHER layer's, which wrote them
    (a layer that reads a cache it does not own): nothing is written and
    the caches come back as they are. In the one-token form they hold the
    step's own row already (the owner's caches AFTER its scatter) and
    ``k``, ``v`` are not read; in the fragment form they are the owner's
    stored rows and ``k``, ``v`` the owner's keys and values of the
    fragment, whose gradient is then summed over every reader.

    ``block`` is the mask's rule: a key is seen from its own block of
    that many positions and from every later one (1: causal). With it
    come two further things ``rows`` may hold. ``rows["step"]``: the
    ``T`` tokens are ONE BLOCK of a stream's generation, at ``pos0 ..
    pos0 + T - 1``: their rows are written first and every query reads
    the slots below ``pos0 + T``, which IS the mask inside a block; the
    one-token form is that with ``T == 1`` and needs no flag.
    ``rows["clean"]``: ``(k, v)`` of another pass over the same
    fragment (a block-diffusion update's clean pass); the queries then
    see, of THOSE rows, the strictly earlier blocks and, of their own,
    their own block (:func:`noisy_masks`).

    Which form runs where, each chosen by what the call sees in its
    input. A fragment (``T > 1``): ``flash_attention.fragment_attention``
    where ``fragment_kernel_applies`` says so (a TPU, bfloat16, whole
    blocks), window or none, else the XLA text a block of streams at a
    time (:func:`env_block`). One token, or one block:
    ``flash_attention.step_attention`` where ``step_kernel_applies`` says
    so, which fetches a stream's key blocks below its depth only (a
    block's ``T x group`` queries of a key head are the rows of its one
    query tile), else ``step_attention_text``. A ring's one token takes
    the same rule: the slots its query sees ARE the ``min(pos0 + 1,
    depth)`` leading ones (before the first turn slot ``s`` holds
    position ``s``, from it on every slot holds a row less than
    ``window`` behind), in whatever order the turns left them.
    ``ray_tpu_attention_{step,fragment}_lowerings_total{path}`` count the
    choice.

    ``select`` (a :class:`Selection`; a full-depth causal cache only):
    every query attends to the ``top_k`` rows its index scores highest
    among those the mask lets it see. The index keys are a THIRD cache,
    scattered with the other two and returned after them. Where ``top_k``
    reaches the cache's depth every row seen is chosen and the call IS
    the one without ``select`` (the same forms, the same kernels).
    Otherwise the choice is a MASK in both forms. One token: no kernel
    serves it (``step_kernel_applies`` answers no for a selection; the
    step counter's label is ``selected_xla``): the index
    scores of the stream's slots (``/index/scores``), their exact top-k
    as a mask over the slots (``/index/topk``) and the one-token text
    over every slot under it; nothing is gathered (``/select`` is the
    scope of a lowering that fetches the chosen rows alone, and holds
    nothing today). A fragment: first the choice, a stream and
    :func:`query_tile` queries at a time: the index scores
    over the stored rows and the fragment's own and the mask
    (``sparse_index.select``); then, where ``fragment_kernel_applies``
    says so (it counts the choice's blocks in the tile's room),
    ``flash_attention.fragment_attention`` with the choice as one more
    operand of both kernels (``path="selected_kernel"``), else the
    text's masked softmax under it a tile at a time
    (``path="selected_xla"``); no
    row is gathered (2,048 rows a query of a fragment would be a
    gigabyte a stream and layer). ``stats["index_rows_selected"]``: the
    rows each query attended to, ``(B, T)``; where ``rows["choices"]``
    asks for it, ``stats["index_choices"]``: the choice itself as a mask
    ``(B, T, slots)`` over the cache's slots (one token, whose own row is
    in its slot already) or over the slots and then the fragment's own
    rows.

    ``stats`` is what the choice alone knows: with a ``window`` the
    (query, key) ``pairs_seen``; for a fragment the key blocks its
    kernel skipped and walked (``attn_key_blocks_*``: a stream's stored
    blocks at or past its start position are skipped) and those the
    one-token kernel would at each of the fragment's positions
    (``attn_decode_key_blocks_*``), 0 of 0 where the text runs, which
    multiplies every slot."""
    k_cache, v_cache = caches
    b, t, h, d = q.shape
    hkv = k.shape[2]
    depth = k_cache.shape[1]
    seg, positions, pos0 = rows["seg"], rows["positions"], rows["pos0"]
    k, v = k.astype(dtype), v.astype(dtype)
    part = lambda name: jax.named_scope(f"{scope}/{name}")
    ring = window is not None
    step, clean = rows.get("step", t == 1), rows.get("clean")
    if ring and (block > 1 or t > 1 and step):
        raise ValueError("a ring cache has no block rule")
    if select is not None and (
            ring or block > 1 or clean is not None or not scatter or t > 1 and step):
        raise ValueError("a learned index selects among a causal cache's own rows")

    if scatter:
        with part("scatter"):
            new_k = scatter_rows(k_cache, k.reshape(b, t, hkv * d), rows, ring)
            new_v = scatter_rows(v_cache, v.reshape(b, t, hkv * d), rows, ring)
    else:
        new_k, new_v = k_cache, v_cache
    after = (new_k, new_v)
    stats = {}
    if select is not None:
        with part("scatter"):
            after += (scatter_rows(select.cache, select.k, rows),)
        if select.top_k >= depth:
            select = None  # every row seen is chosen: the forms below, as they are
            if rows.get("choices"):
                stats["index_choices"] = fragment_masks(
                    seg, pos0 + 1, None, depth, None)[0] if step else jnp.concatenate(
                        fragment_masks(seg, pos0, None, depth, None), axis=-1)

    qh = (q * scale).astype(dtype).reshape(b, t, hkv, h // hkv, d)
    # the kernels' rules are asked about a selection only where there is one
    asked = {} if select is None else {"selected": True}
    step_kernel = flash_attention.step_kernel_applies(h, hkv, d, depth, dtype, **asked)

    def attend(qe, ke, ve, kc, vc, sege, pos0e, pose=None, cleane=None):
        """One block of streams: the masked scores over the stored keys
        and the fragment's own in one softmax."""
        kc = kc.reshape(kc.shape[:2] + (hkv, d))
        vc = vc.reshape(vc.shape[:2] + (hkv, d))
        keys, values = [kc, ke], [vc, ve]
        with part("scores"):
            see_old, see = fragment_masks(sege, pos0e, pose, depth, window, block)
            masks = [see_old, see]
            if cleane is not None:
                keys.insert(1, cleane[0]), values.insert(1, cleane[1])
                masks[1:] = noisy_masks(sege, block)
            scores = [jnp.einsum(
                "btngd,bsnd->bngts", qe, x, preferred_element_type=jnp.float32)
                for x in keys]
            w = jax.nn.softmax(jnp.concatenate([
                jnp.where(m[:, None, None], s, -jnp.inf)
                for m, s in zip(masks, scores)], axis=-1), axis=-1)
            w = w.astype(dtype)
        with part("out"):
            ends = itertools.accumulate(x.shape[1] for x in values)
            out = functools.reduce(operator.add, [jnp.einsum(
                "bngts,bsnd->btngd", w[..., hi - x.shape[1]:hi], x,
                preferred_element_type=jnp.float32)
                for hi, x in zip(ends, values)])
        return (out, pairs_seen(see_old, see)) if ring else out

    if step:
        # decode reads the cache it has just written: the own keys sit
        # at slots pos0 .. pos0 + t - 1, so the stored range is t longer
        see = fragment_masks(seg[:, :1] if t > 1 else seg, pos0 + t, positions,
                             depth, window)[0][:, 0]
        if ring:
            stats["pairs_seen"] = jnp.sum(jnp.sum(see, axis=1, dtype=jnp.float32))
        # a block's queries of a key head as the rows of one tile
        qs = qh if t == 1 else qh.transpose(0, 2, 1, 3, 4).reshape(
            b, 1, hkv, t * (h // hkv), d)
        if select is not None:
            # every slot of the cache under the choice's mask: on the
            # v5e a gather of the chosen rows by slot number cost more
            # than the rows it saved (ops/sparse_index's docstring)
            metrics.inc_attention_step_lowering("selected_xla")
            with part("index/scores"):
                index = sparse_index.scores(select.q, select.w, after[2])[:, 0]
            with part("index/topk"):
                chosen = sparse_index.select(index, see, select.top_k)
            with jax.named_scope(scope):
                o = flash_attention.step_attention_text(qs, new_k, new_v, chosen)
            stats["index_rows_selected"] = jnp.sum(
                chosen, axis=1, dtype=jnp.float32)[:, None]
            if rows.get("choices"):
                stats["index_choices"] = chosen[:, None]
        elif step_kernel:
            # a full-depth cache is half unwritten at the mean, a ring
            # before its first turn: the tiled step kernel fetches a
            # stream's key blocks below its depth only
            metrics.inc_attention_step_lowering("kernel")
            held = jnp.minimum(pos0 + t, depth) if ring else pos0 + t
            with part("scores"):
                o = flash_attention.step_attention(qs, new_k, new_v, held)
        else:
            metrics.inc_attention_step_lowering("xla")
            with jax.named_scope(scope):
                o = flash_attention.step_attention_text(qs, new_k, new_v, see)
        if t > 1:
            o = o.reshape(b, hkv, t, h // hkv, d).transpose(0, 2, 1, 3, 4)
        return o.reshape(b, t, h, d), after, stats

    # the kernel's block rule is a bit mask: a power of two
    own = t if clean is None else 2 * t
    kernel = not block & (block - 1) and flash_attention.fragment_kernel_applies(
        t, h, hkv, d, depth, dtype, own, **asked)
    none = (jnp.int32(0), 0)
    for name, (skipped, walked) in (
            ("attn_key_blocks",
             flash_attention.fragment_key_blocks(pos0, depth) if kernel else none),
            ("attn_decode_key_blocks",
             flash_attention.step_key_blocks(positions + 1, depth)
             if step_kernel else none)):
        stats[name + "_skipped"] = skipped
        stats[name + "_walked"] = jnp.int32(walked)
    chosen = None
    if select is not None:
        # the attention's heads and the index's size the text's tiles; the
        # kernels take the choice in two parts, a byte a pair
        sized = h + select.q.shape[2]
        chosen = _choose_rows(sized, seg, pos0, select, part, split=kernel)
        stats["index_rows_selected"] = functools.reduce(jnp.add, [
            jnp.sum(c, axis=-1, dtype=jnp.float32)
            for c in (chosen if kernel else (chosen,))])
        if rows.get("choices"):
            stats["index_choices"] = (
                jnp.concatenate(chosen, axis=-1) != 0 if kernel else chosen)
    if kernel:
        # one tiled kernel, forward and backward: no score matrix is
        # written, and no block of streams is needed to hold one; a
        # choice is one more operand of both
        metrics.inc_attention_fragment_lowering(
            "kernel" if chosen is None else "selected_kernel")
        with part("scores"):
            o = flash_attention.fragment_attention(
                qh, k, v, k_cache, v_cache, pos0, seg, positions, window=window,
                block=block, clean=clean, chosen=chosen)
            if ring:  # the masks' arithmetic, reduced where it is built
                stats["pairs_seen"] = jnp.sum(pairs_seen(
                    *fragment_masks(seg, pos0, positions, depth, window)))
    elif chosen is not None:
        metrics.inc_attention_fragment_lowering("selected_xla")
        o = _selected_text(sized, qh, k, v, k_cache, v_cache, chosen, part)
    else:
        metrics.inc_attention_fragment_lowering("xla")
        nb = max(1, b // env_block(h, t, depth + own))
        if b % nb:
            nb = 1
        # a ring's masks need each query's position
        args = (qh, k, v, k_cache, v_cache, seg, pos0) + (
            (positions,) if ring or clean is not None else ())
        if clean is not None:
            args += (tuple(x.astype(dtype) for x in clean),)
        blocked = jax.tree_util.tree_map(
            lambda a: a.reshape((nb, b // nb) + a.shape[1:]), args)
        o = jax.lax.map(lambda xs: jax.checkpoint(attend)(*xs), blocked)
        o = jax.tree_util.tree_map(lambda a: a.reshape((b,) + a.shape[2:]), o)
        if ring:
            o, seen = o
            stats["pairs_seen"] = jnp.sum(seen)
    return o.reshape(b, t, h, d), after, stats


def _blocks_and_tiles(b, t, heads, rows):
    """How a fragment under a learned index is cut, so that ``heads``
    heads' float32 scores over ``rows`` keys fit :func:`env_block` and
    :func:`query_tile`: ``(blocked, whole, tiles, join)``. ``blocked``:
    arrays ``(B, ...)`` with the streams in blocks on a leading axis,
    ``whole`` its inverse; ``tiles``: a block's array ``(b, T, ...)``
    with the queries in tiles on a leading axis, ``join`` its inverse."""
    tile = query_tile(heads, t, rows)
    nb = max(1, b // env_block(heads, tile, rows))
    if b % nb:
        nb = 1
    blocked = lambda *args: jax.tree_util.tree_map(
        lambda a: a.reshape((nb, b // nb) + a.shape[1:]), args)
    whole = lambda a: a.reshape((b,) + a.shape[2:])
    tiles = lambda a: jnp.moveaxis(
        a.reshape((a.shape[0], t // tile, tile) + a.shape[2:]), 1, 0)
    join = lambda a: jnp.moveaxis(a, 0, 1).reshape((a.shape[1], t) + a.shape[3:])
    return blocked, whole, tiles, join


def _choose_rows(heads, seg, pos0, select, part, split=False):
    """A learned index's choice over a fragment, ``(B, T, depth + T)``
    bool: which of the stored slots and then of the fragment's own rows
    each query attends to. A block of streams and a tile of queries at
    a time (:func:`_blocks_and_tiles` for ``heads`` heads, the
    attention's and the index's), the index scores over the stored rows
    and the fragment's own and their exact top-k among the rows the
    masks let the query see. It has no derivative and is kept for the
    backward pass, a byte a (query, row) pair. ``split``: the stored
    slots' part and the own rows' apart and int8, as the fragment
    kernels take them (``flash_attention.fragment_attention``'s
    ``chosen``), written so by the fusion that makes the choice."""
    depth = select.cache.shape[1]
    blocked, whole, tiles, join = _blocks_and_tiles(
        *seg.shape, heads, depth + seg.shape[1])

    def choose(sege, pos0e, qi, wi, ki, ic):
        seen = jnp.concatenate(fragment_masks(sege, pos0e, None, depth, None), axis=-1)
        index_keys = jnp.concatenate([ic, ki], axis=1)

        def some_queries(xs):
            qit, wit, seent = xs
            with part("index/scores"):
                index = sparse_index.scores(qit, wit, index_keys)
            with part("index/topk"):
                choice = sparse_index.select(index, seent, select.top_k)
                if not split:
                    return choice
                return tuple(c.astype(jnp.int8)
                             for c in jnp.split(choice, [depth], axis=-1))

        return jax.tree_util.tree_map(join, jax.lax.map(
            some_queries, tuple(tiles(a) for a in (qi, wi, seen))))

    return jax.tree_util.tree_map(whole, jax.lax.map(
        lambda xs: choose(*xs),
        blocked(seg, pos0, select.q, select.w, select.k, select.cache)))


def _selected_text(heads, qh, k, v, k_cache, v_cache, chosen, part):
    """The fragment form under a choice (:func:`_choose_rows`) as XLA
    writes it, ``o (B, T, kv, group, D)`` float32: where no kernel's
    lowering exists, and the kernels' oracle. A block of streams and a
    tile of queries at a time as the choice was made, the masked softmax
    of the attention's scores over every stored row and the fragment's
    own under the choice, each block and tile recomputed in the backward
    pass as the text without an index is."""
    b, t, hkv, _, d = qh.shape
    depth, dtype = k_cache.shape[1], qh.dtype
    blocked, whole, tiles, join = _blocks_and_tiles(b, t, heads, depth + t)

    def attend(qe, ke, ve, kc, vc, chosene):
        kc = kc.reshape(kc.shape[:2] + (hkv, d))
        vc = vc.reshape(vc.shape[:2] + (hkv, d))

        def some_queries(qt, chosent):
            with part("scores"):
                s = jnp.concatenate([jnp.einsum(
                    "btngd,bsnd->bngts", qt, x, preferred_element_type=jnp.float32)
                    for x in (kc, ke)], axis=-1)
                w = jax.nn.softmax(
                    jnp.where(chosent[:, None, None], s, -jnp.inf), axis=-1).astype(dtype)
            with part("out"):
                return sum(jnp.einsum(
                    "bngts,bsnd->btngd", wx, x, preferred_element_type=jnp.float32)
                    for wx, x in ((w[..., :depth], vc), (w[..., depth:], ve)))

        return join(jax.lax.map(
            lambda xs: jax.checkpoint(some_queries)(*xs), (tiles(qe), tiles(chosene))))

    return whole(jax.lax.map(
        lambda xs: jax.checkpoint(attend)(*xs),
        blocked(qh, k, v, k_cache, v_cache, chosen)))
