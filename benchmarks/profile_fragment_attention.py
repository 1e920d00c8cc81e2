"""The attention of a fragment alone on the chip: XLA text against kernel.

    chiprun -- env PYTHONPATH=. python benchmarks/profile_fragment_attention.py \
        [<case> ...] [<block_k> ...]

One attention layer's ``cached_attention`` of each sequence cell as the
learn form runs it (a group of ``learn_streams`` streams, depths spread
evenly over the episode): the forward pass, and the forward pass with the
gradients of ``q``, ``k`` and ``v``, on the host's clock over 10 queued
calls, for the XLA text (the rule patched off) and for the kernel
(``ops/flash_attention.fragment_attention``), and the distance between
the two in the output and in each gradient. ``xing4_latent`` is the
latent layer's attention from the queries and the latent rows on
(``ops/latent_attention``): ``expanded_fragment``'s text, every stored
key rebuilt through ``W_kvb``, against ``absorbed_fragment`` on the
kernel, with the gradients of ``q_nope``, ``q_pe``, the own rows and
``W_kvb``. ``keye2_selected`` is the learned-index cell's layer (32
query heads over 4 key heads of 128, 16 streams over episodes of 8,192)
under a choice of 2,048 rows a query, made once by
``cached_attention._choose_rows`` from random index operands (scattered,
as seeded index weights choose) and handed to both sides as data: the
text under the choice (``_selected_text``) against the kernel with the
choice as its operand, and beside them the kernel WITHOUT a choice
(``kernel_no_choice_*``: what the operand costs). Prints one JSON line
a case. TPU only: a time from another backend is not a device time.

    ... benchmarks/profile_fragment_attention.py step [<case> ...] [<block_k> ...]

The ONE-TOKEN form of a softmax layer alone, as the rollout runs it (all
of a cell's streams, depths spread evenly over the episode, the caches
donated so that the step's scatter is in place): the text (every slot
under a mask) against ``ops/flash_attention.step_attention``,
microseconds a step over 5 calls of 64 scanned steps, and the GB/s of
each over the bytes it moves (the text all slots of both caches, the
kernel the key blocks its streams hold). The ``*_ring`` cases are the
three ring cells' window layers (SmallThinker's 4,096 slots with half
its streams in their first turn; Phi-4's and Laguna's 512, six rings in
turn, microseconds a ring). ``xing4_latent`` there is the
latent layer's one-token form from the queries and the step's own row on
(``ops/latent_attention.latent_attention``: the scatter, the absorbed
query, scores and weighted sum, ``W_kvb``'s value half) at 32 streams 64
positions apart over 2,048 slots of 576 lanes, five layers' caches in
turn: XLA's text (every slot, twice) against ``step_attention`` over the
one cache (the latent of the blocks held and every slot's 64 further
lanes as a lane tile), the bytes those of rows as HBM holds them (576
lanes padded to 640), microseconds a layer.

    JAX_PLATFORMS=cpu PYTHONPATH=. python benchmarks/profile_fragment_attention.py jaxpr

Needs no TPU: the sha256 of the fragment kernel pair's jaxpr (forward,
the checkpoint's recomputation and backward: bodies, index maps,
operands) WITHOUT a choice at every case above, and with a clean pass's
rows under a block rule of 4 (the block-diffusion cell's). Run it from two
checkouts (this file laid over the older one): equal hashes say that a
change of ``ops/flash_attention.py`` left the kernels of the cells
without a learned index as they were, where a program lowered on the CPU
holds the text and says nothing of them.
"""

from __future__ import annotations

import functools
import hashlib
import json
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops import cached_attention, flash_attention, latent_attention

# streams of a group, tokens, key heads, group, head, depth, window, episode
CASES = {
    "smallthinker_full": (16, 256, 4, 7, 128, 8192, None, 8192),
    "smallthinker_ring": (16, 256, 4, 7, 128, 4096, 4096, 8192),
    "qwen3next": (16, 128, 2, 8, 256, 2048, None, 2048),
    "granite4h": (16, 256, 8, 4, 64, 2048, None, 2048),
}
# the same, then the index's heads, head and rows a query
SELECTED_CASES = {
    "keye2_selected": (16, 256, 4, 8, 128, 8192, None, 8192, 16, 64, 2048),
}
# a group's streams, tokens, heads, nope, rope, latent, value head, depth
LATENT_CASES = {
    "xing4_latent": (8, 128, 32, 128, 64, 512, 128, 2048),
}
# a cell's streams, key heads, group, head, depth (= episode); a RING's
# further three: its window, the episode its streams are spread over and
# the rings stepped in turn (each with caches of its own: a 512-row ring
# alone stays in fast memory from step to step, which none does in its
# cell; the Phi-4 cell's differential pairs are key heads of 128 lanes)
STEP_CASES = {
    "smallthinker_full": (32, 4, 7, 128, 8192),
    "laguna_full": (16, 8, 6, 128, 4096),
    "qwen3next": (64, 2, 8, 256, 2048),
    "granite4h": (16, 8, 4, 64, 2048),
    "smallthinker_ring": (32, 4, 7, 128, 4096, 4096, 8192, 1),
    "phi4flash_ring": (16, 10, 4, 128, 512, 512, 8192, 6),
    "laguna_ring": (16, 8, 8, 128, 512, 512, 4096, 6),
}
# a cell's streams, heads, nope, rope, latent, value head, depth (= episode)
LATENT_STEP_CASES = {
    "xing4_latent": (32, 32, 128, 64, 512, 128, 2048),
}
CALLS = 10
STEP_CALLS = 5
STEPS = 64  # of one call
LAYERS = 5  # latent layers stepped in turn


def ms_per_call(fn, *args):
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    out = None
    for _ in range(CALLS):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) * 1e3 / CALLS


def rel(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def fragment_rows(b, t, episode):
    """Every stream at another place in its episode, one with a reset
    inside: ``pos0``, ``seg``, ``positions``."""
    pos0 = jnp.asarray(np.arange(b) * (episode // b), jnp.int32)
    fresh = np.zeros((b, t), bool)
    fresh[1, t // 2] = True
    seg = jnp.asarray(np.cumsum(fresh, 1), jnp.int32)
    steps = np.arange(t)[None]
    opened = np.maximum.accumulate(np.where(fresh, steps, -1), axis=1)
    positions = jnp.where(
        seg == 0, pos0[:, None] + steps, steps - opened).astype(jnp.int32)
    return pos0, seg, positions


def report(name, text, kernel, args, w, parts, pos0, depth, blocks, beside=None):
    """``text(*args)`` against ``kernel(block_k)(*args)``: a JSON line a
    key block size. ``beside``: ``{name: block_k -> fn}`` timed as the
    kernel is and not compared."""

    def measure(fn):
        """Milliseconds of the forward pass and of forward, recomputation
        and backward; the output and the gradients."""
        fwd = jax.jit(fn)
        both = jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(jax.checkpoint(fn)(*a) * w),
            argnums=tuple(range(len(args)))))
        times = (round(ms_per_call(fwd, *args), 3),
                 round(ms_per_call(both, *args), 3))
        return times, (fwd(*args),) + both(*args)[1]

    (xla_fwd, xla_all), want = measure(text)
    for block_k in blocks:
        (fwd, in_all), got = measure(kernel(block_k))
        skipped, walked = flash_attention.fragment_key_blocks(pos0, depth, block_k)
        others = {}
        for label, fn in (beside or {}).items():
            (others[f"{label}_fwd_ms"], others[f"{label}_fwd_remat_bwd_ms"]), _ = (
                measure(fn(block_k)))
        print(json.dumps({
            "case": name,
            "block_k": flash_attention.fragment_block_k(depth, block_k),
            "xla_fwd_ms": xla_fwd, "xla_fwd_remat_bwd_ms": xla_all,
            "kernel_fwd_ms": fwd, "kernel_fwd_remat_bwd_ms": in_all, **others,
            **{f"rel_{part}": round(rel(a, b), 5)
               for part, a, b in zip(parts, got, want)},
            "key_blocks_skipped_share": round(float(skipped) / walked, 4),
        }), flush=True)


def run(name, b, t, kv, group, d, depth, window, episode, *index, blocks):
    bf = jnp.bfloat16
    h = kv * group
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    q = jax.random.normal(keys[0], (b, t, h, d), jnp.float32)
    k = jax.random.normal(keys[1], (b, t, kv, d), jnp.float32)
    v = jax.random.normal(keys[2], (b, t, kv, d), jnp.float32)
    kc = jax.random.normal(keys[3], (b, depth, kv * d), bf)
    vc = jax.random.normal(keys[4], (b, depth, kv * d), bf)
    w = jax.random.normal(keys[5], (b, t, h, d), jnp.float32)
    pos0, seg, positions = fragment_rows(b, t, episode)
    ctx = {"seg": seg, "positions": positions, "pos0": pos0}
    scale = d ** -0.5
    heads_of = lambda q: (q * scale).astype(bf).reshape(b, t, kv, group, d)

    def text(q, k, v):
        return cached_attention.cached_attention(
            q, k, v, (kc, vc), ctx, scale=scale, window=window, dtype=bf,
            scope="swa" if window else "attn")[0]

    def kernel(block_k, **choice):
        def attention(q, k, v):
            return flash_attention.fragment_attention(
                heads_of(q), k.astype(bf), v.astype(bf), kc, vc, pos0, seg,
                positions, window=window, block_k=block_k, **choice,
            ).reshape(b, t, h, d)
        return attention

    beside = None
    if index:
        heads, width, top_k = index
        ik = jax.random.split(keys[5], 4)
        select = cached_attention.Selection(
            jax.random.normal(ik[0], (b, t, heads, width), bf),
            jax.random.uniform(ik[1], (b, t, heads), jnp.float32),
            jax.random.normal(ik[2], (b, t, width), bf),
            jax.random.normal(ik[3], (b, depth, width), bf), top_k)
        chosen = jax.jit(lambda: cached_attention._choose_rows(
            h + heads, seg, pos0, select, jax.named_scope))()

        def text(q, k, v):
            return cached_attention._selected_text(
                h + heads, heads_of(q), k.astype(bf), v.astype(bf), kc, vc,
                chosen, jax.named_scope).reshape(b, t, h, d)

        kernel, beside = functools.partial(
            kernel, chosen=(chosen[..., :depth], chosen[..., depth:])), {
                "kernel_no_choice": kernel}

    report(name, text, kernel, (q, k, v), w, ("o", "dq", "dk", "dv"),
           pos0, depth, blocks, beside)


def run_latent(name, b, t, h, dn, rope, latent, dv, depth, blocks):
    bf = jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    q_nope = jax.random.normal(keys[0], (b, t, h, dn), jnp.float32)
    q_pe = jax.random.normal(keys[1], (b, t, h, rope), jnp.float32)
    # normed latents and roped keys, of order one as the cache holds them
    rows_new = jax.random.normal(keys[2], (b, t, latent + rope), bf)
    cache = jax.random.normal(keys[3], (b, depth, latent + rope), bf)
    kv_b = jax.random.normal(keys[4], (latent, h * (dn + dv)), jnp.float32) * latent ** -0.5
    w = jax.random.normal(keys[5], (b, t, h, dv), jnp.float32)
    pos0, seg, positions = fragment_rows(b, t, depth)
    scale = (dn + rope) ** -0.5

    def text(q_nope, q_pe, rows_new, kv_b):
        return latent_attention.expanded_fragment(
            q_nope, q_pe, rows_new, cache, kv_b, seg, pos0, scale, bf,
            block=latent_attention._ENV_BLOCK)

    def kernel(block_k):
        def attention(q_nope, q_pe, rows_new, kv_b):
            return latent_attention.absorbed_fragment(
                q_nope, q_pe, rows_new, cache, kv_b, seg, positions, pos0,
                scale, bf, block_k=block_k)
        return attention

    report(name, text, kernel, (q_nope, q_pe, rows_new, kv_b), w,
           ("o", "dq_nope", "dq_pe", "drows", "dkv_b"), pos0, depth, blocks)


def us_a_step(applies, block_k, call, *state, layers=1):
    """Microseconds a step (and layer) and the last call's output of
    ``call(*state) -> (o, *state)``, ``STEPS`` steps a call under one
    ``lax.scan`` with the caches in its carry, as the rollout runs them
    (a call alone is the host's dispatch, 0.3 ms), the step kernel's
    rule ``applies`` and its key block patched in while the call is
    traced."""
    step_attention = flash_attention.step_attention
    rule = flash_attention.step_kernel_applies
    flash_attention.step_kernel_applies = applies
    flash_attention.step_attention = functools.partial(
        step_attention, block_k=block_k)
    try:
        for _ in range(2):
            o, *state = call(*state)
        jax.block_until_ready(o)
        start = time.perf_counter()
        for _ in range(STEP_CALLS):
            o, *state = call(*state)
        jax.block_until_ready(o)
        return (time.perf_counter() - start) * 1e6 / (
            STEP_CALLS * STEPS * layers), o
    finally:
        flash_attention.step_attention = step_attention
        flash_attention.step_kernel_applies = rule


def run_step(name, b, kv, group, d, depth, window=None, episode=None, layers=1,
             *, blocks):
    bf = jnp.bfloat16
    h = kv * group
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(keys[0], (b, 1, h, d), jnp.float32)
    k = jax.random.normal(keys[1], (b, 1, kv, d), jnp.float32)
    v = jax.random.normal(keys[2], (b, 1, kv, d), jnp.float32)
    # every stream half a fragment past its place in the episode (a ring
    # a window deep in an episode of two: half the streams have not
    # turned it yet)
    episode = episode or depth
    pos0 = jnp.asarray(
        np.arange(b) * (episode // b) + episode // (2 * b), jnp.int32)
    ctx = {"seg": jnp.zeros((b, 1), jnp.int32), "positions": pos0[:, None],
           "pos0": pos0}

    def measure(applies, block_k):
        @functools.partial(jax.jit, donate_argnums=(0,))
        def call(caches):
            def step(caches, i):
                outs = [cached_attention.cached_attention(
                    q + i, k, v, pair, ctx, scale=d ** -0.5, window=window,
                    dtype=bf, scope="attn")[:2] for pair in caches]
                # every layer's output is used: none is dead code
                return [pair for _, pair in outs], sum(o for o, _ in outs)

            caches, o = jax.lax.scan(
                step, caches, jnp.arange(STEPS, dtype=jnp.float32) / STEPS)
            return o[0], caches

        caches = [tuple(jax.random.normal(key, (b, depth, kv * d), bf)
                        for key in jax.random.split(pair))
                  for pair in jax.random.split(keys[3], layers)]
        return us_a_step(applies, block_k, call, caches, layers=layers)

    text_us, want = measure(lambda *a: False, None)
    row = 2 * 2 * kv * d  # bytes of a slot's key and value
    for block_k in blocks:
        us, got = measure(lambda *a: True, block_k)
        bk = flash_attention.fragment_block_k(depth, block_k)
        skipped, held = flash_attention.step_key_blocks(pos0 + 1, depth, block_k)
        moved = (held - int(skipped)) * bk * row
        print(json.dumps({
            "case": name, "form": "ring_step" if window else "step", "block_k": bk,
            "text_us": round(text_us, 1), "kernel_us": round(us, 1),
            "text_gb_per_s": round(b * depth * row / text_us / 1e3, 1),
            "kernel_gb_per_s": round(moved / us / 1e3, 1),
            "rel_o": round(rel(got, want), 5),
            "key_blocks_skipped_share": round(float(skipped) / held, 4),
        }), flush=True)


def run_latent_step(name, b, h, dn, rope, latent, dv, depth, blocks):
    bf = jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    q_nope = jax.random.normal(keys[0], (b, 1, h, dn), jnp.float32)
    q_pe = jax.random.normal(keys[1], (b, 1, h, rope), jnp.float32)
    row_new = jax.random.normal(keys[2], (b, 1, latent + rope), bf)
    kv_b = jax.random.normal(keys[3], (latent, h * (dn + dv)), jnp.float32) * latent ** -0.5
    pos0 = jnp.asarray(np.arange(b) * (depth // b), jnp.int32)  # 64 apart
    ctx = {"seg": jnp.zeros((b, 1), jnp.int32), "positions": pos0[:, None],
           "pos0": pos0}
    scale = (dn + rope) ** -0.5

    def measure(applies, block_k):
        @functools.partial(jax.jit, donate_argnums=(0,))
        def call(caches):
            def step(caches, i):
                outs = [latent_attention.latent_attention(
                    q_nope + i, q_pe, row_new, cache, kv_b, ctx, scale=scale,
                    dtype=bf)[:2] for cache in caches]
                # every layer's output is used: none is dead code
                return [cache for _, cache in outs], sum(o for o, _ in outs)

            caches, o = jax.lax.scan(
                step, caches, jnp.arange(STEPS, dtype=jnp.float32) / STEPS)
            return o[0], caches

        # the cell's five layers, each with a cache of its own: one alone
        # stays in fast memory from step to step (the text then reads it
        # at 2.4 TB/s, which no layer of the cell does)
        caches = [jax.random.normal(key, (b, depth, latent + rope), bf)
                  for key in jax.random.split(keys[4], LAYERS)]
        return us_a_step(applies, block_k, call, caches, layers=LAYERS)

    text_us, want = measure(lambda *a, **value: False, None)
    lanes = flash_attention._LANES
    row = 2 * flash_attention._ceil_to(latent + rope, lanes)  # a slot as HBM holds it
    for block_k in blocks:
        us, got = measure(lambda *a, **value: True, block_k)
        bk = flash_attention.fragment_block_k(depth, block_k)
        skipped, held = flash_attention.step_key_blocks(pos0 + 1, depth, block_k)
        # the latent of the blocks held, and every slot's lanes after it
        moved = (held - int(skipped)) * bk * 2 * latent + b * depth * 2 * (
            flash_attention._ceil_to(rope, lanes))
        print(json.dumps({
            "case": name, "form": "latent_step", "block_k": bk,
            "text_us": round(text_us, 1), "kernel_us": round(us, 1),
            "text_gb_per_s": round(2 * b * depth * row / text_us / 1e3, 1),
            "kernel_gb_per_s": round(moved / us / 1e3, 1),
            "kernel_mb_fetched": round(moved / 1e6, 2),
            "rel_o": round(rel(got, want), 5),
            "key_blocks_skipped_share": round(float(skipped) / held, 4),
        }), flush=True)


def jaxpr_hashes():
    bf, i32 = jnp.bfloat16, jnp.int32
    on = jax.ShapeDtypeStruct

    def line(name, attention, differentiated, fixed):
        """``attention(*differentiated, *fixed)``'s gradients under a
        checkpoint, traced for shapes alone."""
        n = len(differentiated)
        traced = jax.make_jaxpr(lambda *a: jax.grad(
            lambda *d: jnp.sum(jax.checkpoint(
                lambda *d: attention(*d, *a[n:]))(*d).astype(jnp.float32)),
            argnums=tuple(range(n)))(*a[:n]))(*differentiated, *fixed)
        # without addresses and the line numbers of the kernels' file
        text = re.sub(r"0x[0-9a-f]+|(?<=\.py):\d+", "", str(traced))
        print(name, hashlib.sha256(text.encode()).hexdigest()[:16], len(text))

    def rows(b, t):
        return on((b,), i32), on((b, t), i32), on((b, t), i32)

    for name, (b, t, kv, group, d, depth, window, _) in CASES.items():
        # a block rule has no window
        for label, block in ((name, 1), (name + "_clean_block4", 4))[
                :1 if window else 2]:
            def attention(q, k, v, kc, vc, pos0, seg, positions, block=block):
                return flash_attention.fragment_attention(
                    q, k, v, kc, vc, pos0, seg, positions, window=window,
                    block=block, clean=(k, v) if block > 1 else None)

            line(label, attention,
                 (on((b, t, kv, group, d), bf),) + (on((b, t, kv, d), bf),) * 2,
                 (on((b, depth, kv * d), bf),) * 2 + rows(b, t))
    for name, (b, t, h, dn, rope, latent, dv, depth) in LATENT_CASES.items():
        def attention(q_nope, q_pe, rows_new, kv_b, cache, pos0, seg, positions):
            return latent_attention.absorbed_fragment(
                q_nope, q_pe, rows_new, cache, kv_b, seg, positions, pos0,
                (dn + rope) ** -0.5, bf)

        line(name, attention,
             (on((b, t, h, dn), jnp.float32), on((b, t, h, rope), jnp.float32),
              on((b, t, latent + rope), bf),
              on((latent, h * (dn + dv)), jnp.float32)),
             (on((b, depth, latent + rope), bf),) + rows(b, t))


def main(argv):
    if argv[:1] == ["jaxpr"]:
        return jaxpr_hashes()
    if jax.default_backend() != "tpu":
        raise SystemExit("a TPU is needed: a time from another backend is no device time")
    if argv[:1] == ["step"]:
        blocks = [int(a) for a in argv if a.isdigit()] or [None]
        for name in [a for a in argv[1:] if not a.isdigit()] or [
                *STEP_CASES, *LATENT_STEP_CASES]:
            if name in STEP_CASES:
                run_step(name, *STEP_CASES[name], blocks=blocks)
            else:
                run_latent_step(name, *LATENT_STEP_CASES[name], blocks)
        return
    # the text is the rule's other branch
    flash_attention.fragment_kernel_applies = lambda *a, **selected: False
    blocks = [int(a) for a in argv if a.isdigit()] or [None]
    names = [a for a in argv if not a.isdigit()] or [
        *CASES, *SELECTED_CASES, *LATENT_CASES]
    for name in names:
        if name in CASES or name in SELECTED_CASES:
            run(name, *{**CASES, **SELECTED_CASES}[name], blocks=blocks)
        else:
            run_latent(name, *LATENT_CASES[name], blocks)


if __name__ == "__main__":
    main(sys.argv[1:])
