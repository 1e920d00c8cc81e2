"""The attention of a fragment alone on the chip: XLA text against kernel.

    chiprun -- env PYTHONPATH=. python benchmarks/profile_fragment_attention.py \
        [<case> ...] [<block_k> ...]

One attention layer's ``_cached_attention`` of each sequence cell as the
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
``W_kvb``. Prints one JSON line a case. TPU only: a time from another
backend is not a device time.
"""

from __future__ import annotations

import json
import sys
import time
import types

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import sequence_lm
from ray_tpu.models.sequence_lm import SequenceLM
from ray_tpu.ops import flash_attention, latent_attention

# streams of a group, tokens, key heads, group, head, depth, window, episode
CASES = {
    "smallthinker_full": (16, 256, 4, 7, 128, 8192, None, 8192),
    "smallthinker_ring": (16, 256, 4, 7, 128, 4096, 4096, 8192),
    "qwen3next": (16, 128, 2, 8, 256, 2048, None, 2048),
    "granite4h": (16, 256, 8, 4, 64, 2048, None, 2048),
}
# a group's streams, tokens, heads, nope, rope, latent, value head, depth
LATENT_CASES = {
    "xing4_latent": (8, 128, 32, 128, 64, 512, 128, 2048),
}
CALLS = 10


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


def report(name, text, kernel, args, w, parts, pos0, depth, blocks):
    """``text(*args)`` against ``kernel(block_k)(*args)``: a JSON line a
    key block size."""

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
        print(json.dumps({
            "case": name,
            "block_k": flash_attention.fragment_block_k(depth, block_k),
            "xla_fwd_ms": xla_fwd, "xla_fwd_remat_bwd_ms": xla_all,
            "kernel_fwd_ms": fwd, "kernel_fwd_remat_bwd_ms": in_all,
            **{f"rel_{part}": round(rel(a, b), 5)
               for part, a, b in zip(parts, got, want)},
            "key_blocks_skipped_share": round(float(skipped) / walked, 4),
        }), flush=True)


def run(name, b, t, kv, group, d, depth, window, episode, blocks):
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
    stub = types.SimpleNamespace(kv_heads=kv, dtype=bf)
    scale = d ** -0.5

    def text(q, k, v):
        return SequenceLM._cached_attention(
            stub, q, k, v, (kc, vc), ctx, scale, window=window,
            scope="swa" if window else None)[0]

    def kernel(block_k):
        def attention(q, k, v):
            qh = (q * scale).astype(bf).reshape(b, t, kv, group, d)
            return flash_attention.fragment_attention(
                qh, k.astype(bf), v.astype(bf), kc, vc, pos0, seg, positions,
                window=window, block_k=block_k,
            ).reshape(b, t, h, d)
        return attention

    report(name, text, kernel, (q, k, v), w, ("o", "dq", "dk", "dv"),
           pos0, depth, blocks)


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
            block=sequence_lm._LATENT_ENV_BLOCK)

    def kernel(block_k):
        def attention(q_nope, q_pe, rows_new, kv_b):
            return latent_attention.absorbed_fragment(
                q_nope, q_pe, rows_new, cache, kv_b, seg, positions, pos0,
                scale, bf, block_k=block_k)
        return attention

    report(name, text, kernel, (q_nope, q_pe, rows_new, kv_b), w,
           ("o", "dq_nope", "dq_pe", "drows", "dkv_b"), pos0, depth, blocks)


def main(argv):
    if jax.default_backend() != "tpu":
        raise SystemExit("a TPU is needed: a time from another backend is no device time")
    # the text is the rule's other branch
    flash_attention.fragment_kernel_applies = lambda *a: False
    blocks = [int(a) for a in argv if a.isdigit()] or [None]
    names = [a for a in argv if not a.isdigit()] or [*CASES, *LATENT_CASES]
    for name in names:
        if name in CASES:
            run(name, *CASES[name], blocks)
        else:
            run_latent(name, *LATENT_CASES[name], blocks)


if __name__ == "__main__":
    main(sys.argv[1:])
