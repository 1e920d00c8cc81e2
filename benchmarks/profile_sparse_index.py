"""The pieces of a learned index's choice alone on the chip, at the
Keye cell's shapes (16 streams, 16,384 slots, top 2,048; a fragment's
tile of 128 queries over 16,640 rows): ``lax.top_k`` against the
threshold by radix select, the gather of the rows ``lax.top_k`` names,
the scores. A call's dispatch is about 0.2 ms of every line: read the
differences, and a traced cell's table for what a step pays.

    chiprun -- env PYTHONPATH=. python benchmarks/profile_sparse_index.py
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops import sparse_index

B, S, K, T = 16, 16384, 2048, 128


def timed(name, fn, *args, reps=20):
    fn = jax.jit(fn)
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    print(f"{name:58s} {1e6 * (time.perf_counter() - t0) / reps:10.1f} us", flush=True)
    return out


def main():
    rng = np.random.default_rng(0)
    index = jnp.asarray(rng.standard_normal((B, S)), jnp.float32)
    depth = jnp.asarray(rng.integers(1, S, B), jnp.int32)
    seen = jnp.arange(S)[None] < depth[:, None]
    tile = jnp.asarray(rng.standard_normal((1, T, S + 256)), jnp.float32)
    tile_seen = jnp.ones((1, T, S + 256), bool)
    cache = jnp.asarray(rng.standard_normal((B, S, 512)), jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((B, 1, 16, 64)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((B, 1, 16)), jnp.float32)
    keys = jnp.asarray(rng.standard_normal((B, S, 64)), jnp.bfloat16)
    print(jax.devices()[0].device_kind)

    timed("scores (16, 1, 16 heads) x (16, 16384, 64)", sparse_index.scores, q, w, keys)
    timed("lax.top_k (16, 16384) k=2048",
          lambda i, s: jax.lax.top_k(jnp.where(s, i, -jnp.inf), K), index, seen)
    timed("kth_largest (16, 16384) k=2048",
          lambda i, s: sparse_index.kth_largest(jnp.where(s, i, -jnp.inf), K), index, seen)
    timed("select: the mask (16, 16384)",
          lambda i, s: sparse_index.select(i, s, K), index, seen)
    timed("cumsum int32 (16, 16384)",
          lambda s: jnp.cumsum(s, axis=-1, dtype=jnp.int32), seen)
    chosen = sparse_index.select(index, seen, K)
    want, slots = jax.lax.top_k(jnp.where(seen, index, -jnp.inf), K)
    same = all(
        set(np.flatnonzero(np.asarray(chosen)[b]))
        == set(np.asarray(slots)[b][np.asarray(want)[b] > -np.inf])
        for b in range(B))
    print("select chooses lax.top_k's rows:", same)
    timed("gather 2 x (16, 2048) rows of 512 bf16",
          lambda c, i: (jax.vmap(lambda x, j: jnp.take(x, j, axis=0))(c, i),
                        jax.vmap(lambda x, j: jnp.take(x, j, axis=0))(c + 1, i)),
          cache, slots)
    timed("lax.top_k (128, 16640) k=2048, the 2048th",
          lambda i: jax.lax.top_k(i, K)[0][..., -1:], tile)
    timed("kth_largest (128, 16640) k=2048",
          lambda i: sparse_index.kth_largest(i, K), tile)
    timed("select: the mask (128, 16640)",
          lambda i, s: sparse_index.select(i, s, K), tile, tile_seen)


if __name__ == "__main__":
    main()
