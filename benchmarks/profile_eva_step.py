"""The two-store one-token kernel alone on the chip, beside its sibling.

    chiprun -- bash benchmarks/chip/eva_step.sh [[<blocks a span>:]<spans ahead> ...]

``ops/eva_attention.step_attention`` at the EvaByte cell's sizes (16
streams at depths ``640 i + s``, 8 heads of 128, a window store of 2,048
rows and a summary store of 640, the cell's four layers' stores in turn:
one layer's alone could stay in fast memory from step to step), 64
scanned steps a call with ``s`` 10 apart so that a call walks the whole
fragment's depths, on the host's clock over 5 calls: microseconds a call
of the kernel (a layer and step), the GB/s over the bytes it fetches
(whole 128-row key blocks inside the two masks, keys and values) and
over the bytes it must move (``perf/eva_model.eva_step_bytes``, what
``eva.step_hbm_roofline_pct`` divides by), the trips of its walk a call,
and its distance from ``step_text``. A number on the command line is a
count of spans in flight beside the one in use (``_AHEAD``) to try,
``8:2`` that with a span's most blocks (``_SPAN_BLOCKS``) before it.
Then the yardstick: ``ops/flash_attention.step_attention`` at the Laguna
cell's tile (16 streams 256 apart in 4,096 slots, 8 key heads of 128, a
query tile of 6 rows padded to 8: the same ``f32[16,8,8,128]`` call),
four layers' caches in turn. One JSON line each. TPU only: a time from
another backend is not a device time.

``PYTHONPATH`` chooses the tree whose kernel runs: the script itself
uses nothing a tree before the spans lacks (``eva_step.sh`` runs it once
on ``.chip_check/parent`` where that is there, and once on the tree).
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops import eva_attention, flash_attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)  # perf/ is the checkout's that holds this script
from perf import eva_model, flops  # noqa: E402

STREAMS, HEADS, HEAD = 16, 8, 128
FRAGMENT, STEPS, STRIDE = 640, 64, 10  # depths 640 i + 10 j, j a step of the call
LAYERS = 4
CALLS = 5


def us_a_call(call, *args):
    """Microseconds a kernel call: ``call`` runs ``STEPS`` steps of
    ``LAYERS`` kernel calls under one ``lax.scan``."""
    for _ in range(2):
        jax.block_until_ready(call(*args))
    start = time.perf_counter()
    for _ in range(CALLS):
        out = call(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) * 1e6 / (CALLS * STEPS * LAYERS)


def run_eva(ahead):
    with open(os.path.join(ROOT, "perf", "configs", "evabyte_6_5b_ppo.json")) as f:
        config = json.load(f)
    window, chunk = int(config["window_size"]), int(config["chunk_size"])
    rows = eva_model.store_rows(config)
    bf = jnp.bfloat16
    if ahead is not None:
        if ":" in ahead:  # blocks of a span : spans in flight
            span, ahead = ahead.split(":")
            eva_attention._SPAN_BLOCKS = int(span)
        eva_attention._AHEAD = int(ahead)
        jax.clear_caches()
    keys = jax.random.split(jax.random.PRNGKey(0), 1 + 4 * LAYERS)
    q = jax.random.normal(keys[0], (STREAMS, HEADS, HEAD), jnp.float32) * HEAD ** -0.5
    stores = [
        tuple(jax.random.normal(
            keys[1 + 4 * n + leaf], (STREAMS, rows["window" if leaf < 2 else "summary"],
                                     HEADS * HEAD), bf) for leaf in range(4))
        for n in range(LAYERS)]
    starts = FRAGMENT * jnp.arange(STREAMS, dtype=jnp.int32)

    @jax.jit
    def call(q, stores):
        def step(total, j):
            positions = starts + STRIDE * j
            outs = [eva_attention.step_attention(
                (q + j.astype(jnp.float32) / STEPS).astype(bf), layer, positions,
                window=window, chunk=chunk) for layer in stores]
            return total + sum(outs), None  # every layer's output is used

        return jax.lax.scan(
            step, jnp.zeros(q.shape, jnp.float32), jnp.arange(STEPS, dtype=jnp.int32))[0]

    us = us_a_call(call, q, stores)
    at = jnp.asarray(np.asarray(starts)[None] + STRIDE * np.arange(STEPS)[:, None])
    seen = eva_attention.rows_seen(at, window, chunk)
    blocks = sum(int(jnp.sum(n)) for n in eva_attention.step_blocks(*seen)) / STEPS
    spans = getattr(eva_attention, "step_spans", None)  # a tree before them: a block a trip
    trips = sum(int(jnp.sum(n)) for n in spans(*seen)) / STEPS if spans else blocks
    fetched = blocks * eva_attention.STEP_BLOCK * eva_model.store_row_bytes(config)
    need = eva_model.eva_step_bytes(config, STREAMS)
    peak = flops.load_peaks(jax.devices()[0].device_kind)["hbm_bytes_per_s"]
    positions = starts + STRIDE * (STEPS // 2)
    got = eva_attention.step_attention(
        q.astype(bf), stores[0], positions, window=window, chunk=chunk)
    want = eva_attention.step_text(q.astype(bf), stores[0], positions, window, chunk)
    print(json.dumps({
        "kernel": "eva_step_attention", "tree": os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(eva_attention.__file__)))),
        "ahead": eva_attention._AHEAD,
        "span_blocks": getattr(eva_attention, "_SPAN_BLOCKS", 1),
        "us_a_call": round(us, 2), "trips_a_call": round(trips, 1),
        "us_a_trip": round(us / trips, 3),
        "mb_fetched": round(fetched / 1e6, 2), "mb_needed": round(need / 1e6, 2),
        "gb_per_s_fetched": round(fetched / us / 1e3, 1),
        "gb_per_s_needed": round(need / us / 1e3, 1),
        "roofline_pct_of_needed": round(100 * need / peak / (us * 1e-6), 1),
        "rel_o": round(float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want)), 5),
    }), flush=True)


def run_sibling():
    """``flash_attention.step_attention`` at the Laguna cell's tile."""
    kv, group, depth = 8, 6, 4096
    bf = jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(1), 1 + 2 * LAYERS)
    q = jax.random.normal(keys[0], (STREAMS, 1, kv, group, HEAD), jnp.float32) * HEAD ** -0.5
    caches = [tuple(jax.random.normal(keys[1 + 2 * n + leaf], (STREAMS, depth, kv * HEAD), bf)
                    for leaf in range(2)) for n in range(LAYERS)]
    held = jnp.arange(STREAMS, dtype=jnp.int32) * (depth // STREAMS) + depth // (2 * STREAMS) + 1

    @jax.jit
    def call(q, caches):
        def step(total, j):
            outs = [flash_attention.step_attention(
                (q + j.astype(jnp.float32) / STEPS).astype(bf), k, v, held)
                for k, v in caches]
            return total + sum(outs), None

        return jax.lax.scan(
            step, jnp.zeros(q.shape, jnp.float32), jnp.arange(STEPS, dtype=jnp.int32))[0]

    us = us_a_call(call, q, caches)
    bk = flash_attention.fragment_block_k(depth)
    skipped, every = flash_attention.step_key_blocks(held, depth)
    trips = every - int(skipped)
    fetched = trips * bk * 2 * 2 * kv * HEAD
    print(json.dumps({
        "kernel": "step_attention", "tile": "f32[16,8,8,128]", "block_k": bk,
        "us_a_call": round(us, 2), "trips_a_call": trips,
        "us_a_trip": round(us / trips, 3),
        "mb_fetched": round(fetched / 1e6, 2),
        "gb_per_s_fetched": round(fetched / us / 1e3, 1),
    }), flush=True)


def main(argv):
    if jax.default_backend() != "tpu":
        raise SystemExit("a TPU is needed: a time from another backend is no device time")
    for ahead in [a for a in argv if a[0].isdigit()] or [None]:
        run_eva(ahead)
    if "no_sibling" not in argv:
        run_sibling()


if __name__ == "__main__":
    main(sys.argv[1:])
