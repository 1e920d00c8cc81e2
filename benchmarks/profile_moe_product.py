"""The held experts' product alone on the chip: dense against grouped,
and the grouped form in ONE call over all the blocks' tokens.

    chiprun -- env PYTHONPATH=. python benchmarks/profile_moe_product.py

One expert layer of each sequence cell as the learn form runs it: four
blocks under ``lax.map`` of a checkpointed body (the Qwen3-Next cell's
blocks of 2,048 tokens, top-10 of 512 with 32 held; the Xing4 cell's
groups of 1,024, top-4 of 64 with 8 held), forward, recomputation and
backward for every operand with float32 master weights, on the host's
clock over 10 queued calls. Prints one JSON line a case: milliseconds a
layer for the dense form, for the grouped form, for the grouped form
with every token on ONE held expert (its way out: the dense form under
the ``cond``), and for ``one_call``: the same layer as the learn form
runs it since PR 51, the grouped form called ONCE over the ``BLOCKS x
t`` tokens under one checkpoint (no ``lax.map``: the feed-forward's half
of a block is outside the loop over groups of streams); the distance
between the forms' gradients, and the grouped form's and ``one_call``'s
largest device operations from a profiler trace.
TPU only: a time from another backend is not a device time.
"""

from __future__ import annotations

import collections
import glob
import json
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

from perf import program_trace
from ray_tpu.ops import moe

CASES = {
    "qwen3next_block": dict(
        t=2048, d=2048, f=512, e=512, k=10, held=32, scoring="softmax"),
    "xing4_group": dict(
        t=1024, d=3584, f=1024, e=64, k=4, held=8, scoring="sigmoid"),
}
BLOCKS = 4
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


def largest_ops(fn, *args, top=12):
    """``[[microseconds a call, count a call, name], ...]`` of one
    traced call's leaf operations on the device."""
    jax.block_until_ready(fn(*args))
    directory = tempfile.mkdtemp()
    with jax.profiler.trace(directory):
        jax.block_until_ready(fn(*args))
    path = glob.glob(directory + "/plugins/profile/*/*.xplane.pb")[0]
    total, count = collections.Counter(), collections.Counter()
    for tf_op, _, duration_ns, name in program_trace.load_op_scopes(path):
        kind = name.split(" ", 1)[-1]  # "%fusion.12 fusion f32[...]" -> "fusion f32[...]"
        if kind.startswith(("while", "conditional")):  # containers, not work
            continue
        key = kind + " | " + tf_op[-40:]
        total[key] += duration_ns / 1e3
        count[key] += 1
    return [[round(v, 1), count[k], k] for k, v in total.most_common(top)]


def layer(product, first=0, one_call=False):
    """``value_and_grad`` of one layer: ``lax.map`` over blocks of a
    checkpointed body, as ``SequenceLM.apply`` ran a block before PR 51;
    ``one_call``: the blocks' tokens one after another in ONE call of
    that body (same arguments, same gradients)."""
    def loss(x, wg, wu, wd, weights, indices, ct):
        body = jax.checkpoint(
            lambda xb, wb, ib: product(xb, wg, wu, wd, ib, wb, first))
        if one_call:
            flat = lambda a: a.reshape((-1,) + a.shape[2:])
            return jnp.sum(body(flat(x), flat(weights), flat(indices)) * flat(ct))
        return jnp.sum(jax.lax.map(lambda a: body(*a), (x, weights, indices)) * ct)

    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)))


def run(name, t, d, f, e, k, held, scoring):
    keys = jax.random.split(jax.random.PRNGKey(33), 6)
    x = jax.random.normal(keys[0], (BLOCKS, t, d), jnp.float32)
    router = jax.random.normal(keys[1], (d, e), jnp.float32) * d ** -0.5
    wg = jax.random.normal(keys[2], (held, d, f), jnp.float32) * d ** -0.5
    wu = jax.random.normal(keys[3], (held, d, f), jnp.float32) * d ** -0.5
    wd = jax.random.normal(keys[4], (held, f, d), jnp.float32) * f ** -0.5
    ct = jax.random.normal(keys[5], x.shape, jnp.float32)
    indices, weights = jax.jit(jax.vmap(
        lambda x: moe.route(x, router, k, True, scoring=scoring)[:2]))(x)
    one_expert = jnp.broadcast_to(
        jnp.arange(held - 1, held - 1 + k, dtype=jnp.int32), indices.shape)

    def dense(x, wg, wu, wd, i, w, first):
        combine = moe.held_combine_weights(i, w, first, held)
        return moe.dense_experts_product(x, wg, wu, wd, combine)

    def grouped(x, wg, wu, wd, i, w, first):
        per_expert, _ = moe.expert_load(i, first, held)
        return moe.grouped_experts_product(x, wg, wu, wd, i, w, per_expert, first, e)

    dense, one_call, grouped = layer(dense), layer(grouped, one_call=True), layer(grouped)
    args = (x, wg, wu, wd, weights)
    (_, ga), (_, gb) = dense(*args, indices, ct), grouped(*args, indices, ct)
    _, gc = one_call(*args, indices, ct)
    buffer = moe.expert_buffer_rows(t, k, e)
    rel = lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(a))
    names = ("x", "w_gate", "w_up", "w_down", "weights")
    load = jax.vmap(lambda i: moe.expert_load(i, 0, held)[0])(indices)
    print(json.dumps({
        "case": name, "device": jax.devices()[0].device_kind,
        "lowering": moe.product_lowering(t, k, e),
        "rows_dense": t * held, "rows_grouped": held * buffer,
        "rows_one_call": held * moe.expert_buffer_rows(BLOCKS * t, k, e),
        "largest_load_of_an_expert": float(load.max()), "buffer": buffer,
        "dense_ms_per_layer": ms_per_call(dense, *args, indices, ct),
        "grouped_ms_per_layer": ms_per_call(grouped, *args, indices, ct),
        "grouped_one_expert_ms_per_layer": ms_per_call(grouped, *args, one_expert, ct),
        "one_call_ms_per_layer": ms_per_call(one_call, *args, indices, ct),
        "grad_rel_l2": {n: rel(a, b) for n, a, b in zip(names, ga, gb)},
        "one_call_grad_rel_l2": {n: rel(a, b) for n, a, b in zip(names, ga, gc)},
        "grouped_largest_ops_us": largest_ops(grouped, *args, indices, ct),
        "one_call_largest_ops_us": largest_ops(one_call, *args, indices, ct),
    }), flush=True)


if __name__ == "__main__":
    if jax.default_backend() != "tpu":
        sys.exit("profile_moe_product: needs a TPU, found " + jax.default_backend())
    for case, shape in CASES.items():
        run(case, **shape)
