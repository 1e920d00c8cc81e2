"""The one-token state-space step alone on the chip: kernel against body.

    chiprun -- env PYTHONPATH=. python benchmarks/profile_ssd_step.py [heads ...]

A run of stacked layers as the granite cell's decode carries it (16
streams, 5 and 4 layers of 64 heads of ``(64, 128)``), 64 steps of
every layer in one donated program (a scan over steps around a scan
over layers, the inputs new each step), on the host's clock over 5
queued calls. Prints one JSON line a case: microseconds a layer and
step for the ``jax.numpy`` body on the layer's slice (the lowering
elsewhere) and for the Pallas kernel at each block of heads named on
the command line (default 16 32 64; ``ops/ssd._KERNEL_HEADS`` is the
one the program uses), the distance between the two lowerings' state
and output after the 64 steps, and the largest device operations of
each from a profiler trace. TPU only: a time from another backend is
not a device time.
"""

from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp

from benchmarks.profile_moe_product import largest_ops
from ray_tpu.ops import ssd

STREAMS, HEADS, HEAD, STATE = 16, 64, 64, 128
STEPS, CALLS = 64, 5


def chain(step):
    """``(leaf, inputs) -> (leaf, outputs)``: ``STEPS`` steps of every
    layer of the leaf, each layer's ``x`` the sum of the step's and the
    layer before's output, as a residual stream would feed it."""
    def steps(leaf, a, xs):
        layers = jnp.arange(leaf.shape[1])

        def one_step(leaf, inputs):
            x, dt, b, c = inputs

            def one_layer(carry, layer):
                leaf, y = carry
                return step(leaf, x + 0.1 * y, dt, a, b, c, layer), None

            (leaf, y), _ = jax.lax.scan(one_layer, (leaf, jnp.zeros_like(x)), layers)
            return leaf, y

        return jax.lax.scan(one_step, leaf, xs)

    return jax.jit(steps, donate_argnums=(0,))


def body(leaf, x, dt, a, b, c, layer):
    return ssd._stacked_step_body(leaf, layer, x, dt, a, b, c)


def kernel(leaf, x, dt, a, b, c, layer):
    return ssd.ssd_step_kernel(leaf, layer, x, dt, a, b, c)


def ms_per_call(fn, leaf, *args):
    leaf, _ = fn(leaf, *args)
    start = time.perf_counter()
    for _ in range(CALLS):
        leaf, out = fn(leaf, *args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) * 1e3 / CALLS


def run(layers, blocks):
    keys = jax.random.split(jax.random.PRNGKey(35), 6)
    normal = lambda k, *shape: jax.random.normal(keys[k], shape, jnp.float32)
    a = -jnp.exp(normal(0, HEADS) * 0.5)
    xs = (
        normal(1, STEPS, STREAMS, HEADS, HEAD),
        jax.nn.softplus(normal(2, STEPS, STREAMS, HEADS) - 1.0),
        normal(3, STEPS, STREAMS, STATE), normal(4, STEPS, STREAMS, STATE),
    )
    fresh = lambda: normal(5, STREAMS, layers, HEADS, HEAD, STATE)
    per_layer_step = 1e3 / (STEPS * layers)
    want_leaf, want_y = chain(body)(fresh(), a, xs)
    line = {
        "leaf": [STREAMS, layers, HEADS, HEAD, STATE],
        "device": jax.devices()[0].device_kind,
        "bytes_a_layer_step": 8 * STREAMS * HEADS * HEAD * STATE,
        "body_us": ms_per_call(chain(body), fresh(), a, xs) * per_layer_step,
        "body_largest_ops_us": largest_ops(
            lambda *v: chain(body)(fresh(), *v), a, xs, top=6),
    }
    rel = lambda got, want: float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    for heads in blocks:
        ssd._KERNEL_HEADS = heads
        ssd.ssd_step_kernel.clear_cache()
        got_leaf, got_y = chain(kernel)(fresh(), a, xs)
        line[f"kernel_{heads}_heads"] = {
            "us": ms_per_call(chain(kernel), fresh(), a, xs) * per_layer_step,
            "state_rel_l2": rel(got_leaf, want_leaf), "y_rel_l2": rel(got_y, want_y),
            "largest_ops_us": largest_ops(
                lambda *v: chain(kernel)(fresh(), *v), a, xs, top=4),
        }
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    if jax.default_backend() != "tpu":
        sys.exit("profile_ssd_step: needs a TPU, found " + jax.default_backend())
    blocks = [int(v) for v in sys.argv[1:]] or [16, 32, 64]
    for layers in (5, 4):
        run(layers, blocks)
