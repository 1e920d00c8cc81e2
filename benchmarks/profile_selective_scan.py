"""The fragment-form selective scan alone on the chip: kernel against text.

    chiprun -- env PYTHONPATH=. python benchmarks/profile_selective_scan.py [tile ...]

One scan layer's recurrence at the Phi-4-mini-flash cell's size (16
streams x 256 tokens, 16 states x 5,120 channels, float32, a reset a
stream somewhere inside), from a stored state: the forward pass alone,
and ``value_and_grad`` of a scalar of both outputs for every operand
(forward plus backward: under a ``custom_vjp`` nothing is recomputed
here; the model's block checkpoint adds a second forward), on the
host's clock over 10 queued calls. Prints one JSON line: milliseconds a
layer for the ``jax.numpy`` text (the lowering elsewhere) and for the
Pallas kernels at each tile of channels named on the command line
(default 256 512 1024; ``ops/selective_scan._TILE`` is the one the
program uses), the share of ``perf/sambay_model.scan_fragment_bytes``'
HBM roofline that one forward twice and one backward make (what
``scan.fragment_hbm_roofline_pct`` reads from a cell's trace), the
(token, channel, state) elements a second of the forward (the vector
unit's rate: ROADMAP B30 (e)), the distance between the two lowerings'
outputs and gradients, and the largest device operations of each from a
profiler trace. TPU only: a time from another backend is not a device
time.
"""

from __future__ import annotations

import json
import sys

import jax
import jax.numpy as jnp

from benchmarks.profile_moe_product import largest_ops, ms_per_call
from perf import flops, sambay_model
from ray_tpu.ops import selective_scan

STREAMS, TOKENS = 16, 256
CONFIG = "perf/configs/phi4_mini_flash_ppo.json"


def operands(inner, state):
    keys = jax.random.split(jax.random.PRNGKey(58), 7)
    normal = lambda k, *shape: jax.random.normal(keys[k], shape, jnp.float32)
    resets = jnp.zeros((STREAMS, TOKENS), jnp.float32)
    # a reset at a token of its own a stream; stream 0 has none
    at = jax.random.randint(keys[6], (STREAMS,), 0, TOKENS)
    resets = resets.at[jnp.arange(1, STREAMS), at[1:]].set(1.0)
    return (
        normal(0, STREAMS, state, inner),
        normal(1, STREAMS, TOKENS, inner),
        jax.nn.softplus(normal(2, STREAMS, TOKENS, inner) - 4.0),
        -jnp.exp(normal(3, state, inner) * 0.5),
        normal(4, STREAMS, TOKENS, state), normal(5, STREAMS, TOKENS, state),
        resets,
    )


def both(scan):
    """``(forward, forward and backward)`` of ``scan`` as jitted calls."""
    def scalar(*ops):
        y, after = scan(*ops)
        return jnp.sum(y * y) + jnp.sum(after * after)

    return jax.jit(scan), jax.jit(
        jax.value_and_grad(scalar, argnums=tuple(range(6))))


def rel(got, want):
    return [
        float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
        for g, w in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want))]


def run(tiles):
    with open(CONFIG) as f:
        config = json.load(f)
    z = sambay_model.sizes(config)
    ops = operands(z["inner"], z["state"])
    device = jax.devices()[0].device_kind
    need = sambay_model.scan_fragment_bytes(config, STREAMS, TOKENS)
    floor_ms = 1e3 * need / flops.load_peaks(device)["hbm_bytes_per_s"]
    elements = STREAMS * TOKENS * z["inner"] * z["state"]

    def case(scan):
        fwd, grad = both(scan)
        fwd_ms, grad_ms = ms_per_call(fwd, *ops), ms_per_call(grad, *ops)
        return (fwd, grad), {
            "fwd_ms": fwd_ms, "fwd_bwd_ms": grad_ms,
            # as the cell runs a layer: forward, forward again under the
            # block's checkpoint, backward
            "roofline_pct_of_scan_fragment_bytes":
                100.0 * floor_ms / (fwd_ms + grad_ms),
            "fwd_elements_per_s": elements / (fwd_ms * 1e-3),
        }

    (text_fwd, text_grad), text = case(selective_scan._scan_text)
    text["largest_ops_us"] = largest_ops(text_grad, *ops, top=6)
    line = {
        "shape": [STREAMS, TOKENS, z["state"], z["inner"]], "device": device,
        "scan_fragment_bytes": need, "hbm_floor_ms": floor_ms, "text": text,
    }
    want = text_fwd(*ops), text_grad(*ops)
    for tile in tiles:
        (fwd, grad), got = case(
            lambda *v, tile=tile: selective_scan.selective_scan_kernel(*v, tile=tile))
        got["tile"] = selective_scan._tile_of(z["inner"], TOKENS, z["state"], tile)
        got["outputs_rel_l2"] = rel(fwd(*ops), want[0])
        got["gradients_rel_l2"] = rel(grad(*ops), want[1])
        got["largest_ops_us"] = largest_ops(grad, *ops, top=6)
        line[f"kernel_tile_{tile}"] = got
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    if jax.default_backend() != "tpu":
        sys.exit("profile_selective_scan: needs a TPU, found " + jax.default_backend())
    run([int(v) for v in sys.argv[1:]] or [256, 512, 1024])
