"""The fragment form of the gated delta rule with a decay a head, alone
on the chip: kernel pair against text.

    chiprun -- env PYTHONPATH=. python benchmarks/profile_delta_rule.py [kernel] [heads=N ...] [chunk ...]

One DeltaNet layer's rule at the Qwen3-Next cell's size (64 streams x 128
tokens, 32 value heads of ``dk = dv = 128``, float32, a reset a stream
somewhere inside, decays of ``-exp(A_log) softplus(.)`` with ``A`` in (1,
16)), from a stored state, taken as the learn program takes it: 16 streams
a call under ``lax.map``. Two timings of each lowering on the host's clock
over 10 queued calls: the forward pass alone, and ``value_and_grad`` of a
scalar of both outputs for every operand with the call under a
``jax.checkpoint`` (what a layer costs an update: forward, forward again,
backward). Prints one JSON line: milliseconds a layer for the chunked
``jax.numpy`` text (``ops/deltanet._chunked_text``: the lowering
elsewhere) and for the Pallas kernels
(``ops/deltanet.gated_delta_chunked_kernel``) at each chunk named on the
command line (default 64, the cell's), the distance between the two
lowerings' outputs and gradients, and the largest device operations of
each from a profiler trace. With ``kernel`` the kernels alone (a quarter
of the call's time: for trying a change to them); with ``heads=N`` the
kernels at ``N`` heads a grid step (default: ``ops/deltanet._CHUNKED_HEADS``,
the one the program uses). TPU only: a time from
another backend is not a device time.
"""

from __future__ import annotations

import functools
import json
import sys

import jax
import jax.numpy as jnp

from benchmarks.profile_moe_product import largest_ops, ms_per_call
from ray_tpu.ops import deltanet

STREAMS, TOKENS, HEADS, DK, DV = 64, 128, 32, 128, 128
GROUP = 16  # streams a call, as ``SequenceLM``'s ``learn_streams``


def operands():
    keys = jax.random.split(jax.random.PRNGKey(64), 8)
    normal = lambda k, *shape: jax.random.normal(keys[k], shape, jnp.float32)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    a = jax.random.uniform(keys[5], (HEADS,), minval=1.0, maxval=16.0)
    resets = jnp.zeros((STREAMS, TOKENS), jnp.float32)
    # a reset at a token of its own a stream; stream 0 has none
    at = jax.random.randint(keys[7], (STREAMS,), 0, TOKENS)
    resets = resets.at[jnp.arange(1, STREAMS), at[1:]].set(1.0)
    return (
        normal(0, STREAMS, HEADS, DK, DV),
        unit(normal(1, STREAMS, TOKENS, HEADS, DK)) * DK ** -0.5,
        unit(normal(2, STREAMS, TOKENS, HEADS, DK)),
        normal(3, STREAMS, TOKENS, HEADS, DV),
        -a * jax.nn.softplus(normal(4, STREAMS, TOKENS, HEADS) + 1.0),
        jax.nn.sigmoid(normal(6, STREAMS, TOKENS, HEADS)),
        resets,
    )


def both(rule, chunk):
    """``(forward, forward + forward again + backward)`` of ``rule`` as
    jitted calls over groups of ``GROUP`` streams."""
    groups = lambda x: x.reshape((STREAMS // GROUP, GROUP) + x.shape[1:])
    whole = lambda x: x.reshape((STREAMS,) + x.shape[2:])

    def layer(*ops, remat):
        call = lambda group: rule(*group, chunk=chunk)
        call = jax.checkpoint(call) if remat else call
        o, after = jax.lax.map(call, tuple(groups(x) for x in ops))
        return whole(o), whole(after)

    def scalar(*ops):
        o, after = layer(*ops, remat=True)
        return jnp.sum(o * o) + jnp.sum(after * after)

    return (jax.jit(lambda *ops: layer(*ops, remat=False)),
            jax.jit(jax.value_and_grad(scalar, argnums=tuple(range(6)))))


def rel(got, want):
    return [
        float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
        for g, w in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want))]


def run(chunks, text=True, heads=(None,)):
    ops = operands()
    line = {"shape": [STREAMS, TOKENS, HEADS, DK, DV], "streams_a_call": GROUP,
            "device": jax.devices()[0].device_kind}
    for chunk in chunks:
        case = {}
        kernels = [
            ("kernel" + (f"_{n}_heads" if n else ""), functools.partial(
                deltanet.gated_delta_chunked_kernel, heads=n)) for n in heads]
        for name, rule in [("text", deltanet._chunked_text)] * text + kernels:
            fwd, grad = both(rule, chunk)
            case[name] = {
                "fwd_ms": ms_per_call(fwd, *ops),
                "fwd_fwd_bwd_ms": ms_per_call(grad, *ops),
                "largest_ops_us": largest_ops(grad, *ops, top=6),
            }
            case[name + "_values"] = fwd(*ops), grad(*ops)
        values = {name: case.pop(name + "_values") for name, _ in kernels}
        if text:
            want = case.pop("text_values")
            for name, got in values.items():
                case[name]["outputs_rel_l2"] = rel(got[0], want[0])
                case[name]["gradients_rel_l2"] = rel(got[1], want[1])
        line[f"chunk_{chunk}"] = case
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    if jax.default_backend() != "tpu":
        sys.exit("profile_delta_rule: needs a TPU, found " + jax.default_backend())
    args = sys.argv[1:]
    run([int(v) for v in args if v.isdigit()] or [64], text="kernel" not in args,
        heads=[int(v[6:]) for v in args if v.startswith("heads=")] or (None,))
