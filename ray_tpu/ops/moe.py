"""Routed expert layer that is told which experts it holds.

The router keeps its published width: every token is scored against
ALL ``E`` experts (a softmax over them, or a sigmoid each), the ``k``
largest scores are kept (and renormalised and scaled where the model
says so; a selection bias may pick them without weighing them). This chip holds experts
``[first, first + held)`` of them. :func:`held_combine_weights` turns a
token's ``k`` (expert, weight) slots into a dense ``(tokens, held)``
weight matrix, zero where an expert is not among the token's ``k``, and
:func:`held_experts_product` computes the grouped product over the held
experts under those weights. What the absent experts would add is left
out (model-configs guide, section 4): no token is dropped, no capacity
is set, and nothing stands in for other chips.

The grouped product is written densely (every held expert over every
token of a block, times the weight): exact for any routing, and the
baseline a sorted or ragged kernel has to beat. A decode step reads
every held expert's weights once either way.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def route_top_k(
    x, router_kernel, k: int, renormalise: bool, scoring: str = "softmax",
    select_bias=None, scale: float = 1.0,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(indices (T, k) int32, weights (T, k) float32)``: scores over
    all router outputs in float32 at precision "highest" (a rounding
    step here changes WHICH experts a token gets), then the top ``k``.
    ``scoring`` is ``"softmax"`` or ``"sigmoid"`` (each expert scored on
    its own: DeepSeek-V3). ``select_bias`` ``(E,)`` is added to the
    scores that PICK the experts and never to a weight, and takes no
    gradient (the load-balancing bias of ``noaux_tc``); ``scale``
    multiplies the weights after the renormalisation
    (``routed_scaling_factor``)."""
    logits = jnp.dot(
        x.astype(jnp.float32), router_kernel.astype(jnp.float32), precision=_HI
    )
    if scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    elif scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"unknown router scoring {scoring!r}")
    if select_bias is None:
        weights, indices = jax.lax.top_k(scores, k)
    else:
        _, indices = jax.lax.top_k(
            scores + jax.lax.stop_gradient(select_bias.astype(jnp.float32)), k
        )
        weights = jnp.take_along_axis(scores, indices, axis=-1)
    if renormalise:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    if scale != 1.0:
        weights = weights * scale
    return indices.astype(jnp.int32), weights


def held_combine_weights(indices, weights, first: int, held: int):
    """``(T, held)`` float32: the weight of held expert ``e`` for each
    token, zero where it is not among the token's slots."""
    local = indices - first  # (T, k)
    hit = local[..., None] == jnp.arange(held, dtype=jnp.int32)
    return jnp.sum(jnp.where(hit, weights[..., None], 0.0), axis=-2)


def expert_load(indices, first: int, held: int):
    """``(tokens per held expert (held,), slots on absent experts)``,
    both float32 counts."""
    local = indices.reshape(-1) - first
    here = (local >= 0) & (local < held)
    per_expert = jnp.zeros((held,), jnp.float32).at[
        jnp.where(here, local, held)
    ].add(1.0, mode="drop")
    return per_expert, jnp.sum(~here).astype(jnp.float32)


def gated_mlp(x, w_gate, w_up, w_down, dtype=jnp.bfloat16):
    """``(silu(x Wg) * (x Wu)) Wd`` with ``dtype`` operands and float32
    accumulation: the shared expert, and one routed expert."""
    xb = x.astype(dtype)
    gate = jnp.dot(xb, w_gate.astype(dtype), preferred_element_type=jnp.float32)
    up = jnp.dot(xb, w_up.astype(dtype), preferred_element_type=jnp.float32)
    hidden = (jax.nn.silu(gate) * up).astype(dtype)
    return jnp.dot(hidden, w_down.astype(dtype), preferred_element_type=jnp.float32)


def held_experts_product(
    x, w_gate, w_up, w_down, combine, *, block_tokens: int = 1024,
    dtype=jnp.bfloat16,
):
    """``sum_e combine[t, e] * expert_e(x_t)`` over the held experts.
    ``x`` ``(T, D)``; ``w_gate``, ``w_up`` ``(held, D, F)``; ``w_down``
    ``(held, F, D)``; ``combine`` ``(T, held)``. Tokens go through in
    blocks of ``block_tokens`` (each recomputed in the backward pass),
    so the ``(block, held, F)`` hidden activations bound the memory,
    not ``(T, held, F)``."""
    t, d = x.shape
    wg, wu, wd = (w.astype(dtype) for w in (w_gate, w_up, w_down))

    @jax.checkpoint
    def block(xb, cb):
        xb = xb.astype(dtype)
        gate = jnp.einsum("td,edf->tef", xb, wg, preferred_element_type=jnp.float32)
        up = jnp.einsum("td,edf->tef", xb, wu, preferred_element_type=jnp.float32)
        hidden = (jax.nn.silu(gate) * up * cb[..., None]).astype(dtype)
        return jnp.einsum(
            "tef,efd->td", hidden, wd, preferred_element_type=jnp.float32
        )

    n = max(1, t // int(block_tokens))
    if n == 1 or t % n:
        return block(x, combine)
    out = jax.lax.map(
        lambda xc: block(*xc),
        (x.reshape(n, t // n, d), combine.reshape(n, t // n, -1)),
    )
    return out.reshape(t, d)
