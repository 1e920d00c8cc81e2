"""Routed expert layer that is told which experts it holds.

The router keeps its published width: every token is scored against
ALL ``E`` experts (a softmax over them, or a sigmoid each), the ``k``
largest scores are kept (and renormalised and scaled where the model
says so; a selection bias may pick them without weighing them). This chip holds experts
``[first, first + held)`` of them and computes what they add to each
token. What the absent experts would add
is left out (model-configs guide, section 4): no token is dropped, no
capacity is set, and nothing stands in for other chips.

An expert is gated, ``(act(x Wg) * (x Wu)) Wd``, or, handed no gate
matrix, UNGATED, ``act(x Wu) Wd`` of two matrices, with ``act`` named by
``activation`` (``"silu"``: SwiGLU; ``"relu"``: ReGLU; ``"relu2"``,
``relu(.)^2``: Nemotron-H's ungated experts).

The product has two forms with one result (float32 summation order
apart), and :func:`product_lowering` picks one from the static shapes:

- **dense** (:func:`dense_experts_product`): every held expert over
  every token, times a ``(tokens, held)`` weight matrix
  (:func:`held_combine_weights`) that is zero where the expert is not
  among the token's ``k``: ``tokens x held`` rows. A decode step reads
  every held expert's weights once either way, and its few tokens leave
  a sort nothing to take.
- **grouped** (:func:`grouped_experts_product`): the (token, slot) pairs
  on held experts sorted by expert, each expert's rows gathered into a
  buffer of its own, one batched product a projection over ``(held,
  buffer)`` rows, the rows added back onto their tokens. A buffer is
  static (:func:`expert_buffer_rows`: three times the tokens a uniform
  router sends one expert, in whole tiles); a call in which a held
  expert gets more takes the dense form under ``lax.cond``, so every
  routing is computed in full: all tokens on one expert cost what the
  dense form costs and drop nothing. A layer that is told so
  (``alone``: a model that generates by masked diffusion routes every
  ``[MASK]`` of a pass alike, so all of a pass's tokens on one expert is
  its ordinary case) lets that many of its most loaded experts outgrow
  their buffers: each keeps its buffer empty and runs over EVERY token
  as one gated product under its column of the combine weights, beside
  the others' buffers, and only a further one sends the call dense.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def chosen_groups(pick, n_group: int, topk_group: int):
    """``(T, n_group)`` bool: the ``topk_group`` groups of consecutive
    experts a token may choose among (DeepSeek-V3's group-limited
    routing, arXiv:2412.19437 section 2.1.2, as ``noaux_tc`` has it): a
    group's score is the sum of its TWO largest ``pick`` (the scores
    that choose: the selection bias included)."""
    grouped = pick.reshape(pick.shape[:-1] + (n_group, -1))
    group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
    _, best = jax.lax.top_k(group_score, topk_group)  # (T, topk_group)
    return jnp.any(best[..., None] == jnp.arange(n_group), axis=-2)


def route(
    x, router_kernel, k: int, renormalise: bool, scoring: str = "softmax",
    select_bias=None, scale: float = 1.0, n_group: int = 1, topk_group: int = 1,
):
    """``(indices (T, k) int32, weights (T, k) float32, groups)``: scores
    over all router outputs in float32 at precision "highest" (a rounding
    step here changes WHICH experts a token gets), then the top ``k``.
    ``scoring`` is ``"softmax"`` or ``"sigmoid"`` (each expert scored on
    its own: DeepSeek-V3). ``select_bias`` ``(E,)`` is added to the
    scores that PICK the experts and never to a weight, and takes no
    gradient (the load-balancing bias of ``noaux_tc``); ``scale``
    multiplies the weights after the renormalisation
    (``routed_scaling_factor``). With ``n_group`` above 1 the choice is
    group-limited: only the experts of a token's :func:`chosen_groups`
    stand for its top ``k`` (the others are struck out), and ``groups``
    is that ``(T, n_group)`` choice; ``n_group`` 1 is the plain top-k
    (the operations it always was) and ``groups`` is None."""
    logits = jnp.dot(
        x.astype(jnp.float32), router_kernel.astype(jnp.float32), precision=_HI
    )
    if scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    elif scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"unknown router scoring {scoring!r}")
    pick = scores if select_bias is None else (
        scores + jax.lax.stop_gradient(select_bias.astype(jnp.float32)))
    groups = None
    if n_group > 1:
        with jax.named_scope("groups"):
            groups = chosen_groups(pick, n_group, topk_group)
            pick = jnp.where(
                jnp.repeat(groups, pick.shape[-1] // n_group, axis=-1), pick, -jnp.inf)
    if pick is scores:
        weights, indices = jax.lax.top_k(scores, k)
    else:
        _, indices = jax.lax.top_k(pick, k)
        weights = jnp.take_along_axis(scores, indices, axis=-1)
    if renormalise:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    if scale != 1.0:
        weights = weights * scale
    return indices.astype(jnp.int32), weights, groups


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


_ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu,
                "relu2": lambda v: jnp.square(jax.nn.relu(v))}


def _hidden(act, gate, up):
    """An expert's hidden activations from its float32 products; ``gate``
    None: an ungated expert."""
    return act(up) if gate is None else act(gate) * up


def _cast(w, dtype):
    return None if w is None else w.astype(dtype)


def gated_mlp(x, w_gate, w_up, w_down, dtype=jnp.bfloat16, activation: str = "silu"):
    """``(act(x Wg) * (x Wu)) Wd``, or ``act(x Wu) Wd`` where ``w_gate``
    is None, with ``dtype`` operands and float32 accumulation: the
    shared expert, and one routed expert."""
    act = _ACTIVATIONS[activation]
    xb = x.astype(dtype)
    gate = None if w_gate is None else jnp.dot(
        xb, w_gate.astype(dtype), preferred_element_type=jnp.float32)
    up = jnp.dot(xb, w_up.astype(dtype), preferred_element_type=jnp.float32)
    hidden = _hidden(act, gate, up).astype(dtype)
    return jnp.dot(hidden, w_down.astype(dtype), preferred_element_type=jnp.float32)


# the row tile a held expert's buffer is a multiple of
GROUP_TILE = 128
# a held expert's buffer over the tokens a uniform router sends it:
# the largest load of a held expert read 1.5-1.8 times the mean in the
# cells (``moe.max_expert_load_ratio``)
_ROOM = 3


def expert_buffer_rows(tokens: int, k: int, num_experts: int) -> int:
    """Rows of one held expert's buffer in the grouped form, static:
    ``_ROOM`` times ``tokens x k / E`` in whole tiles. 2,048 tokens,
    top-10 of 512: 128; 1,024 tokens, top-4 of 64: 256."""
    return max(1, -(-_ROOM * tokens * k // (num_experts * GROUP_TILE))) * GROUP_TILE


def product_lowering(tokens: int, k: int, num_experts: int) -> str:
    """``"dense"`` or ``"grouped"``, from static shapes alone: dense
    while the tokens are no more than one expert's buffer, so that the
    buffers would hold as many rows as the dense form computes. A
    decode step's 64 tokens (top-10 of 512): dense; a learn block's
    2,048: 65,536 dense rows against 32 buffers of 128, grouped."""
    fits_a_buffer = tokens <= expert_buffer_rows(tokens, k, num_experts)
    return "dense" if fits_a_buffer else "grouped"


def rows_computed(per_expert, tokens: int, k: int, num_experts: int, lowering: str,
                  alone: int = 0):
    """Rows of the ``(rows, D) x (D, F)`` products that ``lowering``
    computes for these per-expert counts, float32: every token under
    every held expert (dense, and a grouped call in which more than
    ``alone`` experts outgrew their buffers), or every held expert's
    buffer, and every token once more for each expert that outgrew its
    own."""
    held = per_expert.shape[0]
    dense_rows = jnp.float32(tokens * held)
    if lowering == "dense":
        return dense_rows
    buffer = expert_buffer_rows(tokens, k, num_experts)
    if not alone:
        return jnp.where(jnp.max(per_expert) <= buffer, held * buffer, dense_rows)
    outgrown = jnp.sum(per_expert > buffer)
    return jnp.where(
        outgrown > alone, dense_rows,
        held * buffer + tokens * outgrown.astype(jnp.float32))


def dense_experts_product(
    x, w_gate, w_up, w_down, combine, *, block_tokens: int = 1024,
    dtype=jnp.bfloat16, activation: str = "silu",
):
    """``sum_e combine[t, e] * expert_e(x_t)``, every held expert over
    every token. ``x`` ``(T, D)``; ``w_gate`` (None: ungated experts),
    ``w_up`` ``(held, D, F)``; ``w_down`` ``(held, F, D)``; ``combine``
    ``(T, held)``. Tokens
    go through in blocks of ``block_tokens`` (each recomputed in the
    backward pass), so the ``(block, held, F)`` hidden activations bound
    the memory, not ``(T, held, F)``."""
    t, d = x.shape
    act = _ACTIVATIONS[activation]
    wg, wu, wd = (_cast(w, dtype) for w in (w_gate, w_up, w_down))

    @jax.checkpoint
    def block(xb, cb):
        xb = xb.astype(dtype)
        gate = None if wg is None else jnp.einsum(
            "td,edf->tef", xb, wg, preferred_element_type=jnp.float32)
        up = jnp.einsum("td,edf->tef", xb, wu, preferred_element_type=jnp.float32)
        hidden = (_hidden(act, gate, up) * cb[..., None]).astype(dtype)
        return jnp.einsum(
            "tef,efd->td", hidden, wd, preferred_element_type=jnp.float32
        )

    n = max(1, t // block_tokens)
    if n == 1 or t % n:
        return block(x, combine)
    out = jax.lax.map(
        lambda xc: block(*xc),
        (x.reshape(n, t // n, d), combine.reshape(n, t // n, -1)),
    )
    return out.reshape(t, d)


def grouped_experts_product(
    x, w_gate, w_up, w_down, indices, weights, per_expert, first: int,
    num_experts: int, dtype=jnp.bfloat16, activation: str = "silu",
    alone: int = 0,
):
    """The same sum over the (token, slot) pairs on held experts only
    (``w_gate`` None: ungated experts). ``indices``, ``weights`` ``(T, k)`` as :func:`route` gives
    them, ``per_expert`` ``(held,)`` the pairs on each held expert
    (:func:`expert_load`). Pairs are sorted by expert (stable; pairs on
    absent experts last), so expert ``e``'s pairs are ``per_expert[e]``
    consecutive places of the order and slot ``c`` of its buffer reads
    the ``c``-th of them; a slot past the count carries weight zero and
    no token. The products are plain batched ones: the backward pass is
    their transposes, the weights' gradient accumulated in float32 by
    the product itself. Up to ``alone`` of the most loaded experts,
    where they have more pairs than a buffer holds, keep their buffers
    empty and run over all ``T`` tokens instead (``one_alone``); more
    such experts than that (any, at 0: every family that does not route
    a pass's tokens alike): ``dense``."""
    t, d = x.shape
    held = w_up.shape[0]
    k = indices.shape[-1]
    buffer = expert_buffer_rows(t, k, num_experts)
    counts = per_expert.astype(jnp.int32)
    act = _ACTIVATIONS[activation]
    # cast once for both ways: the cond hands back ``dtype`` gradients
    wg, wu, wd = (_cast(w, dtype) for w in (w_gate, w_up, w_down))

    buffered = counts
    if alone:
        # the most loaded experts, where they have more pairs than a
        # buffer holds, keep their buffers empty and run over every
        # token instead
        most = jnp.argsort(-counts)[:alone]
        outgrown = counts[most] > buffer
        buffered = counts.at[most].set(jnp.where(outgrown, 0, counts[most]))

    def grouped(buffered):
        """``buffered``: the pairs of each held expert that go through
        its buffer (all of them, or none of an outgrown one's)."""
        local = indices.reshape(-1) - first  # (T k,)
        here = (local >= 0) & (local < held)
        order = jnp.argsort(jnp.where(here, local, held), stable=True)
        slot = jnp.arange(buffer, dtype=jnp.int32)
        place = (jnp.cumsum(counts) - counts)[:, None] + slot  # (held, buffer)
        filled = slot < buffered[:, None]
        pair = jnp.take(order, jnp.minimum(place, t * k - 1))
        token = jnp.where(filled, pair // k, t)  # t: no token
        weight = jnp.where(filled, jnp.take(weights.reshape(-1), pair), 0.0)
        rows = jnp.take(x.astype(dtype), token, axis=0, mode="fill", fill_value=0)
        gate = None if wg is None else jnp.einsum(
            "ecd,edf->ecf", rows, wg, preferred_element_type=jnp.float32)
        up = jnp.einsum("ecd,edf->ecf", rows, wu, preferred_element_type=jnp.float32)
        hidden = (_hidden(act, gate, up) * weight[..., None]).astype(dtype)
        out = jnp.einsum(
            "ecf,efd->ecd", hidden, wd, preferred_element_type=jnp.float32
        )
        return jnp.zeros((t, d), jnp.float32).at[token.reshape(-1)].add(
            out.reshape(-1, d), mode="drop"
        )

    def dense():
        combine = held_combine_weights(indices, weights, first, held)
        return dense_experts_product(
            x, wg, wu, wd, combine, dtype=dtype, activation=activation)

    fits = jnp.max(buffered) <= buffer
    out = jax.lax.cond(fits, lambda: grouped(buffered), dense)
    if alone:
        from ray_tpu import sharding as sharding_lib

        def one_alone(e):
            column = jnp.sum(
                jnp.where(indices - first == e, weights, 0.0), axis=-1, keepdims=True)
            of = lambda w: None if w is None else jax.lax.dynamic_index_in_dim(
                w, e, 0, keepdims=False)
            return column * gated_mlp(x, of(wg), of(wu), of(wd), dtype, activation)

        # typed as the tokens are: inside a ``shard_map`` a cond's two
        # results vary over the same axes
        nothing = lambda: sharding_lib.varying(
            jnp.zeros((t, d), jnp.float32), sharding_lib.vma_of((x, weights)))
        for r in range(alone):
            out = out + jax.lax.cond(
                outgrown[r] & fits, lambda r=r: one_alone(most[r]), nothing)
    return out
