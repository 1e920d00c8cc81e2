"""Gated delta rule (Gated DeltaNet, Yang et al. 2024) in two forms
that are the same function of the same inputs.

Per head, with a ``(dk, dv)`` state ``S``, a log-decay ``g_t <= 0``
and a write strength ``beta_t`` in (0, 1)::

    S   <- exp(g_t) * S
    d_t  = beta_t * (v_t - S^T k_t)
    S   <- S + k_t d_t^T
    o_t  = S^T q_t

**The decay is a number a head, or a number a KEY CHANNEL** (Kimi Delta
Attention, Kimi Linear, arXiv:2510.26692): ``g`` with a trailing ``dk``
axis makes the first line ``S <- diag(exp(g_t)) S``, a row of ``S`` a
channel. Both forms take either, told apart by ``g``'s rank when they
are traced; the scalar decay keeps the text it had (PERF.md, PR 61: the
shared path is split, not adapted), and 128 equal channels give what
the scalar gives, to rounding.

- :func:`gated_delta_step` is that recurrence for ONE token: the
  rollout lane's decode step (state in, state out).
- :func:`gated_delta_chunked` computes a fragment of ``T`` tokens from
  a start state in chunks of ``C``: inside a chunk the ``C`` rank-one
  writes are solved at once (``(I + tril(K_beta K^T * decay, -1))^-1``,
  by the nilpotent product ``(I - A)(I + A^2)(I + A^4)...``, all matrix
  products), and only the chunk-end state is carried: the learn
  program's form, whose backward pass keeps ``T / C`` states instead
  of ``T``. A decay a channel has no ``(C, C)`` factor: each product
  over ``dk`` carries ``exp(G_i - G_j)`` INSIDE the sum
  (:func:`_channel_decayed_products`), so the chunk is cut into
  sub-blocks of ``_SUB`` rows and both operands are scaled against a
  sub-block's FIRST row: every factor is at most 1 except inside a
  diagonal sub-block, where it is at most ``exp(-(_SUB - 1) min g)``.
  That is finite in float32 for the bounded gate the layer computes
  (``g > -5``: ``exp(75)``) and for nothing much below it: the bound is
  the model's (``kda_safe_gate``), and no clip is added here.

**The one-token form has two lowerings of one algorithm**, picked by
what the code can see when it is traced, never by an option:

- :func:`gated_delta_step_kernel`, a Pallas (Mosaic) kernel, where the
  default backend is a TPU and ``dk`` and ``dv`` are whole 128-lane
  tiles (:func:`_kernel_applies`). A block of heads of one stream sits
  in VMEM while the four lines run on it, and the state's buffer is
  updated in place (``input_output_aliases``): per step and layer the
  state crosses HBM ONCE in and ONCE out, 8 bytes an element.
- :func:`_delta_step_body`, the four lines in ``jax.numpy``, everywhere
  else (the CPU, odd head sizes). It is the statement of the function
  and the kernel's reference. XLA cannot put a reduction and the
  elementwise op that consumes its result into one fusion, so on a TPU
  this body reads every matrix three times and writes it once.

``ray_tpu_deltanet_step_lowerings_total{path="kernel"|"xla",
decay="head"|"channel"}`` counts, at trace time, which one each traced
one-token form took and for which decay.

**Resets.** ``resets`` (1.0 where a token begins a new episode) zero
the state before that token. In the chunked form a reset splits its
chunk into segments: products across a segment boundary are masked
out, and the start state reaches only the tokens before the first
reset. The one-token form has no argument for it; its caller zeroes
the rows first (``SequenceLM.reset_state``). On the rollout lane that
is the lane, AFTER the step on which a stream's episode ended
(``execution/jax_rollout.py``, under a ``lax.cond`` on "some stream
ended"), so no full-state pass runs on a step where none did. (As a
property of the function, ``g = -inf`` on a row clears its finite
matrix before the write, ``exp(g) = 0``; nothing in the repo relies on
it.)

Everything here is float32 at precision "highest" (the kernel: float32
multiply-adds on the VPU): the state is an accumulator over the whole
episode, and the PPO ratio divides what the chunked form says by what
the recurrence said.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import backend
from ray_tpu.telemetry import metrics as telemetry_metrics

_HI = jax.lax.Precision.HIGHEST

# heads of one stream a grid step of the kernel holds in VMEM: 1 MB of
# matrices in and out at 128 x 128 (on the v5e 8 heads a step were 4%
# slower, 32 no faster)
_KERNEL_HEADS = 16
# rows of a chunk's sub-block under a decay a channel (Kimi Linear's 16)
_SUB = 16
# what a log-decay a channel must stay above for the chunked form's
# largest factor, ``exp(-(_SUB - 1) g)``, to be finite in float32
# (``exp(88)`` is the last that is): a layer whose gate can go below it
# is refused where it is described, not clipped here
CHANNEL_LOG_DECAY_FLOOR = -88.0 / (_SUB - 1)


def gated_delta_step(state, q, k, v, g, beta):
    """One token of the recurrence. ``state`` ``(..., dk, dv)``; ``q``,
    ``k`` ``(..., dk)``; ``v`` ``(..., dv)``; ``beta`` ``(...)``; ``g``
    ``(...)``, or ``(..., dk)``: a decay a key channel. Returns
    ``(state, o)`` with ``o`` ``(..., dv)``."""
    decay = "channel" if g.ndim == k.ndim else "head"
    if _kernel_applies(state):
        telemetry_metrics.inc_deltanet_step_lowering("kernel", decay)
        return gated_delta_step_kernel(state, q, k, v, g, beta)
    telemetry_metrics.inc_deltanet_step_lowering("xla", decay)
    return _delta_step_body(state, q, k, v, g, beta)


def _delta_step_body(state, q, k, v, g, beta):
    # a decay a channel scales the rows, a decay a head the matrix
    decay = jnp.exp(g)
    state = state * (decay[..., None] if g.ndim == k.ndim else decay[..., None, None])
    read = jnp.einsum("...kv,...k->...v", state, k, precision=_HI)
    delta = beta[..., None] * (v - read)
    state = state + k[..., :, None] * delta[..., None, :]
    out = jnp.einsum("...kv,...k->...v", state, q, precision=_HI)
    return state, out


def _kernel_applies(state) -> bool:
    """The kernel's lowering exists for a TPU, for ``(streams, heads,
    dk, dv)`` float32 with ``dk`` and ``dv`` whole 128-lane tiles and
    the heads whole 8-sublane tiles (``k`` and ``q`` are turned from
    lanes to sublanes a block of heads at a time); "a TPU" as
    ``ops/backend.is_tpu`` has it."""
    if not backend.is_tpu() or state.ndim != 4:
        return False
    heads, dk, dv = state.shape[-3:]
    return (
        state.dtype == jnp.float32
        and dk % 128 == 0 and dv % 128 == 0 and heads % 8 == 0
    )


def _delta_step_kernel(decay_ref, beta_ref, k_ref, q_ref, v_ref, s_ref,
                       s_out_ref, o_ref, *, channel: bool):
    """One stream, a block of heads. ``s_ref`` ``(1, H, dk, dv)``; the
    other inputs ``(1, H, width)`` rows, ``beta`` repeated along the
    lanes, and ``decay`` too where it is a number a head; with
    ``channel`` it is a row of ``dk``, a number a key channel. ``k`` and
    ``q`` (and a decay a channel) arrive with ``dk`` on the lanes and are
    turned once a block, so that a head's column broadcasts along the
    lanes of its matrix."""
    heads = s_ref.shape[1]
    k_cols, q_cols = k_ref[0].T, q_ref[0].T  # (dk, H)
    decay, beta, v = decay_ref[0], beta_ref[0], v_ref[0]
    if channel:
        decay = decay.T  # (dk, H): a head's column scales its rows
    outs = []
    for h in range(heads):
        k_col, q_col = k_cols[:, h : h + 1], q_cols[:, h : h + 1]
        s = s_ref[0, h] * (decay[:, h : h + 1] if channel else decay[h : h + 1])
        read = jnp.sum(s * k_col, axis=0, keepdims=True)
        delta = beta[h : h + 1] * (v[h : h + 1] - read)
        s = s + k_col * delta
        s_out_ref[0, h] = s
        outs.append(jnp.sum(s * q_col, axis=0, keepdims=True))
    o_ref[0] = jnp.concatenate(outs, axis=0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gated_delta_step_kernel(state, q, k, v, g, beta, *, interpret=False):
    """:func:`gated_delta_step` as one Pallas call over ``(streams,
    heads / block)``: ``state`` ``(B, H, dk, dv)`` float32, updated in
    place. ``interpret`` runs it in the Pallas interpreter (the CPU
    tests); nothing upstream passes it. A ``jit`` of its own, so that
    a program with many call sites (a lane's rollout has nine: three
    layers in the act, the truncation's value forward and the tail's)
    traces the kernel once and lowers it once: traced at every site
    it cost a run of the sequence cell 7 s of set-up."""
    from ray_tpu import sharding as sharding_lib

    b, h, dk, dv = state.shape
    heads = _KERNEL_HEADS if h % _KERNEL_HEADS == 0 else 8
    channel = g.ndim == 3  # a row of ``dk`` a head, as ``k`` is
    decay = jnp.exp(g) if channel else jnp.broadcast_to(
        jnp.exp(g)[..., None], (b, h, dv))
    beta = jnp.broadcast_to(beta[..., None], (b, h, dv))
    # inside a ``shard_map`` the outputs vary over the mesh axes the
    # inputs do
    vma = sharding_lib.vma_of((state, q, k, v, decay, beta))
    rows = lambda width: pl.BlockSpec((1, heads, width), lambda i, j: (i, j, 0))
    matrices = pl.BlockSpec((1, heads, dk, dv), lambda i, j: (i, j, 0, 0))
    return pl.pallas_call(
        functools.partial(_delta_step_kernel, channel=channel),
        grid=(b, h // heads),
        in_specs=[rows(dk if channel else dv), rows(dv), rows(dk), rows(dk),
                  rows(dv), matrices],
        out_specs=[matrices, rows(dv)],
        out_shape=[
            jax.ShapeDtypeStruct(state.shape, state.dtype, vma=vma),
            jax.ShapeDtypeStruct(v.shape, v.dtype, vma=vma),
        ],
        input_output_aliases={5: 0},
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        name="gated_delta_step",
    )(decay, beta, k, q, v, state)


def _unit_lower_inverse(a):
    """``(I + a)^-1`` for strictly lower-triangular ``a`` ``(..., C, C)``:
    ``a`` is nilpotent (``a^C = 0``), so the inverse is the finite
    product ``(I - a)(I + a^2)(I + a^4)...`` — log2(C) matrix products
    and no triangular solve."""
    c = a.shape[-1]
    eye = jnp.eye(c, dtype=a.dtype)
    inv = eye - a
    power = a
    span = 2
    while span < c:
        power = jnp.matmul(power, power, precision=_HI)
        inv = jnp.matmul(inv, eye + power, precision=_HI)
        span *= 2
    return inv


def _channel_decayed_products(rows, k, gcum):
    """``out[.., i, j] = sum_c rows[.., i, c] k[.., j, c] exp(G[.., i, c]
    - G[.., j, c])`` for ``i >= j`` (entries above the diagonal are
    finite and meaningless: the caller masks them). ``rows`` ``(R, B, H,
    C, dk)``: the ``R`` left operands that share ``k`` and ``gcum``
    ``(B, H, C, dk)``, the chunk's keys and inclusive running sums of
    the log-decays. A block of ``sub`` rows takes its FIRST row's sums
    as the reference: its own rows are scaled by ``exp(G_i - G_ref) <=
    1``, the keys by ``exp(G_ref - G_j)``, which is at most 1 for a key
    of an earlier block and at most ``exp(-(sub - 1) min g)`` for a key
    of the block itself; a key of a later block gets 0."""
    c, dk = k.shape[-2:]
    sub = math.gcd(c, _SUB)
    n = c // sub
    blocked = gcum.reshape(gcum.shape[:-2] + (n, sub, dk))
    ref = blocked[..., :1, :]  # (B, H, n, 1, dk)
    left = rows.reshape(rows.shape[:-2] + (n, sub, dk)) * jnp.exp(blocked - ref)
    # keys up to the block's last row, against the block's reference
    seen = (jnp.arange(c) // sub)[None, :] <= jnp.arange(n)[:, None]  # (n, C)
    right = k[..., None, :, :] * jnp.exp(jnp.where(
        seen[..., None], ref - gcum[..., None, :, :], -jnp.inf))  # (B, H, n, C, dk)
    out = jnp.einsum("rbhnik,bhnjk->rbhnij", left, right, precision=_HI)
    return out.reshape(rows.shape[:-2] + (c, c))


def gated_delta_chunked(
    state,
    q,
    k,
    v,
    g,
    beta,
    resets: Optional[jnp.ndarray] = None,
    chunk: int = 64,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``T`` tokens from ``state``. ``q``, ``k`` ``(B, T, H, dk)``; ``v``
    ``(B, T, H, dv)``; ``beta`` ``(B, T, H)``; ``g`` ``(B, T, H)``, or
    ``(B, T, H, dk)``: a decay a key channel, which must stay above
    ``CHANNEL_LOG_DECAY_FLOOR`` (module docstring); ``state`` ``(B, H, dk,
    dv)``; ``resets`` ``(B, T)`` or None. ``T`` is a multiple of
    ``chunk`` (or shorter than it). Returns ``(o (B, T, H, dv), state)``.
    """
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    c = min(int(chunk), t)
    if t % c:
        raise ValueError(f"fragment of {t} tokens is not a multiple of {c}")
    n = t // c
    channel = g.ndim == 4
    if resets is None:
        resets = jnp.zeros((b, t), jnp.float32)

    def chunks(x):  # (B, T, H, ...) -> (n, B, H, C, ...)
        x = x.reshape((b, n, c) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    qs, ks, vs = chunks(q), chunks(k), chunks(v)
    gs, betas = chunks(g), chunks(beta)
    rs = jnp.moveaxis(resets.reshape(b, n, c), 1, 0)  # (n, B, C)
    row = jnp.arange(c)
    lower = row[:, None] >= row[None, :]
    strictly_lower = row[:, None] > row[None, :]

    def one_chunk(s, x):
        qc, kc, vc, gc, bc, rc = x
        seg = jnp.cumsum((rc > 0.5).astype(jnp.int32), axis=-1)[:, None]
        same = seg[..., :, None] == seg[..., None, :]  # (B, 1, C, C)
        kb = kc * bc[..., None]
        # ``a``: the writes' products under their decays, strictly below
        # the diagonal; ``qk``: the reads', on and below it; ``reach``
        # and ``tail``: the decay from the chunk's start to each row and
        # from each row to the chunk's end, a column (or a number a
        # channel) of each row
        if channel:
            gcum = jnp.cumsum(gc, axis=-2)  # (B, H, C, dk)
            a, qk = _channel_decayed_products(jnp.stack([kb, qc]), kc, gcum)
            a = jnp.where(strictly_lower & same, a, 0.0)
            qk = jnp.where(lower & same, qk, 0.0)
            # the start state reaches the tokens before the first reset
            reach = jnp.exp(gcum) * (seg == 0)[..., None]
            tail = jnp.exp(gcum[..., -1:, :] - gcum) * (seg == seg[..., -1:])[..., None]
        else:
            gcum = jnp.cumsum(gc, axis=-1)  # (B, H, C)
            diff = gcum[..., :, None] - gcum[..., None, :]
            decay = jnp.exp(jnp.where(lower & same, diff, -jnp.inf))
            a = jnp.einsum("bhik,bhjk->bhij", kb, kc, precision=_HI)
            a = jnp.where(strictly_lower, a * decay, 0.0)
            qk = jnp.einsum("bhik,bhjk->bhij", qc, kc, precision=_HI) * decay
            reach = (jnp.exp(gcum) * (seg == 0))[..., None]
            tail = (jnp.exp(gcum[..., -1:] - gcum) * (seg == seg[..., -1:]))[..., None]
        solve = _unit_lower_inverse(a)
        u = jnp.matmul(solve, vc * bc[..., None], precision=_HI)
        w = jnp.matmul(solve, kb * reach, precision=_HI)
        v_new = u - jnp.matmul(w, s, precision=_HI)  # (B, H, C, dv)
        out = jnp.matmul(qc * reach, s, precision=_HI) + jnp.matmul(
            qk, v_new, precision=_HI
        )
        # what is left at the chunk's end: the carried state if no
        # reset fell in the chunk, and the writes of the last segment
        s = s * jnp.swapaxes(reach[..., -1:, :], -1, -2) + jnp.einsum(
            "bhjk,bhjv->bhkv", kc * tail, v_new, precision=_HI
        )
        return s, out

    # a decay a channel: a chunk's scaled operands and products (a key's
    # row once a sub-block, 0.27 GB a layer of 32 heads at 16 streams of
    # 256 tokens) are made again in the backward pass, a chunk at a time,
    # and the scan keeps the chunks' start states and inputs alone
    state, outs = jax.lax.scan(
        jax.checkpoint(one_chunk) if channel else one_chunk, state,
        (qs, ks, vs, gs, betas, rs)
    )
    # (n, B, H, C, dv) -> (B, T, H, dv)
    outs = jnp.moveaxis(jnp.moveaxis(outs, 0, 1), 2, 3)
    return outs.reshape(b, t, h, dv), state
