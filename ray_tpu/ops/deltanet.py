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

**Each form has two lowerings of one algorithm**, picked by what the
code can see when it is traced, never by an option. The one-token form:

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

The fragment form with a decay a HEAD:

- :func:`gated_delta_chunked_kernel`, two Pallas kernels under one
  ``custom_vjp``, where the default backend is a TPU, the operands are
  float32, ``dk`` and ``dv`` whole 128-lane tiles and the chunks fill
  whole 128-row tiles, alone or side by side
  (:func:`_chunked_kernel_applies`). A grid step is a stream and a
  block of heads; a head's state, its chunks and their ``(C, C)``
  products stay in VMEM over the fragment, every product on the MXU at
  precision highest, and ``exp(G_i - G_j)`` is at most 1 wherever the
  masks keep it, whatever the decay. What bounds it is the MXU's six
  bfloat16 passes a float32 product, not HBM (PERF.md section 6).
- :func:`_chunked_text`, the ``lax.scan`` over chunks in ``jax.numpy``,
  everywhere else (the CPU, odd sizes, another precision): a fusion a
  product, whose operands and ``(C, C)`` results cross HBM. It is the
  statement of the function and the kernels' reference.

A decay a CHANNEL keeps the text on every backend: another algebra
(sub-blocks under a bounded gate), separated on purpose (PERF.md, PR 64).

``ray_tpu_deltanet_step_lowerings_total{path="kernel"|"xla",
decay="head"|"channel"}`` and ``ray_tpu_deltanet_chunked_lowerings_total``
(the same labels) count, at trace time, which one each traced one-token
and fragment form took and for which decay.

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
    decay = "channel" if g.ndim == 4 else "head"
    if _chunked_kernel_applies(state, q, k, v, g, beta, chunk):
        telemetry_metrics.inc_deltanet_chunked_lowering("kernel", decay)
        return gated_delta_chunked_kernel(state, q, k, v, g, beta, resets, chunk)
    telemetry_metrics.inc_deltanet_chunked_lowering("xla", decay)
    return _chunked_text(state, q, k, v, g, beta, resets, chunk)


def _chunked_text(state, q, k, v, g, beta, resets=None, chunk: int = 64):
    """:func:`gated_delta_chunked` in ``jax.numpy``: the statement of the
    function, what the CPU, odd sizes and a decay a channel run, and the
    kernels' reference."""
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


# -- the fragment form with a decay a head, on a kernel pair ----------------

# rows of a fragment a step of the kernels' walk takes at once: whole
# chunks, and where a chunk is shorter than a 128-row tile as many of
# them side by side as fill one (two chunks of 64 are ONE block-diagonal
# (128, 128) problem for everything a state does not enter: the MXU pays
# by the tile, so the solve of two chunks costs what the solve of one does)
_STEP_ROWS = 128
# rows of the block of per-token numbers a grid step reads, tokens on the
# lanes: the chunk's running log-decay, beta, the segment number, the
# decay from the chunk's start to the token (0 after a reset), the decay
# from the token to the chunk's end (0 before the last reset); three spare
_TOKEN_ROWS = 8
# heads a grid step of the chunked kernels holds, a leading axis of every
# array in them (on the v5e at the cell's size 1 a step ran the forward
# kernel in 2.10 ms a call and the backward in 1.38, 2 in 1.44 / 1.11, 4 in
# 1.32 / 1.04, 8 in 1.30 / 1.00, each with the solve's two products a power
# stacked on one weight tile, 2% of the forward, which the loop kept gives
# up: 1.35 / 1.04 at 4; benchmarks/profile_delta_rule.py)
_CHUNKED_HEADS = 4
_VMEM_BYTES = 64 << 20


def _chunks_a_step(c: int, n: int) -> int:
    """Chunks of ``c`` rows, of a fragment's ``n``, that one step of the
    kernels' walk takes side by side."""
    return max(p for p in range(1, max(1, _STEP_ROWS // c) + 1) if n % p == 0)


def _chunked_kernel_applies(state, q, k, v, g, beta, chunk) -> bool:
    """The kernel pair's lowering exists for a TPU (``ops/backend.is_tpu``),
    a decay a HEAD (``g`` of rank 3; a decay a channel is another
    algebra and keeps the text), float32 operands, ``dk`` and ``dv``
    whole 128-lane tiles, and chunks that fill whole 128-row tiles,
    alone or side by side."""
    if not backend.is_tpu() or g.ndim != 3:
        return False
    t, dk, dv = q.shape[1], q.shape[-1], v.shape[-1]
    c = min(int(chunk), t)
    if t % c or c % 8:
        return False
    return (
        all(x.dtype == jnp.float32 for x in (state, q, k, v, g, beta))
        and dk % 128 == 0 and dv % 128 == 0
        and (c * _chunks_a_step(c, t // c)) % _STEP_ROWS == 0
    )


def _mxu(a, b, contract):
    """A float32 product a head on the MXU at precision highest (six
    bfloat16 passes), float32 accumulation. ``a``, ``b`` ``(heads, .,
    .)``."""
    return jax.lax.dot_general(
        a, b, (contract, ((0,), (0,))), precision=_HI,
        preferred_element_type=jnp.float32)


def _nn(a, b):  # a @ b, a head
    return _mxu(a, b, ((2,), (1,)))


def _nt(a, b):  # a @ b.T
    return _mxu(a, b, ((2,), (2,)))


def _tn(a, b):  # a.T @ b
    return _mxu(a, b, ((1,), (1,)))


def _turned(x):
    return jnp.swapaxes(x, 1, 2)


def _rows_above(x, at, of):
    """``x`` ``(heads, c, w)`` as chunk ``at``'s rows of ``of`` chunks
    side by side, zeros elsewhere: a block-diagonal matrix's rows of one
    chunk meet it over all ``of * c`` columns, whole tiles."""
    if of == 1:
        return x
    zeros = jnp.zeros_like(x)
    return jnp.concatenate([x if i == at else zeros for i in range(of)], axis=1)


def _lanes(col, width):
    """Columns ``(heads, p, 1)`` along ``width`` lanes."""
    return jnp.broadcast_to(col, col.shape[:2] + (width,))


def _row_at(col, at, width):
    """Row ``at`` of columns ``(heads, p, 1)`` along ``width`` lanes,
    ``(heads, 1, width)``: it meets a matrix by a broadcast down the
    sublanes. A sum under a mask, because Mosaic has no broadcast of one
    number both ways at once."""
    rows = jax.lax.broadcasted_iota(jnp.int32, col.shape[:2] + (width,), 1)
    return jnp.sum(jnp.where(rows == at, _lanes(col, width), 0.0), axis=1,
                   keepdims=True)


def _step_operands(rows, q, k, v, c):
    """What a step's ``p`` rows (``p / c`` chunks side by side) give on
    the vector unit before any product, for every head of the grid step
    at once (a leading axis: the heads share nothing, and an operation
    written once for all of them is one to trace and, on the chip, a
    link of each head's chain in turn): the per-token columns, the decay
    matrix under its masks and the scaled operands. ``rows`` ``(heads,
    8, p)`` (``_TOKEN_ROWS``); ``q``, ``k`` ``(heads, p, dk)``; ``v``
    ``(heads, p, dv)``."""
    heads, p = q.shape[:2]
    # the per-token rows as columns: one transpose of a square tile a head
    turned = _turned(jnp.concatenate(
        [rows, jnp.zeros((heads, p - _TOKEN_ROWS, p), jnp.float32)], axis=1))
    gcum, beta, seg, reach, tail = (turned[:, :, r : r + 1] for r in range(5))
    i = jax.lax.broadcasted_iota(jnp.int32, (p, p), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (p, p), 1)
    within = i >= j
    for edge in range(c, p, c):
        # of one chunk: on one side of every chunk's edge (Mosaic compares
        # no two masks)
        after_i, after_j = i >= edge, j >= edge
        within = within & ((after_i & after_j) | ~(after_i | after_j))
    lower = (_lanes(seg, p) == rows[:, 2:3]) & within
    # exp(G_i - G_j), at most 1 wherever the mask keeps it
    diff = _lanes(gcum, p) - rows[:, 0:1]
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
    kb = k * beta
    return dict(
        i=i, j=j, decay=decay, kb=kb, vb=v * beta, qr=q * reach,
        kbr=kb * reach, kt=k * tail, beta=beta, reach=reach, tail=tail)


def _step_products(m, q, k, c):
    """The text's ``a``, ``qk`` and ``solve`` of a step as block-diagonal
    ``(heads, p, p)`` matrices, from :func:`_step_operands`' ``m``. ``k
    beta`` and ``q`` meet ``k^T`` as ONE left operand of ``2 p`` rows."""
    p = q.shape[1]
    i, j = m["i"], m["j"]
    products = _nt(jnp.concatenate([m["kb"], q], axis=1), k)  # (heads, 2p, p)
    a = jnp.where(i > j, products[:, :p] * m["decay"], 0.0)
    qk = products[:, p:] * m["decay"]
    # (I + a)^-1 = (I - a)(I + a^2)(I + a^4)...: ``a^c = 0`` a chunk
    solve, power, span = (i == j).astype(jnp.float32) - a, a, 2
    while span < c:
        power = _nn(power, power)
        solve = solve + _nn(solve, power)
        span *= 2
    return a, qk, solve


def _walk(steps, body):
    """``body(s)`` for each step: a loop on the chip, and no loop at all
    for a fragment of one step (the cell's: two chunks of 64)."""
    if steps == 1:
        body(0)
    else:
        jax.lax.fori_loop(0, steps, lambda s, _: body(s), None)


def _step_rows(s, p):
    return pl.ds(s * p, p) if isinstance(s, int) else pl.ds(pl.multiple_of(s * p, p), p)


def _by_head(ref, at, heads):
    """A step's rows of a ``(1, T, heads * width)`` block, a head a
    leading row: ``(heads, p, width)``."""
    width = ref.shape[2] // heads
    return jnp.stack(
        [ref[0, at, hh * width : (hh + 1) * width] for hh in range(heads)])


def _to_heads(ref, at, x):
    """``x`` ``(heads, p, width)`` into a step's rows of a ``(1, T, heads
    * width)`` block."""
    width = x.shape[2]
    for hh in range(x.shape[0]):
        ref[0, at, hh * width : (hh + 1) * width] = x[hh]


def _chunked_fwd_kernel(rows_ref, q_ref, k_ref, v_ref, s0_ref,
                        o_ref, s1_ref, starts_ref, kept_ref, news_ref, *, c):
    """One stream, a block of heads, the fragment's chunks in turn.
    ``rows`` ``(1, heads, steps, 8, p)``; ``q``, ``k``, ``v``, ``o`` ``(1,
    T, heads * width)``, the heads' lanes of the stream's tokens; the
    states ``(1, heads, dk, dv)``. For the backward kernel: ``starts``
    ``(1, heads, n, dk, dv)``, the state each chunk began from; ``kept``
    ``(1, heads, steps, 3, p, p)``, a step's ``a``, ``qk`` and ``solve``
    (ten of a step's eighteen products are the solve: the backward pass
    reads it, and makes no product of the forward pass again); ``news``
    ``(1, T, heads * dv)``, the tokens' writes. The states sit in their
    output block between steps (a loop's carry read from a block is
    typed apart from one computed inside the loop under ``shard_map``)."""
    heads, steps, p = rows_ref.shape[1], rows_ref.shape[2], rows_ref.shape[4]
    dv = s0_ref.shape[3]
    per = p // c
    s1_ref[...] = s0_ref[...]

    def some_chunks(s):
        at = _step_rows(s, p)
        q, k = _by_head(q_ref, at, heads), _by_head(k_ref, at, heads)
        m = _step_operands(rows_ref[0, :, s], q, k, _by_head(v_ref, at, heads), c)
        a, qk, solve = _step_products(m, q, k, c)
        kept_ref[0, :, s, 0], kept_ref[0, :, s, 1], kept_ref[0, :, s, 2] = a, qk, solve
        state, outs, news = s1_ref[0], [], []
        for ci in range(per):
            own = slice(ci * c, (ci + 1) * c)
            starts_ref[0, :, s * per + ci] = state
            # what the start state gives the reads and takes from the writes
            from_state = _nn(
                jnp.concatenate([m["qr"][:, own], m["kbr"][:, own]], axis=1), state)
            v_new = _nn(solve[:, own],
                        _rows_above(m["vb"][:, own] - from_state[:, c:], ci, per))
            news.append(v_new)
            outs.append(from_state[:, :c]
                        + _nn(qk[:, own], _rows_above(v_new, ci, per)))
            # the carried state if no reset fell in the chunk, and the
            # writes of its last segment
            state = state * _row_at(m["reach"], (ci + 1) * c - 1, dv) + _tn(
                m["kt"][:, own], v_new)
        _to_heads(o_ref, at, jnp.concatenate(outs, axis=1))
        _to_heads(news_ref, at, jnp.concatenate(news, axis=1))
        s1_ref[0] = state

    _walk(steps, some_chunks)


def _chunked_bwd_kernel(rows_ref, q_ref, k_ref, v_ref, starts_ref, kept_ref,
                        news_ref, do_ref, ds1_ref,
                        dq_ref, dk_ref, dv_ref, drows_ref, ds0_ref, *, c):
    """The same grid step backwards, the steps from the last to the
    first, on what the forward kernel kept: a step walks its chunks
    backwards with the states' cotangent in ITS output block, and the
    cotangents of the two ``(p, p)`` products are taken once a step, all
    chunks side by side. ``drows`` is ``rows``' cotangent (the running
    log-decay through ``exp(G_i - G_j)`` alone: ``reach`` and ``tail``
    are operands of their own, and XLA's text outside differentiates
    what made them)."""
    heads, steps, p = rows_ref.shape[1], rows_ref.shape[2], rows_ref.shape[4]
    dv = ds1_ref.shape[3]
    per = p // c
    ds0_ref[...] = ds1_ref[...]

    def some_chunks(back):
        s = steps - 1 - back
        at = _step_rows(s, p)
        q, k, v, do, v_new = (
            _by_head(ref, at, heads) for ref in (q_ref, k_ref, v_ref, do_ref, news_ref))
        m = _step_operands(rows_ref[0, :, s], q, k, v, c)
        a, qk, solve = kept_ref[0, :, s, 0], kept_ref[0, :, s, 1], kept_ref[0, :, s, 2]
        turned_solve = _turned(solve)
        through_reads = _tn(qk, do)  # (heads, p, dv)
        d_rhs, d_qr, d_kbr, d_kt = ([None] * per for _ in range(4))
        d_reach = jnp.zeros((heads, p, 1), jnp.float32)
        for ci in reversed(range(per)):
            own = slice(ci * c, (ci + 1) * c)
            start, ds = starts_ref[0, :, s * per + ci], ds0_ref[0]
            d_new = through_reads[:, own] + _nn(m["kt"][:, own], ds)
            d_rhs[ci] = _nn(turned_solve[:, own], _rows_above(d_new, ci, per))
            d_kt[ci] = _nt(v_new[:, own], ds)
            cots = jnp.concatenate([do[:, own], d_rhs[ci]], axis=1)
            to_operands = _nt(cots, start)  # (heads, 2c, dk)
            d_qr[ci], d_kbr[ci] = to_operands[:, :c], -to_operands[:, c:]
            last = (ci + 1) * c - 1
            through_carry = jnp.sum(
                jnp.sum(start * ds, axis=2, keepdims=True), axis=1, keepdims=True)
            d_reach = d_reach + jnp.where(m["i"][:, :1] == last, through_carry, 0.0)
            ds0_ref[0] = ds * _row_at(m["reach"], last, dv) + _tn(
                jnp.concatenate([m["qr"][:, own], -m["kbr"][:, own]], axis=1), cots)
        d_rhs, d_qr, d_kbr, d_kt = (
            jnp.concatenate(x, axis=1) for x in (d_rhs, d_qr, d_kbr, d_kt))
        # cotangents of ``a`` (minus d_rhs v_new^T) and of ``qk``
        over = _nt(jnp.concatenate([d_rhs, do], axis=1), v_new)  # (heads, 2p, p)
        d_a, d_qk = -over[:, :p], over[:, p:]
        to_products = jnp.concatenate(
            [jnp.where(m["i"] > m["j"], d_a * m["decay"], 0.0), d_qk * m["decay"]],
            axis=1)
        times_k = _nn(to_products, k)  # (heads, 2p, dk)
        d_kb = times_k[:, :p] + d_kbr * m["reach"]
        _to_heads(dq_ref, at, times_k[:, p:] + d_qr * m["reach"])
        _to_heads(dk_ref, at, (
            _tn(to_products, jnp.concatenate([m["kb"], q], axis=1))
            + d_kb * m["beta"] + d_kt * m["tail"]))
        _to_heads(dv_ref, at, d_rhs * m["beta"])
        over_lanes = lambda x: jnp.sum(x, axis=2, keepdims=True)
        through_decay = d_a * a + d_qk * qk
        columns = (
            over_lanes(through_decay),
            over_lanes(d_rhs * v) + over_lanes(d_kb * k),
            None,
            d_reach + over_lanes(d_qr * q) + over_lanes(d_kbr * m["kb"]),
            over_lanes(d_kt * k),
        )
        # the columns as rows, by one transpose of a square tile a head
        tile = sum(jnp.where(m["j"] == r, _lanes(col, p), 0.0)
                   for r, col in enumerate(columns) if col is not None)
        against = jnp.sum(through_decay, axis=1, keepdims=True)  # (heads, 1, p)
        row = jax.lax.broadcasted_iota(jnp.int32, (_TOKEN_ROWS, p), 0)
        drows_ref[0, :, s] = _turned(tile)[:, :_TOKEN_ROWS] - jnp.where(
            row == 0, against, 0.0)

    _walk(steps, some_chunks)


def _chunked_call(kernel, operands, outs, *, c, heads, interpret, name):
    """One of the two kernels over ``(streams, heads / heads a step)``.
    ``operands`` and ``outs`` as ``(how it is blocked, array or
    shape)``: ``tokens`` ``(B, T, H * width)``, a stream's rows of the
    step's heads' lanes; ``head`` ``(B, H, ...)`` (a state, ``rows``,
    what the forward kernel keeps), the step's heads' whole."""
    from ray_tpu import sharding as sharding_lib

    of = next(v.shape[1] for how, v in operands if how == "head")
    blocked = {
        "tokens": lambda shape: pl.BlockSpec(
            (1, shape[1], shape[2] // of * heads), lambda i, j: (i, 0, j)),
        "head": lambda shape: pl.BlockSpec(
            (1, heads) + tuple(shape[2:]),
            lambda i, j: (i, j) + (0,) * (len(shape) - 2)),
    }
    arrays = [v for _, v in operands]
    return pl.pallas_call(
        functools.partial(kernel, c=c),
        grid=(arrays[0].shape[0], of // heads),
        in_specs=[blocked[how](v.shape) for how, v in operands],
        out_specs=[blocked[how](shape) for how, shape in outs],
        # inside a ``shard_map`` the results vary over the axes the
        # operands do
        out_shape=[
            jax.ShapeDtypeStruct(shape, jnp.float32,
                                 vma=sharding_lib.vma_of(arrays))
            for _, shape in outs
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_BYTES,
        ),
        name=name,
    )(*arrays)


# A ``jit`` of their own, as the step kernel has: a program with many call
# sites (three layers; the forward pass, its recomputation and the backward
# pass; the standalone learn program and the fused one) traces and lowers
# each kernel once a shape.
@functools.partial(jax.jit, static_argnames=("c", "heads", "interpret"))
def _chunked_fwd(state, q, k, v, rows, *, c, heads, interpret):
    n, (steps, p) = q.shape[1] // c, (rows.shape[2], rows.shape[4])
    return _chunked_call(
        _chunked_fwd_kernel,
        [("head", rows), ("tokens", q), ("tokens", k), ("tokens", v),
         ("head", state)],
        [("tokens", v.shape), ("head", state.shape),
         ("head", state.shape[:2] + (n,) + state.shape[2:]),
         ("head", state.shape[:2] + (steps, 3, p, p)), ("tokens", v.shape)],
        c=c, heads=heads, interpret=interpret, name="gated_delta_chunked_fwd")


@functools.partial(jax.jit, static_argnames=("c", "heads", "interpret"))
def _chunked_bwd(q, k, v, rows, starts, kept, news, do, ds1, *, c, heads, interpret):
    return _chunked_call(
        _chunked_bwd_kernel,
        [("head", rows), ("tokens", q), ("tokens", k), ("tokens", v),
         ("head", starts), ("head", kept), ("tokens", news), ("tokens", do),
         ("head", ds1)],
        [("tokens", q.shape), ("tokens", k.shape), ("tokens", v.shape),
         ("head", rows.shape), ("head", ds1.shape)],
        c=c, heads=heads, interpret=interpret, name="gated_delta_chunked_bwd")


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _chunked_rule(c, heads, interpret, state, q, k, v, rows):
    return _chunked_fwd(state, q, k, v, rows, c=c, heads=heads, interpret=interpret)[:2]


def _chunked_rule_fwd(c, heads, interpret, state, q, k, v, rows):
    o, after, *kept = _chunked_fwd(
        state, q, k, v, rows, c=c, heads=heads, interpret=interpret)
    return (o, after), (q, k, v, rows, *kept)


def _chunked_rule_bwd(c, heads, interpret, kept, cotangents):
    dq, dk, dv, drows, dstate = _chunked_bwd(
        *kept, *cotangents, c=c, heads=heads, interpret=interpret)
    return dstate, dq, dk, dv, drows


_chunked_rule.defvjp(_chunked_rule_fwd, _chunked_rule_bwd)


def gated_delta_chunked_kernel(state, q, k, v, g, beta, resets=None,
                               chunk: int = 64, *, heads=None, interpret=False):
    """:func:`gated_delta_chunked` with a decay a head as two Pallas
    calls under one ``custom_vjp``, for operands
    :func:`_chunked_kernel_applies` admits. A grid step is one stream and
    a block of heads (``_CHUNKED_HEADS``, a leading axis of every array
    in the kernels: they share nothing, and the solve is a chain of
    products each of which waits for the one before, so the MXU takes
    one head's product while the vector unit cuts another's operands
    into bfloat16 pieces): a head's ``(dk, dv)`` state stays in VMEM
    over the fragment's chunks beside the chunks' ``(C, C)`` products,
    so a fragment moves its operands in and its outputs out, and for
    the backward pass its chunks' start states, the ``(C, C)`` products
    and the tokens' writes, and nothing else. The same algebra as the
    text, every product float32 at precision highest; what XLA's text
    keeps here is what is a number a token (the running log-decay, the
    segment numbers, the two decays against the chunk's ends), which
    the kernels read as one ``(8, rows)`` block a step. ``heads`` caps
    the heads a grid step holds and ``interpret`` runs the kernels in
    the Pallas interpreter (the CPU tests): nothing upstream passes
    either."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    c = min(int(chunk), t)
    if t % c:
        raise ValueError(f"fragment of {t} tokens is not a multiple of {c}")
    n = t // c
    p = c * _chunks_a_step(c, n)
    if resets is None:
        resets = jnp.zeros((b, t), jnp.float32)
    by_chunk = lambda x: x.reshape((b, n, c) + x.shape[2:])
    gcum = jnp.cumsum(by_chunk(g), axis=2)  # (B, n, C, H)
    seg = jnp.cumsum((by_chunk(resets) > 0.5).astype(jnp.float32), axis=2)[..., None]
    # the start state reaches the tokens before the first reset, and the
    # tokens of the last segment reach the chunk's end
    reach = jnp.exp(gcum) * (seg == 0)
    tail = jnp.exp(gcum[:, :, -1:] - gcum) * (seg == seg[:, :, -1:])
    rows = jnp.stack(
        [gcum, by_chunk(beta), jnp.broadcast_to(seg, gcum.shape), reach, tail]
        + [jnp.zeros_like(gcum)] * (_TOKEN_ROWS - 5))  # (8, B, n, C, H)
    # (B, H, steps, 8, rows a step)
    rows = rows.reshape(_TOKEN_ROWS, b, t // p, p, h).transpose(1, 4, 2, 0, 3)
    o, state = _chunked_rule(
        c, math.gcd(h, heads or _CHUNKED_HEADS), interpret, state,
        q.reshape(b, t, h * dk), k.reshape(b, t, h * dk), v.reshape(b, t, h * dv),
        rows)
    return o.reshape(b, t, h, dv), state
