"""The Mamba-2 state-space recurrence (Dao & Gu, arXiv:2405.21060) in
two forms that are the same function of the same inputs.

Per head, with a ``(P, N)`` state ``S`` (``P`` the head size, ``N`` the
state size), a step size ``dt_t > 0``, a scalar ``A < 0`` a head, and
``B_t``, ``C_t`` of ``N`` numbers::

    S   <- exp(dt_t A) S + dt_t x_t B_t^T
    y_t  = S C_t

(the skip ``D x_t`` and the gate are the caller's).

**The group axis.** ``B`` and ``C`` come in one of two shapes, told
apart by their rank beside ``dt``'s: ``(..., N)``, rows every head
shares (``mamba_n_groups`` 1: Granite 4.0-H), or ``(..., G, N)``, a row
a GROUP of ``H / G`` consecutive heads (head ``h`` reads group ``h //
(H / G)``; ``n_groups`` 8 of 64 heads: Nemotron-H). All three forms
take both; the shared form lowers to the program it lowered to before
there was a group axis.

- :func:`ssd_step` is that recurrence for ONE token: the rollout
  lane's decode step (state in, state out).
- :func:`ssd_chunked` computes a fragment of ``T`` tokens from a start
  state in chunks of ``C``: inside a chunk token ``i`` reads token
  ``j <= i`` through ``exp(sum_{j < l <= i} dt_l A) (C_i . B_j) dt_j``
  (the semiseparable matrix, all matrix products) and only the
  chunk-end state is carried: the learn program's form.

**The one-token form has two lowerings of one algorithm**, picked by
what the code can see when it is traced, never by an option:

- :func:`ssd_step_kernel`, a Pallas (Mosaic) kernel, where the default
  backend is a TPU and the state is a run's STACKED float32 leaf
  ``(streams, layers, H, P, N)`` with ``N`` whole 128-lane tiles
  (:func:`_kernel_applies`). It takes the leaf whole and the layer's
  index: the index rides as a scalar-prefetch operand into the
  matrices' index map and the leaf's buffer is the call's own output
  (``input_output_aliases``), so only that layer's blocks cross HBM,
  ONCE in and ONCE out, 8 bytes an element, and the other layers lie
  untouched in the same buffer. (Fed a layer's slice and followed by an
  update of the slice, a kernel would make the compiler materialise
  the slice and copy it back: four passes.) A block of heads of one
  stream sits in VMEM while both lines run on it; ``y`` is read from
  the block just written, its sum over the lanes on the MXU
  (:func:`_lane_sums`), which is idle otherwise.
- :func:`_step_body`, the two lines in ``jax.numpy``, everywhere else
  (the CPU, odd sizes, a state without a layer axis; on a stacked leaf
  around a dynamic slice and its update in place). It is the statement
  of the function and the kernel's reference. XLA does not put the
  in-place update of a slice and a reduction over its result into one
  fusion: on a TPU this body writes the matrices in one pass (67 MB in
  and out a layer in 102 us at the granite cell's size) and reads the
  OLD matrices again in a second (34 MB, 48 us) to compute ``y``.

``ray_tpu_ssm_step_lowerings_total{path="kernel"|"xla"}`` counts, at
trace time, which one each traced one-token form took.

**Resets.** ``resets`` (1.0 where a token begins a new episode) zero
the state before that token. In the chunked form a reset splits its
chunk into segments: products across a segment boundary are masked
out, and the start state reaches only the tokens before the first
reset. The one-token form has no argument for it; its caller zeroes
the rows first (``SequenceLM.reset_state``), as for ``ops/deltanet.py``.

Everything here is float32 at precision "highest" (the kernel:
float32 multiply-adds on the VPU, and a sum whose addends reach the
MXU as exact pieces): the state is an accumulator over the whole
episode, and the PPO ratio divides what the chunked form says by what
the recurrence said.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import backend
from ray_tpu.telemetry import metrics as telemetry_metrics

_HI = jax.lax.Precision.HIGHEST

# heads of one stream a grid step holds in VMEM: 1 MB of matrices in and
# out at 64 x 128 (on the v5e 16 heads were 1.1% slower, 64 0.7% faster)
_KERNEL_HEADS = 32


def _groups(b, dt) -> int:
    """``G`` where ``b`` has the group axis ``(..., G, N)``, one rank
    above ``dt``'s ``(..., H)``; 0 where every head shares ``(..., N)``."""
    return b.shape[-2] if b.ndim > dt.ndim else 0


def ssd_step(state, x, dt, a, b, c, layer=None):
    """One token. ``state`` ``(..., H, P, N)``; ``x`` ``(..., H, P)``;
    ``dt`` ``(..., H)``; ``a`` ``(H,)``; ``b``, ``c`` ``(..., N)`` or
    ``(..., G, N)`` (the module's docstring). Returns ``(state, y)``
    with ``y`` ``(..., H, P)``.

    With ``layer`` (an int32 scalar) ``state`` is a run's stacked leaf
    ``(B, layers, H, P, N)`` of which that layer's matrices take the
    step: the leaf comes back whole, the other layers as they were."""
    if layer is not None and _kernel_applies(state, _groups(b, dt) or 1):
        telemetry_metrics.inc_ssm_step_lowering("kernel")
        return ssd_step_kernel(state, layer, x, dt, a, b, c)
    telemetry_metrics.inc_ssm_step_lowering("xla")
    if layer is None:
        return _step_body(state, x, dt, a, b, c)
    return _stacked_step_body(state, layer, x, dt, a, b, c)


def _step_body(state, x, dt, a, b, c):
    groups = _groups(b, dt)
    if groups:  # each head its group's row: (..., G, N) -> (..., H, 1, N)
        a_head = lambda v: jnp.repeat(v, dt.shape[-1] // groups, axis=-2)[..., None, :]
    else:
        a_head = lambda v: v[..., None, None, :]
    decay = jnp.exp(dt * a)[..., None, None]
    write = (dt[..., None] * x)[..., None] * a_head(b)
    state = decay * state + write
    y = jnp.sum(state * a_head(c), axis=-1)
    return state, y


def _stacked_step_body(state, layer, x, dt, a, b, c):
    """:func:`_step_body` on layer ``layer`` of a stacked leaf: a
    dynamic slice, and its update in place."""
    mine = jax.lax.dynamic_index_in_dim(state, layer, 1, keepdims=False)
    mine, y = _step_body(mine, x, dt, a, b, c)
    return jax.lax.dynamic_update_index_in_dim(
        state, mine.astype(state.dtype), layer, 1), y


def _kernel_applies(state, groups: int = 1) -> bool:
    """The kernel's lowering exists for a TPU, for a stacked float32
    leaf ``(streams, layers, H, P, N)`` with ``N`` whole 128-lane tiles
    and ``P`` and the heads OF A GROUP whole 8-sublane tiles (a matrix's
    rows; ``x`` is turned from lanes to sublanes a block of heads at a
    time, and a block lies inside one group); "a TPU" as
    ``ops/backend.is_tpu`` has it."""
    if not backend.is_tpu() or state.ndim != 5:
        return False
    heads, p, n = state.shape[-3:]
    return (
        state.dtype == jnp.float32
        and n % 128 == 0 and p % 8 == 0
        and heads % groups == 0 and (heads // groups) % 8 == 0
    )


def _lane_sums(t, ones):
    """The sums over the lanes of float32 ``t`` ``(P, N)`` as a ``(1,
    P)`` row, on the MXU at float32 accuracy: ``t`` in three bfloat16
    pieces (three times 8 bits of mantissa, all a float32 has), each
    against ``ones`` ``(8, N)`` (exact products) with float32
    accumulation; ``ones . piece^T`` puts ``P`` on the lanes. The XLU's
    lane reduction beside the column broadcast of the write held the
    kernel at 130 us a call on the v5e where a copy takes 103."""
    total = None
    for _ in range(3):
        piece = t.astype(jnp.bfloat16)
        t = t - piece.astype(jnp.float32)
        part = jax.lax.dot_general(
            ones, piece, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        total = part if total is None else total + part
    return total[:1]


def _ssd_step_kernel(layer_ref, decay_ref, dtx_ref, b_ref, c_ref, s_ref,
                     s_out_ref, y_ref):
    """One stream, a block of heads of the layer ``layer_ref`` names (the
    index maps have used it). ``s_ref`` ``(1, 1, H, P, N)``; ``decay``
    ``(1, H, N)``, a head's scalar repeated along the lanes; ``dtx``
    ``(1, H, P)`` arrives with ``P`` on the lanes and is turned once a
    block, so that a head's column broadcasts along the lanes of its
    matrix; ``b``, ``c`` ``(1, 1, N)``, the rows of the block's group
    (every head's, where there is one group). The read is taken from the
    block just written, while it is in VMEM."""
    del layer_ref
    heads, _, n = s_ref.shape[2:]
    dtx_cols = dtx_ref[0].T  # (P, H)
    decay, b, c = decay_ref[0], b_ref[0], c_ref[0]
    ones = jnp.ones((8, n), jnp.bfloat16)
    rows = []
    for h in range(heads):
        s = s_ref[0, 0, h] * decay[h : h + 1] + dtx_cols[:, h : h + 1] * b
        s_out_ref[0, 0, h] = s
        rows.append(_lane_sums(s * c, ones))
    y_ref[0] = jnp.concatenate(rows, axis=0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_step_kernel(state, layer, x, dt, a, b, c, *, interpret=False):
    """:func:`ssd_step` on layer ``layer`` of a stacked leaf as one
    Pallas call over ``(streams, heads / block)``: ``state`` ``(B,
    layers, H, P, N)`` float32, aliased in to out. ``layer`` rides as a
    scalar-prefetch operand into the matrices' index map, so only that
    layer's blocks cross HBM, once in and once out, and the other
    layers lie untouched in the same buffer. ``interpret`` runs it in
    the Pallas interpreter (the CPU tests); nothing upstream passes it.
    A ``jit`` of its own, so that a program with many call sites (the
    act, the truncation's value forward and the tail's, two runs each)
    traces and lowers the kernel once a leaf shape."""
    from ray_tpu import sharding as sharding_lib

    bsz, _, h, p, n = state.shape
    groups = _groups(b, dt)
    # a block of heads lies inside one group
    per_group = h // (groups or 1)
    heads = _KERNEL_HEADS if per_group % _KERNEL_HEADS == 0 else 8
    f32 = lambda v: v.astype(state.dtype)
    decay = jnp.broadcast_to(f32(jnp.exp(dt * a))[..., None], (bsz, h, n))
    dtx = f32(dt[..., None] * x)
    # inside a ``shard_map`` the outputs vary over the mesh axes the
    # inputs do
    vma = sharding_lib.vma_of((state, x, dt, b, c))
    rows = lambda width: pl.BlockSpec(
        (1, heads, width), lambda i, j, layer: (i, j, 0))
    if groups:
        # ``(B, G, N)``: the row of block ``j``'s group, its axis squeezed
        # out of the block (Mosaic wants a block's last two dimensions
        # whole: ``(1, N)`` of ``(B, G, 1, N)``)
        shared = pl.BlockSpec(
            (1, None, 1, n), lambda i, j, layer: (i, j * heads // per_group, 0, 0))
        row_of = lambda v: f32(v)[:, :, None]
    else:  # ``(B, N)``: the one row, for every block
        shared = pl.BlockSpec((1, 1, n), lambda i, j, layer: (i, 0, 0))
        row_of = lambda v: f32(v)[:, None]
    matrices = pl.BlockSpec(
        (1, 1, heads, p, n), lambda i, j, layer: (i, layer[0], j, 0, 0))
    return pl.pallas_call(
        _ssd_step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bsz, h // heads),
            in_specs=[rows(n), rows(p), shared, shared, matrices],
            out_specs=[matrices, rows(p)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(state.shape, state.dtype, vma=vma),
            jax.ShapeDtypeStruct(x.shape, state.dtype, vma=vma),
        ],
        # operand 5 counts the prefetched scalar
        input_output_aliases={5: 0},
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        name="ssd_step",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), decay, dtx,
      row_of(b), row_of(c), state)


def ssd_chunked(
    state,
    x,
    dt,
    a,
    b,
    c,
    resets: Optional[jnp.ndarray] = None,
    chunk: int = 256,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``T`` tokens from ``state``. ``x`` ``(B, T, H, P)``; ``dt`` ``(B,
    T, H)``; ``a`` ``(H,)``; ``b``, ``c`` ``(B, T, N)`` or ``(B, T, G,
    N)`` (the module's docstring); ``state`` ``(B, H, P, N)``; ``resets``
    ``(B, T)`` or None. ``T`` is a multiple of ``chunk`` (or shorter
    than it). Returns ``(y (B, T, H, P), state)``."""
    bsz, t, h, p = x.shape
    size = min(int(chunk), t)
    if t % size:
        raise ValueError(f"fragment of {t} tokens is not a multiple of {size}")
    n = t // size
    if resets is None:
        resets = jnp.zeros((bsz, t), jnp.float32)

    def chunks(v):  # (B, T, ...) -> (n, B, C, ...)
        return jnp.moveaxis(v.reshape((bsz, n, size) + v.shape[2:]), 1, 0)

    row = jnp.arange(size)
    lower = row[:, None] >= row[None, :]

    # the three places ``B`` and ``C`` enter: the ``C_i . B_j`` products
    # weighing a head's decays, the read of the carried state and the
    # write of the chunk's end state
    groups = _groups(b, dt)
    if groups:  # heads (B, H, ...) <-> their groups (B, G, H / G, ...)
        grouped = lambda v: v.reshape((bsz, groups, h // groups) + v.shape[2:])
        heads = lambda v: v.reshape((bsz, h) + v.shape[3:])
        products = lambda cc, bc: jnp.einsum("bign,bjgn->bgij", cc, bc, precision=_HI)
        weigh = lambda decay, cb: heads(grouped(decay) * cb[:, :, None])
        read = lambda s, cc: heads(jnp.einsum(
            "bgkpn,bcgn->bgkcp", grouped(s), cc, precision=_HI))
        write = lambda xw, bc: heads(jnp.einsum(
            "bgkcp,bcgn->bgkpn", grouped(xw), bc, precision=_HI))
    else:
        products = lambda cc, bc: jnp.einsum("bin,bjn->bij", cc, bc, precision=_HI)
        weigh = lambda decay, cb: decay * cb[:, None]
        read = lambda s, cc: jnp.einsum("bhpn,bcn->bhcp", s, cc, precision=_HI)
        write = lambda xw, bc: jnp.einsum("bhcp,bcn->bhpn", xw, bc, precision=_HI)

    def one_chunk(s, xs):
        # (B, C, H, P), (B, C, H), (B, C[, G], N) x 2, (B, C)
        xc, dtc, bc, cc, rc = xs
        g = jnp.moveaxis(dtc * a, 1, 2)  # (B, H, C) log-decays
        gcum = jnp.cumsum(g, axis=-1)
        seg = jnp.cumsum((rc > 0.5).astype(jnp.int32), axis=-1)[:, None]  # (B, 1, C)
        same = seg[..., :, None] == seg[..., None, :]  # (B, 1, C, C)
        # the start state reaches the tokens before the first reset
        reach = jnp.exp(gcum) * (seg == 0)  # (B, H, C)
        diff = gcum[..., :, None] - gcum[..., None, :]
        decay = jnp.exp(jnp.where(lower & same, diff, -jnp.inf))  # (B, H, C, C)
        cb = products(cc, bc)  # (B[, G], C, C)
        xdt = jnp.moveaxis(xc * dtc[..., None], 1, 2)  # (B, H, C, P)
        y = jnp.matmul(weigh(decay, cb), xdt, precision=_HI) + reach[
            ..., None
        ] * read(s, cc)
        # what is left at the chunk's end: the carried state if no
        # reset fell in the chunk, and the writes of the last segment
        tail = jnp.exp(gcum[..., -1:] - gcum) * (seg == seg[..., -1:])
        s = s * reach[..., -1, None, None] + write(xdt * tail[..., None], bc)
        return s, jnp.moveaxis(y, 1, 2)  # (B, C, H, P)

    state, ys = jax.lax.scan(
        one_chunk, state, tuple(chunks(v) for v in (x, dt, b, c, resets))
    )
    return jnp.moveaxis(ys, 0, 1).reshape(bsz, t, h, p), state
