"""The selective scan of Mamba-1 (Gu & Dao, arXiv:2312.00752, section
3.2 and algorithm 2) as ONE op in two forms that are the same function:
per channel ``c`` and state ``n``

    S_t[n, c] = exp(dt_t[c] A[n, c]) S_{t-1}[n, c] + dt_t[c] B_t[n] u_t[c]
    y_t[c]    = sum_n S_t[n, c] C_t[n]

a decay a (channel, state), which is why ``ops/ssd.py`` does not serve:
its chunked form turns ONE scalar decay a head into ``(chunk, chunk)``
matrices, and a decay that differs along both axes of the state has no
such form. Everything here is float32 and element-wise (no product
enters the MXU): the state is an accumulator over the episode, and the
PPO ratio divides one form by the other.

Layout. The state is ``(streams, N, channels)``: the CHANNELS on the
lanes (5,120 = 40 whole tiles) and the ``N`` states on the sublanes, so
that the device pads nothing (``(streams, channels, 16)`` would pad 16
lanes to 128, eight times the bytes); ``A`` lies the same way, ``(N,
channels)``.

- :func:`selective_step`: one token, state in and state out. XLA's text
  on every backend: one token moves the state once each way, which is
  the form's floor.
- :func:`selective_scan`: a fragment from a stored state with ``resets``
  inside. A fragment's states, ``(streams, T, N, channels)``, are 1.34 GB
  a layer at 16 x 256 x 16 x 5,120 and are never alive in HBM.

**The fragment form has two lowerings of one algorithm**, picked by what
the code can see when it is traced (:func:`_kernel_applies`), never by
an option:

- :func:`selective_scan_kernel`, two Pallas (Mosaic) kernels under one
  ``custom_vjp``, where the default backend is a TPU, the operands are
  float32, the channels whole 128-lane tiles, ``N`` whole 8-sublane
  tiles and ``T`` whole chunks. A grid step is one stream and one tile
  of channels (``_TILE``); its ``(N, tile)`` state stays in registers
  and VMEM over the fragment's tokens, so the matrix crosses HBM once in
  and once out a FRAGMENT. ``B`` and ``C`` reach the kernels repeated
  along the lanes, ``(streams, T, N, 128)``, made by XLA and fetched once
  a stream: on the chip a token's ``N`` numbers cannot be turned from a
  row into a column without a transpose a token. **The backward kernel
  holds** the tile's ``T + 1`` states in VMEM (16.8 MB at 257 x 16 x
  1,024): it runs the fragment forward again from the stored state, then
  the tokens from the last to the first, so the forward pass stores
  nothing for it but its operands; ``dB`` / ``dC`` come back with their
  lanes kept, summed over a stream's tiles of channels inside the kernel
  and over the lanes by XLA, ``dA`` a stream's share. **What bounds it**
  is the vector unit, not HBM: an ``exp``, a select and nine more
  operations a (token, channel, state) forward, about three times that
  backward, and a sum down the sublanes a (token, channel) (PERF.md
  section 6 has the measured rate).
- :func:`_scan_text`, ``jax.numpy``, everywhere else (the CPU, odd
  sizes, another precision): the state rides the carry of a scan over
  the tokens, ``chunk`` tokens under one ``jax.checkpoint``, so that the
  backward pass holds the states at the chunks' starts (``T / chunk``)
  and recomputes one chunk's (``chunk``) at a time. It is the statement
  of the function and the kernel's reference. On a TPU its carry lives
  in HBM: the matrix moves once a TOKEN each way.

``ray_tpu_selective_scan_lowerings_total{form="step" | "fragment" |
"kernel"}`` counts, at trace time, which one each traced scan took
(``fragment`` is the text)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import backend
from ray_tpu.telemetry import metrics

# tokens whose states the text's backward pass holds at once; the kernel
# takes fragments of whole chunks
_CHUNK = 16
_LANES, _ROWS = 128, 8
# channels a grid step of the kernels holds: on the v5e at the Phi-4 cell's
# size 1,024 ran the forward in 0.73 ms and the backward in 1.98, 512 in
# 0.80 / 2.11, 256 in 0.90 / 2.29 (benchmarks/profile_selective_scan.py)
_TILE = 1024
# what the backward kernel may hold of a tile's states (T + 1 of them),
# and what Mosaic is told a kernel may take of VMEM in all (the blocks
# in flight beside them: 43 MB at the Phi-4 cell's size)
_HELD_STATES_BYTES = 17 << 20
_VMEM_BYTES = 64 << 20


def _token(state, u, dt, a, b, c):
    """``state`` ``(B, N, C)``; ``u``, ``dt`` ``(B, C)``; ``a`` ``(N,
    C)``; ``b``, ``c`` ``(B, N)``."""
    new = jnp.exp(dt[:, None, :] * a) * state + (dt * u)[:, None, :] * b[:, :, None]
    return new, jnp.sum(new * c[:, :, None], axis=1)


def selective_step(state, u, dt, a, b, c):
    """One token of every stream: ``(state after, y (B, C))``."""
    metrics.inc_selective_scan_lowering("step")
    return _token(state, u, dt, a, b, c)


def selective_scan(state, u, dt, a, b, c, resets, chunk: int = _CHUNK):
    """A fragment from the stored ``state`` ``(B, N, C)``: ``u``, ``dt``
    ``(B, T, C)``, ``b``, ``c`` ``(B, T, N)``, ``resets`` ``(B, T)`` (1.0
    where a token opens an episode: its state starts from nothing).
    Returns ``(y (B, T, C), state after)``."""
    if _kernel_applies(state, u, dt, a, b, c, resets):
        metrics.inc_selective_scan_lowering("kernel")
        return selective_scan_kernel(state, u, dt, a, b, c, resets)
    metrics.inc_selective_scan_lowering("fragment")
    return _scan_text(state, u, dt, a, b, c, resets, chunk)


def _scan_text(state, u, dt, a, b, c, resets, chunk: int = _CHUNK):
    """The fragment form in ``jax.numpy``: the statement of the function,
    what the CPU and odd sizes run, and the kernel's reference."""
    t = u.shape[1]
    chunk = max(k for k in range(1, min(chunk, t) + 1) if t % k == 0)
    # time-major, a chunk a leading row
    xs = jax.tree_util.tree_map(
        lambda v: jnp.moveaxis(v, 1, 0).reshape((t // chunk, chunk) + v.shape[:1]
                                                + v.shape[2:]),
        (u, dt, b, c, resets > 0.5))

    def token(s, x):
        u_t, dt_t, b_t, c_t, fresh = x
        s = jnp.where(fresh[:, None, None], 0.0, s)
        return _token(s, u_t, dt_t, a, b_t, c_t)

    @jax.checkpoint
    def some_tokens(s, x):
        return jax.lax.scan(token, s, x)

    state, y = jax.lax.scan(some_tokens, state, xs)
    return jnp.moveaxis(y.reshape((t,) + y.shape[2:]), 0, 1), state


# -- the kernel ------------------------------------------------------------

def _tile_of(channels: int, tokens: int, states: int, tile=None) -> int:
    """Channels a grid step holds: the widest whole number of 128-lane
    tiles up to ``tile`` (``_TILE``) that divides ``channels`` and whose
    ``tokens + 1`` states the backward kernel can hold; 0 if none."""
    room = _HELD_STATES_BYTES // ((tokens + 1) * states * 4)
    widths = [w for w in range(_LANES, min(tile or _TILE, room) + 1, _LANES)
              if channels % w == 0]
    return max(widths, default=0)


def _kernel_applies(state, u, dt, a, b, c, resets) -> bool:
    """The kernel's lowering exists for a TPU (``ops/backend.is_tpu``),
    float32 operands, channels a whole number of 128-lane tiles, ``N`` a
    whole number of 8-sublane tiles, ``T`` a whole number of chunks, and
    a 128-lane tile's states of the fragment inside the backward
    kernel's room."""
    if not backend.is_tpu():
        return False
    n, channels = state.shape[-2:]
    t = u.shape[1]
    return (
        all(v.dtype == jnp.float32 for v in (state, u, dt, a, b, c, resets))
        and channels % _LANES == 0 and n % 8 == 0 and t % _CHUNK == 0
        and _tile_of(channels, t, n) > 0
    )


def _lane_tiles(width):
    return [slice(k, k + _LANES) for k in range(0, width, _LANES)]


def _row(ref, k, j, lanes):
    """Token ``8 k + j``'s row of a ``(1, T / 8, 8, tile)`` block, ``(1,
    128)``: it meets an ``(N, 128)`` tile by a broadcast along the
    sublanes. The tokens come eight a leading row because Mosaic loads
    from a sublane it knows when it compiles, and from a leading row it
    learns when it runs."""
    return ref[0, k, j : j + 1, lanes]


def _advance(s, fresh, dt, u, a, bx):
    """:func:`_token`'s first line on one ``(N, 128)`` tile, after the
    reset: the same float32 arithmetic, element for element."""
    s = jnp.where(fresh, 0.0, s)
    return jnp.exp(dt * a) * s + (dt * u) * bx


def _over_states(x):
    """The sum over the ``N`` states of an ``(N, 128)`` tile, a ``(1,
    128)`` row: down the sublanes."""
    return jnp.sum(x, axis=0, keepdims=True)


def _scan_fwd_kernel(fresh_ref, u_ref, dt_ref, bx_ref, cx_ref, a_ref, s0_ref,
                     y_ref, s1_ref):
    """One stream, one tile of channels, the fragment's tokens in turn.
    ``u``, ``dt``, ``y`` ``(1, T / 8, 8, tile)``; ``bx``, ``cx`` ``(1, T,
    N, 128)``, a token's ``B`` / ``C`` down the sublanes and repeated
    along the lanes; ``a`` ``(N, tile)``; the states ``(1, N, tile)``.
    The state is a list of ``(N, 128)`` tiles over eight tokens and the
    output block between them (a loop's carry read from a block is
    typed apart from one computed inside the loop under ``shard_map``):
    it never leaves the chip between the fragment's first token and its
    last."""
    stream = pl.program_id(0)
    tiles = _lane_tiles(u_ref.shape[-1])
    s1_ref[...] = s0_ref[...]

    def eight_tokens(k, _):
        s = [s1_ref[0, :, lanes] for lanes in tiles]
        for j in range(_ROWS):
            t = k * _ROWS + j
            fresh = fresh_ref[stream, t] != 0
            bx, cx = bx_ref[0, t], cx_ref[0, t]
            for i, lanes in enumerate(tiles):
                s[i] = _advance(s[i], fresh, _row(dt_ref, k, j, lanes),
                                _row(u_ref, k, j, lanes), a_ref[:, lanes], bx)
                y_ref[0, k, j : j + 1, lanes] = _over_states(s[i] * cx)
        for i, lanes in enumerate(tiles):
            s1_ref[0, :, lanes] = s[i]

    jax.lax.fori_loop(0, u_ref.shape[1], eight_tokens, None)


def _scan_bwd_kernel(fresh_ref, u_ref, dt_ref, bx_ref, cx_ref, a_ref, s0_ref,
                     dy_ref, ds1_ref,
                     du_ref, ddt_ref, dbx_ref, dcx_ref, da_ref, ds0_ref,
                     held_ref):
    """The same grid step backwards. First the fragment's states again
    from the stored one, into ``held`` ``(T + 1, N, tile)`` of VMEM (row
    ``t`` the state BEFORE token ``t``, row 0 the stored one); then the
    tokens from the last to the first, ``ds`` in its output block
    between eight of them. ``dbx``, ``dcx`` ``(1, T, N, 128)`` keep the
    lanes (the caller sums them) and are summed over the stream's tiles
    of channels, which is why that grid axis is sequential; ``da`` ``(1,
    N, tile)`` is the stream's share. A reset stops the gradient where
    it stopped the state."""
    stream, part = pl.program_id(0), pl.program_id(1)
    groups = u_ref.shape[1]
    tiles = _lane_tiles(u_ref.shape[-1])

    @pl.when(part == 0)
    def _():
        dbx_ref[...] = jnp.zeros_like(dbx_ref)
        dcx_ref[...] = jnp.zeros_like(dcx_ref)

    held_ref[0] = s0_ref[0]

    def eight_tokens(k, _):
        s = [held_ref[k * _ROWS, :, lanes] for lanes in tiles]
        for j in range(_ROWS):
            t = k * _ROWS + j
            fresh = fresh_ref[stream, t] != 0
            bx = bx_ref[0, t]
            for i, lanes in enumerate(tiles):
                s[i] = _advance(s[i], fresh, _row(dt_ref, k, j, lanes),
                                _row(u_ref, k, j, lanes), a_ref[:, lanes], bx)
                held_ref[t + 1, :, lanes] = s[i]

    jax.lax.fori_loop(0, groups, eight_tokens, None)
    da_ref[...] = jnp.zeros_like(da_ref)
    ds0_ref[...] = ds1_ref[...]

    def eight_tokens_back(back, _):
        ds = [ds0_ref[0, :, lanes] for lanes in tiles]
        k = groups - 1 - back
        for j in reversed(range(_ROWS)):
            t = k * _ROWS + j
            fresh = fresh_ref[stream, t] != 0
            bx, cx = bx_ref[0, t], cx_ref[0, t]
            db = dc = None
            for i, lanes in enumerate(tiles):
                dt, u = _row(dt_ref, k, j, lanes), _row(u_ref, k, j, lanes)
                dy, a = _row(dy_ref, k, j, lanes), a_ref[:, lanes]
                before = jnp.where(fresh, 0.0, held_ref[t, :, lanes])
                decay = jnp.exp(dt * a)
                d = ds[i] + dy * cx  # everything that reaches S_t
                dc_i, db_i = held_ref[t + 1, :, lanes] * dy, d * (dt * u)
                dc, db = (dc_i, db_i) if dc is None else (dc + dc_i, db + db_i)
                # through dt_t u_t B_t, and through exp(dt_t A)
                d_write = _over_states(d * bx)
                d_log = d * before * decay
                du_ref[0, k, j : j + 1, lanes] = d_write * dt
                ddt_ref[0, k, j : j + 1, lanes] = (
                    d_write * u + _over_states(d_log * a))
                da_ref[0, :, lanes] += d_log * dt
                ds[i] = jnp.where(fresh, 0.0, d * decay)
            dbx_ref[0, t] += db
            dcx_ref[0, t] += dc
        for i, lanes in enumerate(tiles):
            ds0_ref[0, :, lanes] = ds[i]

    jax.lax.fori_loop(0, groups, eight_tokens_back, None)


def _rows(v):
    """``(B, T, C)`` with the tokens eight a leading row (:func:`_row`):
    the same bytes in the same places on the chip."""
    return v.reshape(v.shape[0], v.shape[1] // _ROWS, _ROWS, v.shape[2])


def _along_lanes(v):
    """``B`` / ``C`` ``(B, T, N)`` as the kernels read them: ``(B, T, N,
    128)``, a token's ``N`` numbers down the sublanes, each repeated
    along the lanes (a column cannot be had from a row on the chip
    without a transpose a token)."""
    return jnp.broadcast_to(v[..., None], v.shape + (_LANES,))


def _scan_call(kernel, operands, more, outs, scratch, *, tile, interpret, name):
    """One of the two kernels over ``(streams, channels / tile)``.
    ``operands`` are :func:`selective_scan`'s; ``more`` what the backward
    kernel reads besides and ``outs`` the results, both as ``(how it is
    blocked, array or shape)``: ``rows`` ``(B, T / 8, 8, C)``, a stream's
    rows of a tile of channels; ``lanes`` ``(B, T, N, 128)``, a stream's
    (every tile of channels reads the one block, fetched once a stream);
    ``state`` ``(B, N, C)`` and ``a`` ``(N, C)``, a tile."""
    from ray_tpu import sharding as sharding_lib

    state, u, dt, a, b, c, resets = operands
    blocked = {
        "rows": lambda shape: pl.BlockSpec(
            (1,) + tuple(shape[1:3]) + (tile,), lambda i, j, _: (i, 0, 0, j)),
        "lanes": lambda shape: pl.BlockSpec(
            (1,) + tuple(shape[1:]), lambda i, j, _: (i, 0, 0, 0)),
        "state": lambda shape: pl.BlockSpec(
            (1, shape[1], tile), lambda i, j, _: (i, 0, j)),
        "a": lambda shape: pl.BlockSpec((shape[0], tile), lambda i, j, _: (0, j)),
    }
    ins = [("rows", _rows(u)), ("rows", _rows(dt)), ("lanes", _along_lanes(b)),
           ("lanes", _along_lanes(c)), ("a", a), ("state", state), *more]
    # the first token of an episode, a scalar the kernels read as they go
    fresh = (resets > 0.5).astype(jnp.int32)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(u.shape[0], u.shape[-1] // tile),
            in_specs=[blocked[how](v.shape) for how, v in ins],
            out_specs=[blocked[how](shape) for how, shape in outs],
            scratch_shapes=scratch,
        ),
        # inside a ``shard_map`` the results vary over the axes the
        # operands do
        out_shape=[
            jax.ShapeDtypeStruct(
                shape, jnp.float32,
                vma=sharding_lib.vma_of((operands, [v for _, v in more])))
            for _, shape in outs
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            # a stream's tiles of channels sum into one dbx / dcx block
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES,
        ),
        name=name,
    )(fresh, *(v for _, v in ins))


# A ``jit`` of their own, so that a program with many call sites (two
# layers, the forward pass, its recomputation and the backward pass, the
# standalone learn program and the fused one) traces and lowers the
# kernels once a shape.
@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _kernel_fwd(*operands, tile, interpret):
    state, u = operands[:2]
    y, after = _scan_call(
        _scan_fwd_kernel, operands, [],
        [("rows", _rows(u).shape), ("state", state.shape)], [],
        tile=tile, interpret=interpret, name="selective_scan_fwd")
    return y.reshape(u.shape), after


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _kernel_bwd(*operands_and_cotangents, tile, interpret):
    *operands, dy, dafter = operands_and_cotangents
    state, u, _, _, b, _, resets = operands
    rows, lanes = _rows(u).shape, b.shape + (_LANES,)
    du, ddt, dbx, dcx, da, dstate = _scan_call(
        _scan_bwd_kernel, operands, [("rows", _rows(dy)), ("state", dafter)],
        [("rows", rows), ("rows", rows), ("lanes", lanes), ("lanes", lanes),
         ("state", state.shape), ("state", state.shape)],
        [pltpu.VMEM((b.shape[1] + 1, b.shape[2], tile), jnp.float32)],
        tile=tile, interpret=interpret, name="selective_scan_bwd")
    return (dstate, du.reshape(u.shape), ddt.reshape(u.shape),
            jnp.sum(da, axis=0), jnp.sum(dbx, axis=-1), jnp.sum(dcx, axis=-1),
            jnp.zeros_like(resets))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _kernel_scan(tile, interpret, *operands):
    return _kernel_fwd(*operands, tile=tile, interpret=interpret)


def _kernel_scan_fwd(tile, interpret, *operands):
    return _kernel_scan(tile, interpret, *operands), operands


def _kernel_scan_bwd(tile, interpret, operands, cotangents):
    return _kernel_bwd(*operands, *cotangents, tile=tile, interpret=interpret)


_kernel_scan.defvjp(_kernel_scan_fwd, _kernel_scan_bwd)


def selective_scan_kernel(state, u, dt, a, b, c, resets, *, tile=None,
                          interpret=False):
    """:func:`selective_scan` as two Pallas calls under one
    ``custom_vjp``, for operands :func:`_kernel_applies` admits.
    ``tile`` caps the channels a grid step holds (``_TILE``);
    ``interpret`` runs the kernels in the Pallas interpreter (the CPU
    tests): nothing upstream passes either."""
    tile = _tile_of(u.shape[-1], u.shape[1], state.shape[-2], tile)
    return _kernel_scan(tile, interpret, state, u, dt, a, b, c, resets)
