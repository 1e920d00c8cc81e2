"""Device-side framestack reconstruction (deduplicated obs transfer).

Atari-style training batches are sliding-window framestacks: row n's
observation is frames [f_n .. f_{n+k-1}], so consecutive rows share
k-1 of their k frames and a naively-shipped (N, H, W, k) obs column
carries each frame k times. The reference avoids SOME of this cost
host-side (plasma stores a fragment's arrays once and workers map them
zero-copy — ``src/ray/object_manager/plasma/store.h:55``), but still
moves full stacks over the loader thread to the device
(``rllib/execution/multi_gpu_learner_thread.py``).

Here the dedup crosses the host→device boundary, where it matters most
on TPU (HBM ingest is the learner's bottleneck once compute is one
fused program): the host ships the UNIQUE frame stream plus a per-row
int32 first-frame index (k× fewer obs bytes), and the jitted learn
program rebuilds the (N, H, W, k) stacks with one gather before the
SGD nest. ``JaxPolicy`` recognizes the ``obs_frames``/``obs_frame_idx``
columns automatically (see ``policy/jax_policy.py``).

Sharding note: the frame pool rides replicated while row columns shard
over the data axis, so stacks build locally on every shard from the
shared pool — correct on any mesh, sized for the single-host learner
path where the transfer win lives.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Batch columns of the deduplicated format.
FRAMES = "obs_frames"
FRAME_IDX = "obs_frame_idx"


# One access pattern (replay sample, superstep ring feed, framestack
# rebuild): R rows of a (M, D) uint32-lane store. Mosaic (jax 0.9.0, v5e)
# refuses a one-row Pallas block ("last two dimensions of your block
# shape ... divisible by 8 and 128"): XLA's gather / scatter it is (PR 21).
def gather_rows(src, idx):
    """``src[idx]`` over the leading axis — the replay/framestack row
    gather. ``src``: (M, ...) any dtype; ``idx``: any int shape."""
    return src[jnp.asarray(idx)]


def scatter_rows(ring, pos, vals):
    """``ring.at[pos].set(vals)`` over the leading axis — the replay
    insert's circular scatter (unwritten rows keep their contents).
    ``pos``: (R,) int; ``vals``: (R, ...) matching ring's row shape."""
    return ring.at[jnp.asarray(pos)].set(vals)


def frame_stream_columns(
    frames: np.ndarray, num_rows: int, k: int
) -> Dict[str, np.ndarray]:
    """Columns for a batch whose row n stacks frames [n .. n+k-1] of a
    contiguous stream. ``frames``: (num_rows + k - 1, H, W, 1)."""
    assert frames.shape[0] >= num_rows + k - 1, (
        frames.shape, num_rows, k
    )
    assert frames.shape[-1] == 1, frames.shape
    return {
        FRAMES: np.asarray(frames),
        FRAME_IDX: np.arange(num_rows, dtype=np.int32),
    }


def decompose_stacked_obs(
    obs: np.ndarray,
) -> Tuple[np.ndarray, np.ndarray] | None:
    """Recover (frame_stream, idx) from a stacked (N, H, W, k) obs
    column IF its rows really are a sliding window (consecutive rows
    share k-1 frames); None when they don't. Host-side utility for
    producers that only have stacked observations."""
    n, h, w, k = obs.shape
    if k <= 1 or n < 2:
        return None
    if not np.array_equal(obs[1:, :, :, : k - 1], obs[:-1, :, :, 1:]):
        return None
    stream = np.concatenate(
        [
            np.moveaxis(obs[0], -1, 0)[..., None],  # (k, H, W, 1)
            obs[1:, :, :, -1][..., None],  # (N-1, H, W, 1)
        ],
        axis=0,
    )
    return stream, np.arange(n, dtype=np.int32)


def decompose_segmented_obs(
    obs: np.ndarray, new_segment: np.ndarray
) -> Tuple[np.ndarray, np.ndarray] | None:
    """Generalized :func:`decompose_stacked_obs` for a batch that
    concatenates SEVERAL sliding windows (rollout fragments from
    different envs/episodes back to back, as e2e train batches are).

    ``new_segment``: (N,) bool — True where row i does NOT slide from
    row i-1 (fragment start, episode reset). Row 0 is always a start.
    Rows inside a segment are verified to really be a sliding window
    (vectorized compare); any mismatch returns None so the caller falls
    back to shipping materialized stacks — a wrong boundary mask can
    cost the dedup win but never correctness. Returns ``(stream, idx)``
    where each segment contributes k + (len-1) frames to the stream.
    """
    n, h, w, k = obs.shape
    if k <= 1 or n == 0:
        return None
    new_segment = np.asarray(new_segment, bool).copy()
    new_segment[0] = True
    slide_rows = np.flatnonzero(~new_segment)
    # verify in row chunks: fancy-indexing the whole batch at once
    # would materialize ~2 extra copies of a multi-GB pixel batch on
    # the host right before the transfer this dedup exists to shrink
    for c in range(0, slide_rows.size, 64):
        rows = slide_rows[c : c + 64]
        if not np.array_equal(
            obs[rows, :, :, : k - 1], obs[rows - 1, :, :, 1:]
        ):
            return None
    starts = np.flatnonzero(new_segment)
    bounds = np.append(starts, n)
    idx = np.empty(n, np.int32)
    pieces = []
    off = 0
    for s, e in zip(bounds[:-1], bounds[1:]):
        seg_len = int(e - s)
        # first row contributes its k frames, later rows 1 new frame
        pieces.append(np.moveaxis(obs[s], -1, 0)[..., None])
        if seg_len > 1:
            pieces.append(obs[s + 1 : e, :, :, -1][..., None])
        idx[s:e] = off + np.arange(seg_len, dtype=np.int32)
        off += seg_len + k - 1
    return np.concatenate(pieces, axis=0), idx


def compress_fragment_obs(
    obs: np.ndarray,
    next_obs: np.ndarray,
    dones: np.ndarray,
) -> Tuple[np.ndarray, np.ndarray] | None:
    """Worker-side compression of ONE rollout fragment's observation
    columns into the frame-pool format, taken before the fragment
    ships to the driver — this is where the dedup pays most: a stacked
    (T, H, W, k) OBS plus NEXT_OBS is 2k single frames' worth of bytes
    per step through pickle, the object ring, driver concat and the
    host→device transfer; the pool is ~1.

    The pool covers NEXT_OBS implicitly: ``next_obs[t]`` is the stack
    at ``idx[t] + 1`` (sliding), so only the fragment's final
    bootstrap frame is appended (the pseudo-row). ``dones`` marks
    in-fragment episode resets (fixed-unroll mode): the obs AFTER a
    done row starts a fresh window. Returns ``(pool, idx)`` with
    ``idx`` of length T (the bootstrap stack lives at ``idx[-1]+1``),
    or None when the rows aren't sliding windows (caller ships stacks
    unchanged)."""
    T = obs.shape[0]
    if T == 0:
        return None
    ext = np.concatenate([obs, next_obs[-1:]], axis=0)
    seg = np.zeros(T + 1, bool)
    seg[0] = True
    if T > 1:
        seg[1:T] = np.asarray(dones[: T - 1], bool)
    dec = decompose_segmented_obs(ext, seg)
    if dec is None:
        return None
    pool, idx = dec
    return pool, idx[:T]


def compress_replay_obs(
    obs: np.ndarray,
    next_obs: np.ndarray,
    dones: np.ndarray,
) -> Tuple[np.ndarray, np.ndarray] | None:
    """Replay-family variant of :func:`compress_fragment_obs`: the
    pool covers OBS **and** NEXT_OBS exactly, including each episode's
    terminal stack. TD losses read ``next_obs`` at every row (the
    bootstrap term is masked at dones, but the bytes still ship and
    replay buffers store them), so unlike the on-policy path the
    terminal observation of every in-fragment episode must survive
    compression: each episode segment contributes one pseudo-row —
    its final ``next_obs`` — to the pooled stream.

    Invariants of the returned ``(pool, idx)`` (idx length T):
    ``obs[t] == stack(idx[t])`` and ``next_obs[t] == stack(idx[t]+1)``
    for ALL t — :func:`materialize_fragment` rebuilds both columns
    byte-identically (its idx+1 clamp is a no-op here because every
    segment ends with the pseudo-row). Returns None when the rows
    aren't sliding windows (caller ships stacks unchanged)."""
    T = obs.shape[0]
    if T == 0:
        return None
    dones = np.asarray(dones[:T], bool)
    # every done row ends a segment; the final row always does
    seg_end = dones.copy()
    seg_end[T - 1] = True
    end_rows = np.flatnonzero(seg_end)
    # ext: obs rows with each segment's terminal next_obs inserted
    # right after its end row (np.insert indices refer to pre-insert
    # positions, hence end_rows + 1)
    ext = np.insert(obs, end_rows + 1, next_obs[end_rows], axis=0)
    n_seg = len(end_rows)
    starts = np.concatenate(([0], end_rows[:-1] + 1))
    new_segment = np.zeros(T + n_seg, bool)
    new_segment[starts + np.arange(n_seg)] = True
    dec = decompose_segmented_obs(ext, new_segment)
    if dec is None:
        return None
    pool, ext_idx = dec
    # obs row t sits at ext position t + (#pseudo-rows inserted
    # before its segment)
    seg_id = np.zeros(T, np.int64)
    seg_id[1:] = np.cumsum(dones[:-1])
    return pool, ext_idx[np.arange(T) + seg_id]


def materialize_stacks_np(
    pool: np.ndarray, idx: np.ndarray, k: int
) -> np.ndarray:
    """Host-side :func:`build_stacks`: (M, H, W, 1) pool + (N,) first-
    frame indices → (N, H, W, k) stacked observations."""
    gathered = pool[idx[:, None] + np.arange(k)[None, :]]
    return np.moveaxis(gathered[..., 0], 1, -1)


def materialize_fragment(batch_cols: Dict, k: int) -> Dict:
    """Undo :func:`compress_fragment_obs` on a batch's columns: rebuild
    OBS exactly, and NEXT_OBS as the ``idx+1`` stacks — exact
    everywhere consumers read it (within segments and the final
    bootstrap row); at interior episode-reset rows the true terminal
    next_obs was not pooled, so those rows get the FOLLOWING row's
    reset obs instead (no trainer reads next_obs at those rows: the
    on-policy family drops the column entirely and the fixed-unroll
    V-trace tree only reads the final bootstrap stack)."""
    cols = dict(batch_cols)
    pool = np.asarray(cols.pop(FRAMES))
    idx = np.asarray(cols.pop(FRAME_IDX), np.int64)
    from ray_tpu.data.sample_batch import SampleBatch

    cols[SampleBatch.OBS] = materialize_stacks_np(pool, idx, k)
    next_idx = np.minimum(idx + 1, len(pool) - k)
    cols[SampleBatch.NEXT_OBS] = materialize_stacks_np(
        pool, next_idx, k
    )
    return cols


def build_stacks(frames: jnp.ndarray, idx: jnp.ndarray, k: int):
    """Device-side: (M, H, W, 1) frame pool + (N,) first-frame indices
    → (N, H, W, k) stacked observations (one gather, XLA-fusable).

    uint8 pools gather through a uint32-lane bitcast view: narrow-
    element gathers are element-width-bound on TPU (~127 GB/s effective
    for uint8 vs ~420 GB/s through uint32 lanes on v5e, measured for
    the minibatch row gather — MFU.md), and the pool gather is the same
    access pattern at 4× fewer, 4× wider elements. Pure data movement:
    the reconstructed stacks are byte-identical."""
    assert frames.shape[-1] == 1, (
        "frame pools are single-channel (stack depth k comes from the "
        f"index expansion); got channel dim {frames.shape[-1]} — "
        "multi-channel frames would silently train on one channel"
    )
    rows = idx[:, None] + jnp.arange(k)[None, :]
    inner = int(np.prod(frames.shape[1:]))
    if frames.dtype == jnp.uint8 and inner % 4 == 0:
        packed = jax.lax.bitcast_convert_type(
            frames.reshape(frames.shape[0], inner // 4, 4), jnp.uint32
        )
        u8 = jax.lax.bitcast_convert_type(
            gather_rows(packed, rows), jnp.uint8
        )
        u8 = u8.reshape((u8.shape[0], k) + frames.shape[1:])
        return jnp.moveaxis(u8[..., 0], 1, -1)
    # (N, k, H, W, 1) → (N, H, W, k)
    return jnp.moveaxis(gather_rows(frames, rows)[..., 0], 1, -1)
