"""Replay buffers: host-RAM ring + the device-resident data plane.

Counterpart of the reference's
``rllib/utils/replay_buffers/{replay_buffer,prioritized_replay_buffer}.py``
(PrioritizedReplayBuffer ``:19``) and the segment trees
(``rllib/execution/segment_tree.py``). TPU-first: storage is columnar
(pre-allocated ring arrays per column) instead of a deque of
per-timestep dicts, so sampling a training batch is a single
fancy-index gather producing learner-ready arrays with zero
python-loop work.

Two storage planes (docs/data_plane.md):

- :class:`ReplayBuffer` / :class:`PrioritizedReplayBuffer` — numpy
  rings on the host. Every learn step re-transfers its sampled rows
  host→device; at SAC-style replay ratios each frame crosses the wire
  dozens of times.
- :class:`DeviceReplayBuffer` / :class:`DevicePrioritizedReplayBuffer`
  — column rings living as device arrays on the learner mesh
  (``ray_tpu.sharding``): inserts are one donated jit'd scatter (each
  transition crosses H2D exactly once), samples are one jit'd gather
  whose output feeds ``JaxPolicy.learn_on_device_batch`` directly.
  The index draw stays HOST-seeded (same generator, same call order
  as the host ring), so a fixed seed produces bit-identical learn
  results on either plane. Priorities stay host-side (the numpy sum
  tree — a device sum tree is an open ROADMAP item); only rows live
  on device. A capacity/memory projection at first insert spills to
  the host ring when the buffer wouldn't fit.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from ray_tpu.data.sample_batch import SampleBatch
from ray_tpu.ops.segment_tree import MinSegmentTree, SumSegmentTree


class ReplayBuffer:
    """Uniform ring buffer (reference replay_buffer.py ReplayBuffer)."""

    def __init__(self, capacity: int = 10000, seed: Optional[int] = None):
        self.capacity = capacity
        self._cols: Dict[str, np.ndarray] = {}
        self._idx = 0
        self._size = 0
        self._rng = np.random.default_rng(seed)
        self._num_added = 0

    def __len__(self) -> int:
        return self._size

    @property
    def num_added(self) -> int:
        return self._num_added

    def _ensure_cols(self, batch: SampleBatch):
        for k, v in batch.items():
            if not isinstance(v, np.ndarray) or v.dtype == object:
                continue
            if k not in self._cols:
                self._cols[k] = np.zeros(
                    (self.capacity,) + v.shape[1:], v.dtype
                )

    def add(self, batch: SampleBatch) -> None:
        n = batch.count
        if n == 0:
            return
        self._ensure_cols(batch)
        idx = (self._idx + np.arange(n)) % self.capacity
        for k, col in self._cols.items():
            if k in batch:
                col[idx] = batch[k]
        self._idx = int((self._idx + n) % self.capacity)
        self._size = int(min(self._size + n, self.capacity))
        self._num_added += n

    def sample(self, num_items: int) -> SampleBatch:
        idx = self._rng.integers(0, self._size, num_items)
        return self._make_batch(idx)

    def draw_index_sets(self, k: int, num_items: int) -> np.ndarray:
        """``k`` uniform draws of ``num_items`` rows as a ``(k, n)``
        index matrix — the superstep's pre-drawn batch schedule. The
        draws are k SEQUENTIAL generator calls (never one k·n call):
        the generator consumes its stream in the host ring's exact
        per-update call order, so a fixed seed stays bit-identical to
        k individual ``sample`` calls."""
        return np.stack(
            [
                self._rng.integers(0, self._size, num_items)
                for _ in range(k)
            ]
        )

    def _make_batch(self, idx: np.ndarray) -> SampleBatch:
        return SampleBatch(
            {k: col[idx] for k, col in self._cols.items()}
        )

    def stats(self) -> Dict:
        return {"size": self._size, "num_added": self._num_added}

    def get_state(self) -> Dict:
        return {
            "cols": {k: v[: self._size].copy() for k, v in self._cols.items()},
            "idx": self._idx,
            "size": self._size,
            "num_added": self._num_added,
        }

    def set_state(self, state: Dict) -> None:
        self._size = state["size"]
        self._idx = state["idx"]
        self._num_added = state["num_added"]
        for k, v in state["cols"].items():
            self._cols[k] = np.zeros(
                (self.capacity,) + v.shape[1:], v.dtype
            )
            self._cols[k][: self._size] = v


def powered_priorities(priorities, alpha: float):
    """THE canonical priority→leaf transform: clamp to 1e-6, then the
    alpha-power — in host numpy f64, for BOTH tree planes. The power
    is the one op in the prioritized path that numpy and XLA round
    differently (last-ulp), so it stays host-side and the device tree
    receives already-powered leaves; everything downstream (sums,
    prefix descent, min, gathers) is exact f64 arithmetic on either
    plane. Returns ``(powered, clamped)`` — the clamped values feed
    the max-priority watermark exactly as the host tree's update
    does."""
    clamped = np.maximum(np.asarray(priorities, np.float64), 1e-6)
    return clamped**alpha, clamped


class _PrioritySampling:
    """Host-side proportional-priority machinery shared by the host
    and device prioritized buffers: numpy sum/min segment trees, the
    stratified index draw, IS-weight computation, and priority
    updates. One implementation on purpose — the device buffer keeps
    bit-identical sampling to the host ring because it runs exactly
    this code; only WHERE the rows live differs. (The device SUM TREE
    — ``replay_device_tree`` — overrides the tree walks with the
    bit-exact device programs of ``ops/segment_tree.DeviceSumTree``;
    this class remains the oracle both planes are asserted against.)"""

    def _init_priority_trees(self, capacity: int, alpha: float) -> None:
        assert alpha >= 0
        self._alpha = alpha
        cap2 = 1
        while cap2 < capacity:
            cap2 *= 2
        self._tree_capacity = cap2
        self._sum_tree = SumSegmentTree(cap2)
        self._min_tree = MinSegmentTree(cap2)
        self._max_priority = 1.0
        self._tree_op = "update"  # insert paths flip this transiently

    def _draw_prioritized(self, num_items: int, beta: float):
        """→ (row indices, IS weights float32) for one stratified
        proportional draw over the current ``self._size`` rows."""
        from ray_tpu.telemetry import metrics as telemetry_metrics

        total = self._sum_tree.sum(0, self._size)
        mass = (
            self._rng.random(num_items) + np.arange(num_items)
        ) / num_items * total
        idx = self._sum_tree.find_prefixsum_idx(mass)
        idx = np.clip(idx, 0, self._size - 1)

        p_min = self._min_tree.min(0, self._size) / total
        max_weight = (p_min * self._size) ** (-beta)
        p_sample = self._sum_tree[idx] / total
        weights = (p_sample * self._size) ** (-beta) / max_weight
        telemetry_metrics.inc_tree_op("sample", "host")
        return idx, weights.astype(np.float32)

    def draw_prioritized_sets(self, k: int, num_items: int, beta: float):
        """``k`` sequential stratified draws → ``(k, n)`` indices and
        IS weights. Priorities are NOT refreshed between the draws —
        the superstep's documented within-chain staleness
        (docs/data_plane.md); the generator call order matches k
        individual ``sample`` calls exactly."""
        idx, weights = zip(
            *(self._draw_prioritized(num_items, beta) for _ in range(k))
        )
        return np.stack(idx), np.stack(weights)

    def update_priorities(
        self, idx: np.ndarray, priorities: np.ndarray
    ) -> None:
        from ray_tpu.telemetry import metrics as telemetry_metrics

        powered, clamped = powered_priorities(priorities, self._alpha)
        self._sum_tree.set_items(idx, powered)
        self._min_tree.set_items(idx, powered)
        self._max_priority = max(
            self._max_priority, float(clamped.max())
        )
        telemetry_metrics.inc_tree_op(self._tree_op, "host")

    def _priority_state(self) -> Dict:
        """Raw (already alpha-powered) leaf values of the stored range
        + max priority — enough to rebuild both trees exactly."""
        idx = np.arange(self._size)
        return {
            "leaf_values": np.asarray(self._sum_tree[idx], np.float64)
            if self._size
            else np.zeros(0, np.float64),
            "max_priority": self._max_priority,
        }

    def _set_priority_state(self, state: Dict) -> None:
        vals = np.asarray(state["leaf_values"], np.float64)
        if len(vals):
            idx = np.arange(len(vals))
            self._sum_tree.set_items(idx, vals)
            self._min_tree.set_items(idx, vals)
        self._max_priority = float(state.get("max_priority", 1.0))


class PrioritizedReplayBuffer(_PrioritySampling, ReplayBuffer):
    """Proportional prioritized replay (reference
    prioritized_replay_buffer.py:19), vectorized over the whole sample
    batch via the numpy segment trees."""

    tree_plane = "host"

    def __init__(
        self,
        capacity: int = 10000,
        alpha: float = 0.6,
        seed: Optional[int] = None,
    ):
        super().__init__(capacity, seed)
        self._init_priority_trees(capacity, alpha)

    def add(self, batch: SampleBatch) -> None:
        # new samples enter at max priority so they are trained on at
        # least once (one insertion code path: add_with_priorities)
        self.add_with_priorities(
            batch, np.full(batch.count, self._max_priority)
        )

    def add_with_priorities(
        self, batch: SampleBatch, priorities: np.ndarray
    ) -> None:
        """Insert with caller-supplied initial priorities (Ape-X:
        workers/driver compute initial TD errors; reference
        apex ReplayActor.add_batch)."""
        n = batch.count
        if n == 0:
            return
        idx = (self._idx + np.arange(n)) % self.capacity
        ReplayBuffer.add(self, batch)
        self._tree_op = "insert"
        try:
            self.update_priorities(
                idx, np.asarray(priorities, np.float64)
            )
        finally:
            self._tree_op = "update"

    def sample(self, num_items: int, beta: float = 0.4) -> SampleBatch:
        from ray_tpu.util import tracing

        with tracing.start_span(
            "replay:sample", n=num_items, tree="host"
        ):
            idx, weights = self._draw_prioritized(num_items, beta)
            batch = self._make_batch(idx)
            batch["weights"] = weights
            batch["batch_indexes"] = idx.astype(np.int64)
            return batch

    def get_state(self) -> Dict:
        state = super().get_state()
        state["priorities"] = self._priority_state()
        return state

    def set_state(self, state: Dict) -> None:
        super().set_state(state)
        if "priorities" in state:
            self._set_priority_state(state["priorities"])


def resolve_device_resident(config: Dict, mesh=None) -> bool:
    """Resolve the ``replay_device_resident`` knob
    (docs/data_plane.md). ``True`` forces device placement (the
    memory projection at first insert can still spill). ``"auto"``
    (the default) turns it on exactly where it pays: a real
    accelerator behind a transfer boundary. On the CPU client
    "device" arrays live in the same host RAM — there is no wire to
    diet, and the extra insert/sample programs are pure overhead —
    so auto resolves off there. Auto also resolves off when
    ``train_batch_size`` doesn't divide the data shards (the host
    path's prepare_batch trims ragged batches; the device path keeps
    static shapes end to end)."""
    mode = config.get("replay_device_resident", "auto")
    if not mode:
        return False
    if mode == "auto":
        from ray_tpu.sharding.mesh import all_cpu, num_shards

        if all_cpu(mesh):
            return False
        shards = num_shards(mesh) if mesh is not None else 1
        if int(config.get("train_batch_size", 0)) % max(1, shards):
            return False
    return True


def resolve_device_tree(config: Dict, mesh=None) -> bool:
    """Resolve the ``replay_device_tree`` knob (docs/data_plane.md
    "device sum tree"). Requires device-resident rows (the tree's
    whole point is an in-program draw→gather over resident rings).
    ``"auto"`` (default) engages only behind a real accelerator —
    on the CPU client the numpy tree walk shares the host RAM the
    "device" tree would live in, and the extra programs are pure
    overhead; ``True`` forces it anywhere (tests, benches)."""
    mode = config.get("replay_device_tree", "auto")
    if not mode:
        return False
    if not resolve_device_resident(config, mesh):
        return False
    if mode == "auto":
        from ray_tpu.sharding.mesh import all_cpu

        if all_cpu(mesh):
            return False
    return True


class DeviceTrainBatch:
    """A sampled batch whose columns are device arrays, ready for
    ``JaxPolicy.learn_on_device_batch`` — the device plane's stand-in
    for a host :class:`SampleBatch` in the off-policy training loops.
    ``indices`` (host numpy) are the drawn ring positions, kept for
    prioritized-priority refresh without a device round trip."""

    is_device_resident = True

    def __init__(
        self,
        tree: Dict[str, Any],
        count: int,
        indices: Optional[np.ndarray] = None,
    ):
        self.tree = tree
        self.count = int(count)
        self.indices = indices

    def __len__(self) -> int:
        return self.count

    def env_steps(self) -> int:
        return self.count

    def __contains__(self, key) -> bool:
        return key in self.tree

    def __getitem__(self, key):
        return self.tree[key]

    def get(self, key, default=None):
        return self.tree.get(key, default)


class SuperstepRingFeed:
    """Feed descriptor handing the device replay rings to a policy's
    fused superstep program (``JaxPolicy.learn_superstep``): the scan
    gathers each update's rows from ``store`` in place using the
    host-pre-drawn ``(k, B)`` index matrix — replay rows never leave
    the mesh, and only ``idx`` (plus any ``extra`` stacked host
    columns, e.g. PER importance weights) cross host→device."""

    def __init__(
        self, store, idx, extra, gather_fn, unpack_fn, shardings, key
    ):
        self.store = store
        self.idx = idx
        self.extra = extra
        # (store, idx) -> (K, B, ...) rows as stored; one update's
        # (B, ...) stored rows -> its logical columns
        self.gather_fn = gather_fn
        self.unpack_fn = unpack_fn
        self.shardings = shardings
        self.key = key  # compile-cache key: the stored column set


def _stored_words(row_shape: tuple) -> int:
    """Words one packed uint8 row occupies in its device ring: the
    stored row width rule of :class:`DeviceReplayBuffer`'s docstring
    (stated there, once)."""
    words = int(np.prod(row_shape)) // 4
    lanes = -(-words // 128) * 128
    return lanes if 4 * lanes <= 5 * words else words


def _pack_rows(v, width: int):
    """``u8[R, *row_shape] -> u32[R, width]``: four pixels a word,
    zero pad up to the ring's stored width."""
    import jax
    import jax.numpy as jnp

    words = jax.lax.bitcast_convert_type(
        v.reshape(v.shape[0], -1, 4), jnp.uint32
    )
    return jnp.pad(words, ((0, 0), (0, width - words.shape[1])))


def _unpack_rows(g, row_shape: tuple):
    """``u32[..., width] -> u8[..., *row_shape]``: slice the pad off,
    then bitcast (a view of the same bytes)."""
    import jax
    import jax.numpy as jnp

    words = int(np.prod(row_shape)) // 4
    u8 = jax.lax.bitcast_convert_type(g[..., :words], jnp.uint8)
    return u8.reshape(g.shape[:-1] + tuple(row_shape))


def _gather_stored(store, idx):
    """Rows ``idx`` (any int shape) of every ring in ``store`` AS
    STORED: a packed pixel column comes out as its words, pad and all
    (the gather's rows are whole lanes, and the pad's slice is free
    only where the bytes are made)."""
    import jax

    from ray_tpu.ops import framestack as framestack_lib

    with jax.named_scope("replay/gather"):
        return {
            k: framestack_lib.gather_rows(ring, idx)
            for k, ring in store.items()
        }


def _unpack_columns(cols, meta):
    """Gathered rows as stored -> logical columns: the ONE words ->
    bytes conversion of a packed pixel column. Columns ``meta`` does
    not know (a feed's extra columns) pass through."""
    import jax

    with jax.named_scope("replay/gather"):
        return {
            k: _unpack_rows(g, meta[k][0])
            if k in meta and meta[k][2]
            else g
            for k, g in cols.items()
        }


def _gather_columns(store, idx, meta):
    """Rows ``idx`` (any int shape) of every ring in ``store`` as
    logical columns — the one gather body of ``gather``/``sample`` and
    the fused tree sample. (The superstep's ring feed takes the two
    halves apart: it gathers K updates' rows before its scan and
    unpacks one update's inside it.)"""
    return _unpack_columns(_gather_stored(store, idx), meta)


class DeviceReplayBuffer:
    """Uniform ring buffer whose column storage lives on the learner
    mesh (docs/data_plane.md).

    - **Insert** is one donated jit'd circular scatter per fragment:
      the host rows cross H2D exactly once, here, and never again.
      uint8 columns (pixel obs) are stored packed as uint32 lanes —
      the same element-width trick as ``_build_learn_fn``'s minibatch
      gather (MFU.md) — so the sample gather moves 4× wider elements.
    - **Stored row width** of a packed column: ``inner // 4`` words
      rounded up to whole 128-word lanes when that costs at most a
      quarter more storage (84×84×4: 7,056 → 7,168 words, +1.6%;
      one 84×84 frame: 1,764 → 1,792), else ``inner // 4`` as is. The
      TPU client lays a 2-D array out with the ROW INDEX minor
      (``{0,1:T(8,128)}``) whenever its last dimension is not a
      multiple of 128, and a ring stored that way is transposed whole
      before any row of it is scattered or gathered (3.7 GB a copy at
      131,072 pixel rows); a whole number of lanes is laid out
      row-major (``{1,0}``), the scatter runs in place on the donated
      ring and the gather reads rows. Narrow rows (a few dozen words:
      vector observations, small uint8 rows) are read column-major
      natively and need nothing, which is why the rule stops where
      the pad would multiply the storage. The pad words are zero,
      are written with each row and sliced off after every gather;
      ``storage_bytes`` counts what is allocated, and a checkpoint
      (``get_state``) holds logical rows, so it loads whatever width
      wrote it.
    - **Sample** draws indices on the HOST from the same seeded
      generator (same call order) as the host :class:`ReplayBuffer`,
      then gathers rows in one jit'd program; a fixed seed therefore
      yields bit-identical learn results on either plane.
    - **Spill**: the first insert projects total storage bytes
      (``capacity ×`` row bytes); past ``memory_cap_bytes`` (default:
      60% of the device's reported ``bytes_limit``, unlimited when the
      backend reports none — e.g. the CPU client) everything delegates
      to a host ring built with the SAME generator object, so the
      spill changes placement, never sampling.
    """

    is_device_resident = True

    def __init__(
        self,
        capacity: int = 10000,
        seed: Optional[int] = None,
        mesh=None,
        memory_cap_bytes: Optional[int] = None,
        label: str = "default_policy",
    ):
        from ray_tpu import sharding as sharding_lib

        self.capacity = int(capacity)
        self._rng = np.random.default_rng(seed)
        self.mesh = mesh if mesh is not None else sharding_lib.get_mesh()
        self.memory_cap_bytes = memory_cap_bytes
        self.label = label
        self._store: Dict[str, Any] = {}  # name -> device ring array
        # name -> (row_shape, dtype, packed_as_uint32)
        self._meta: Dict[str, tuple] = {}
        self._idx = 0
        self._size = 0
        self._num_added = 0
        self._insert_fn = None
        self._sample_fn = None
        self._host: Optional[ReplayBuffer] = None  # spill fallback
        self.storage_bytes = 0

    # -- spill ----------------------------------------------------------

    @property
    def spilled(self) -> bool:
        return self._host is not None

    def _make_host_fallback(self) -> ReplayBuffer:
        buf = ReplayBuffer(self.capacity)
        # same generator OBJECT: the spill changes row placement, not
        # the index stream — fixed-seed runs stay bit-identical
        buf._rng = self._rng
        return buf

    def _resolve_memory_cap(self) -> Optional[int]:
        if self.memory_cap_bytes is not None:
            return int(self.memory_cap_bytes)
        try:
            import jax

            stats = jax.local_devices()[0].memory_stats() or {}
            limit = stats.get("bytes_limit")
            if limit:
                return int(0.6 * float(limit))
        except Exception:
            pass
        return None  # backend reports no budget: no projection check

    # -- storage --------------------------------------------------------

    @staticmethod
    def _canonical(v: np.ndarray) -> np.ndarray:
        """Match jax's dtype canonicalization BEFORE the transfer:
        with x64 disabled a ``device_put`` of f64/i64 lands as
        f32/i32 anyway (that's what the host ring's learn path
        ships), so cast host-side — same values, half the wire
        bytes."""
        import jax

        if not jax.config.jax_enable_x64:
            if v.dtype == np.float64:
                return v.astype(np.float32)
            if v.dtype == np.int64:
                return v.astype(np.int32)
            if v.dtype == np.uint64:
                return v.astype(np.uint32)
        return v

    @staticmethod
    def _packable(shape: tuple, dtype) -> bool:
        inner = int(np.prod(shape)) if shape else 1
        return (
            np.dtype(dtype) == np.uint8
            and len(shape) >= 1
            and inner % 4 == 0
        )

    @classmethod
    def _ring_shape_dtype(cls, capacity: int, row_shape: tuple, dtype):
        """``(shape, dtype)`` of the ring one column is stored in."""
        if cls._packable(row_shape, dtype):
            return (capacity, _stored_words(row_shape)), np.uint32
        return (capacity,) + tuple(row_shape), np.dtype(dtype)

    def _ensure_storage(self, tree: Dict[str, np.ndarray]) -> bool:
        """Allocate device rings for any new columns; returns False
        when the projection spilled this buffer to the host ring."""
        if self._host is not None:
            return False
        import jax
        import jax.numpy as jnp

        from ray_tpu import sharding as sharding_lib

        new_cols = {
            k: v for k, v in tree.items() if k not in self._store
        }
        if not new_cols:
            return True
        # allocated bytes (a padded pixel column counts its pad)
        rings = {
            k: self._ring_shape_dtype(
                self.capacity, tuple(v.shape[1:]), v.dtype
            )
            for k, v in new_cols.items()
        }
        ring_bytes = {
            k: int(np.prod(shape)) * np.dtype(dtype).itemsize
            for k, (shape, dtype) in rings.items()
        }
        projected = self.storage_bytes + sum(ring_bytes.values())
        cap = self._resolve_memory_cap()
        if cap is not None and projected > cap:
            # snapshot BEFORE arming the host fallback (get_state
            # delegates once _host is set)
            prior = self.get_state() if self._store else None
            self._host = self._make_host_fallback()
            if prior is not None:
                # columns arrived incrementally and the projection
                # only now tipped over: replay the resident rows into
                # the host ring so nothing is lost
                self._store, self._meta = {}, {}
                self._host.set_state(
                    {
                        "cols": prior["cols"],
                        "idx": prior["idx"],
                        "size": prior["size"],
                        "num_added": prior["num_added"],
                    }
                )
            self.storage_bytes = 0
            return False
        from ray_tpu.util import tracing

        with tracing.phase(
            "setup:replay",
            columns=len(new_cols),
            bytes=sum(ring_bytes.values()),
        ):
            for k, v in new_cols.items():
                row_shape = tuple(v.shape[1:])
                packed = self._packable(row_shape, v.dtype)
                ring = jnp.zeros(*rings[k])
                # rows shard over the data axis when capacity divides
                # the shard count, else replicate (specs.leaf_sharding
                # rule); put_global assembles cross-process shards when
                # the mesh spans hosts (fleet rings, docs/fleet.md) and
                # is plain device_put on a local mesh
                self._store[k] = sharding_lib.put_global(
                    ring, sharding_lib.leaf_sharding(ring, self.mesh)
                )
                self._meta[k] = (row_shape, v.dtype, packed)
                self.storage_bytes += ring_bytes[k]
        self._insert_fn = None
        self._sample_fn = None
        return True

    def _build_insert_fn(self):
        import jax

        from ray_tpu import sharding as sharding_lib
        from ray_tpu.ops import framestack as framestack_lib

        meta = dict(self._meta)

        @jax.named_scope("replay/insert")
        def fn(store, rows, pos):
            out = dict(store)
            for k, v in rows.items():
                _, _, packed = meta[k]
                if packed:
                    v = _pack_rows(v, store[k].shape[1])
                out[k] = framestack_lib.scatter_rows(store[k], pos, v)
            return out

        return sharding_lib.sharded_jit(
            fn,
            donate_argnums=(0,),
            label=f"replay_insert[{self.label}]",
        )

    def _gather_fn(self):
        """``(store, idx) -> logical columns`` over this buffer's
        current column set (holds the meta, not the buffer)."""
        return functools.partial(_gather_columns, meta=dict(self._meta))

    def _build_sample_fn(self, row_sharded: bool):
        from ray_tpu import sharding as sharding_lib

        fn = self._gather_fn()

        # explicit output placement: the learn programs declare
        # row-sharded batch inputs, and jit rejects (rather than
        # reshards) a committed mismatch — so the gather emits rows
        # already laid out for the nest; draws whose length doesn't
        # divide the shards (state snapshots) replicate instead
        out_spec = (
            sharding_lib.batch_sharded(self.mesh)
            if row_sharded
            else sharding_lib.replicated(self.mesh)
        )
        return sharding_lib.sharded_jit(
            fn,
            out_specs=out_spec,
            label=f"replay_sample[{self.label}]",
        )

    # -- ring bookkeeping (mirrors ReplayBuffer exactly) ----------------

    def __len__(self) -> int:
        if self._host is not None:
            return len(self._host)
        return self._size

    @property
    def num_added(self) -> int:
        if self._host is not None:
            return self._host.num_added
        return self._num_added

    def add(self, batch: SampleBatch) -> None:
        self.add_tree(
            {
                k: np.asarray(v)
                for k, v in batch.items()
                if isinstance(v, np.ndarray) and v.dtype != object
            }
        )

    def add_tree(self, tree: Dict[str, np.ndarray]) -> None:
        """Insert a host column tree (equal leading dims). This is the
        ONE host→device crossing of these rows."""
        tree = {
            k: self._canonical(np.ascontiguousarray(v))
            for k, v in tree.items()
        }
        if not tree:
            return
        n = int(next(iter(tree.values())).shape[0])
        if n == 0:
            return
        if not self._ensure_storage(tree):
            self._host.add(SampleBatch(tree))
            self._report_occupancy()
            return
        from ray_tpu import sharding as sharding_lib
        from ray_tpu.telemetry import metrics as telemetry_metrics

        telemetry_metrics.add_h2d_bytes(
            "replay_insert", sharding_lib.tree_nbytes(tree)
        )
        if self._insert_fn is None:
            self._insert_fn = self._build_insert_fn()
        pos = (self._idx + np.arange(n)) % self.capacity
        self._store = self._insert_fn(
            self._store, tree, pos.astype(np.int32)
        )
        self._idx = int((self._idx + n) % self.capacity)
        self._size = int(min(self._size + n, self.capacity))
        self._num_added += n
        self._report_occupancy()

    def add_device_tree(self, tree: Dict[str, Any]) -> None:
        """Insert rows that are ALREADY device-resident (the jax
        rollout lane: in-program rollout rows — docs/pipeline.md).
        Zero H2D: the same donated scatter as :meth:`add_tree` runs on
        the resident columns. Ring bookkeeping, the host index
        generator, and (in the prioritized subclass) the sum-tree
        stream are EXACTLY the host insert's — inserting the same rows
        from either side leaves every subsequent ``sample()`` draw
        bit-identical (tests/test_jax_env.py). A spilled buffer pulls
        the rows back to its host ring (placement changes, sampling
        doesn't)."""
        tree = dict(tree)
        if not tree:
            return
        n = int(next(iter(tree.values())).shape[0])
        if n == 0:
            return
        if not self._ensure_storage(tree):
            import jax

            self._host.add(SampleBatch(jax.device_get(tree)))
            self._report_occupancy()
            return
        if self._insert_fn is None:
            self._insert_fn = self._build_insert_fn()
        pos = (self._idx + np.arange(n)) % self.capacity
        self._store = self._insert_fn(
            self._store, tree, pos.astype(np.int32)
        )
        self._idx = int((self._idx + n) % self.capacity)
        self._size = int(min(self._size + n, self.capacity))
        self._num_added += n
        self._report_occupancy()

    def _report_occupancy(self) -> None:
        from ray_tpu.telemetry import metrics as telemetry_metrics

        telemetry_metrics.set_replay_occupancy(
            self.label,
            len(self),
            self.capacity,
            self.storage_bytes,
            device=self._host is None,
        )

    # -- sampling --------------------------------------------------------

    def sample(self, num_items: int):
        if self._host is not None:
            return self._host.sample(num_items)
        from ray_tpu.util import tracing

        with tracing.start_span("replay:sample", n=num_items):
            idx = self._rng.integers(0, self._size, num_items)
            return self.gather(idx)

    def _num_shards(self) -> int:
        from ray_tpu import sharding as sharding_lib

        return max(1, sharding_lib.num_shards(self.mesh))

    def gather(self, idx: np.ndarray) -> DeviceTrainBatch:
        """Rows at caller-chosen ring positions as one jit'd device
        gather (QMIX draws its own indices; ``sample`` feeds the
        host-seeded uniform draw through here)."""
        from ray_tpu.telemetry import metrics as telemetry_metrics

        idx = np.asarray(idx)
        row_sharded = len(idx) % self._num_shards() == 0 and len(idx) > 0
        if self._sample_fn is None:
            self._sample_fn = {}
        fn = self._sample_fn.get(row_sharded)
        if fn is None:
            fn = self._sample_fn[row_sharded] = self._build_sample_fn(
                row_sharded
            )
        idx32 = idx.astype(np.int32)
        # the index upload is the sample path's entire H2D payload
        # here (rows are resident); the device-tree draw even deletes
        # this — its indices never exist host-side
        telemetry_metrics.add_h2d_bytes("replay_sample", idx32.nbytes)
        tree = fn(self._store, idx32)
        return DeviceTrainBatch(dict(tree), len(idx), indices=idx)

    def draw_index_sets(self, k: int, num_items: int) -> np.ndarray:
        """Same draw discipline as the host ring (k sequential calls
        on the shared generator) — see ``ReplayBuffer
        .draw_index_sets``. Valid whether or not this buffer spilled
        (the generator object is shared with the spill ring)."""
        size = len(self)
        return np.stack(
            [
                self._rng.integers(0, size, num_items)
                for _ in range(k)
            ]
        )

    def superstep_feed(
        self,
        idx: np.ndarray,
        extra: Optional[Dict[str, np.ndarray]] = None,
    ) -> SuperstepRingFeed:
        """Package the device rings for an in-program superstep gather
        (``idx``: pre-drawn ``(k, B)`` positions; ``extra``: stacked
        host columns merged after the gather). The two halves of the
        sample path's gather body: ``gather_fn`` takes the K updates'
        rows out of the rings as stored (before the scan), ``unpack_fn``
        makes one update's logical columns of them (inside it) — same
        uint32-lane unpack, so each update consumes rows bit-identical
        to ``gather()``'s output."""
        if self._host is not None:
            raise RuntimeError(
                "superstep_feed on a spilled buffer — use the host "
                "stacked path"
            )
        import jax

        if not isinstance(idx, jax.Array):
            idx = np.ascontiguousarray(idx, np.int32)
        shardings = {k_: v.sharding for k_, v in self._store.items()}
        return SuperstepRingFeed(
            store=self._store,
            idx=idx,
            extra=dict(extra or {}),
            gather_fn=_gather_stored,
            unpack_fn=functools.partial(
                _unpack_columns, meta=dict(self._meta)
            ),
            shardings=shardings,
            key=tuple(sorted(self._store)),
        )

    def stats(self) -> Dict:
        return {
            "size": len(self),
            "num_added": self.num_added,
            "device_resident": self._host is None,
            "storage_bytes": self.storage_bytes,
        }

    # -- checkpoint state ------------------------------------------------

    def get_state(self) -> Dict:
        if self._host is not None:
            state = self._host.get_state()
            state["spilled"] = True
            return state
        import jax

        host_store = jax.device_get(self._store)
        cols = {}
        for k, ring in host_store.items():
            row_shape, dtype, packed = self._meta[k]
            rows = ring[: self._size]
            if packed:
                # logical rows: the pad words stay behind
                words = int(np.prod(row_shape)) // 4
                rows = (
                    rows[:, :words]
                    .copy()
                    .view(np.uint8)
                    .reshape((self._size,) + row_shape)
                )
            else:
                rows = rows.copy()
            cols[k] = rows
        return {
            "cols": cols,
            "idx": self._idx,
            "size": self._size,
            "num_added": self._num_added,
            "spilled": False,
        }

    def set_state(self, state: Dict) -> None:
        if state.get("spilled"):
            self._host = self._make_host_fallback()
            self._host.set_state(state)
            return
        cols = state["cols"]
        size = int(state["size"])
        full = {}
        for k, v in cols.items():
            ring = np.zeros(
                (self.capacity,) + v.shape[1:], v.dtype
            )
            ring[:size] = v
            full[k] = ring
        self._store, self._meta = {}, {}
        self.storage_bytes = 0
        if full and not self._ensure_storage(full):
            # restoring on a smaller-memory host: land in the spill
            # ring instead
            self._host.set_state(
                {k: state[k] for k in ("cols", "idx", "size", "num_added")}
            )
            return
        if full:
            if self._insert_fn is None:
                self._insert_fn = self._build_insert_fn()
            self._store = self._insert_fn(
                self._store,
                full,
                np.arange(self.capacity, dtype=np.int32),
            )
        self._idx = int(state["idx"])
        self._size = size
        self._num_added = int(state["num_added"])


class DevicePrioritizedReplayBuffer(_PrioritySampling, DeviceReplayBuffer):
    """Prioritized replay with device-resident rows. Two tree planes
    (docs/data_plane.md "device sum tree"):

    - ``device_tree=False`` (legacy): the sum/min trees (and every
      priority update) stay host-side — exactly the host
      :class:`PrioritizedReplayBuffer` code via ``_PrioritySampling``
      — while the drawn rows gather on device.
    - ``device_tree=True``: priorities live as f64 mesh arrays
      (``ops/segment_tree.DeviceSumTree``) and a sample is ONE fused
      program — prefix-descent draw → clip → IS weights → row gather
      — whose only host-fed input is the generator's raw uniform
      stream, so the index draws (and sampled priorities) reproduce
      the host trees bit-exactly and zero payload bytes cross H2D on
      the sample path. The alpha-power transform stays host-side
      (``powered_priorities`` — the one cross-backend-inexact op), so
      priority refreshes pull |td| D2H, power, and push powered
      leaves back; the tree WALK never returns to the host.

    IS weights ride into the batch tree as a device column;
    ``batch_indexes`` ride on the returned :class:`DeviceTrainBatch`
    (host numpy under the host tree, a device i32 array under the
    device tree — ``update_priorities`` accepts either)."""

    def __init__(
        self,
        capacity: int = 10000,
        alpha: float = 0.6,
        seed: Optional[int] = None,
        mesh=None,
        memory_cap_bytes: Optional[int] = None,
        label: str = "default_policy",
        device_tree: bool = False,
    ):
        super().__init__(
            capacity,
            seed,
            mesh=mesh,
            memory_cap_bytes=memory_cap_bytes,
            label=label,
        )
        self._init_priority_trees(capacity, alpha)
        self._dtree = None
        self._tree_sample_fns: Dict = {}
        self._tree_draw_fns: Dict = {}
        if device_tree:
            from ray_tpu.ops.segment_tree import DeviceSumTree

            self._dtree = DeviceSumTree(
                self._tree_capacity, mesh=self.mesh, label=label
            )

    @property
    def tree_plane(self) -> str:
        """Which tree implementation serves draws right now (the
        ``tree`` label of ``info/telemetry/replay``)."""
        if self._host is not None or self._dtree is None:
            return "host"
        return "device"

    def _make_host_fallback(self) -> ReplayBuffer:
        buf = PrioritizedReplayBuffer(self.capacity, self._alpha)
        buf._rng = self._rng
        if self._dtree is not None:
            # the spill rings own the priorities from here on: pull
            # the (usually still pristine) device leaves across once
            buf._set_priority_state(
                {
                    "leaf_values": self._dtree.leaf_values(self._size),
                    "max_priority": self._max_priority,
                }
            )
            self._dtree = None
            self._tree_sample_fns = {}
            self._tree_draw_fns = {}
            return buf
        # spill happens at first insert, before any priority write:
        # handing over the (still pristine) trees keeps one source of
        # truth if callers pre-seeded priorities
        buf._sum_tree = self._sum_tree
        buf._min_tree = self._min_tree
        buf._max_priority = self._max_priority
        return buf

    # -- device-tree priority writes ------------------------------------

    def update_priorities(
        self, idx, priorities: np.ndarray
    ) -> None:
        """Host-tree mode: the mixin's numpy tree writes. Device-tree
        mode: host alpha-power (the oracle transform), then one
        donated device update program; ``idx`` may be a host array or
        the device i32 indices a fused sample returned (no D2H)."""
        if self._dtree is None:
            return _PrioritySampling.update_priorities(
                self, idx, priorities
            )
        from ray_tpu.telemetry import metrics as telemetry_metrics

        powered, clamped = powered_priorities(priorities, self._alpha)
        self._dtree.set_powered(idx, powered)
        self._max_priority = max(
            self._max_priority, float(clamped.max())
        )
        telemetry_metrics.inc_tree_op(self._tree_op, "device")

    def refresh_priorities_stacked(
        self, idx, abs_td: np.ndarray, active
    ) -> None:
        """The superstep's PER refresh against the device tree: the
        stacked ``(k, B)`` |td| (one D2H — the host alpha-power needs
        it) powers host-side and lands in ONE stacked device update,
        applied in update order with the nan-guard's skipped slots
        masked out — exactly the host path's per-update
        ``update_priorities(idx[i], td[i] + 1e-6)`` loop."""
        from ray_tpu.telemetry import metrics as telemetry_metrics

        active = np.asarray(active, bool)
        if not active.any():
            return
        # the epsilon add stays in the |td| dtype (f32): the host call
        # site computes `pri[i] + 1e-6` under numpy's weak-scalar
        # promotion BEFORE the f64 cast inside update_priorities —
        # rounding it the same way here keeps the leaf stream (and the
        # max-priority watermark) bit-exact across tree planes
        powered, clamped = powered_priorities(
            np.asarray(abs_td) + 1e-6, self._alpha
        )
        if self._dtree is None:
            # spilled mid-superstep is impossible (feed construction
            # requires residency), but route host-tree mode through
            # the sequential oracle writes for completeness
            for i in range(len(active)):
                if active[i]:
                    _PrioritySampling.update_priorities(
                        self, np.asarray(idx)[i], abs_td[i] + 1e-6
                    )
            return
        self._dtree.set_powered(idx, powered, active=active)
        self._max_priority = max(
            self._max_priority, float(clamped[active].max())
        )
        telemetry_metrics.inc_tree_op(
            "update", "device", int(active.sum())
        )

    def _insert_priorities(self, idx, priorities) -> None:
        self._tree_op = "insert"
        try:
            self.update_priorities(
                idx, np.asarray(priorities, np.float64)
            )
        finally:
            self._tree_op = "update"

    def add_tree(
        self,
        tree: Dict[str, np.ndarray],
        priorities: Optional[np.ndarray] = None,
    ) -> None:
        if not tree:
            return
        n = int(next(iter(tree.values())).shape[0])
        if n == 0:
            return
        if priorities is None:
            priorities = np.full(n, self._max_priority)
        if self._host is not None:
            self._host.add_with_priorities(
                SampleBatch(tree), priorities
            )
            self._report_occupancy()
            return
        idx = (self._idx + np.arange(n)) % self.capacity
        DeviceReplayBuffer.add_tree(self, tree)
        if self._host is not None:  # this insert triggered the spill
            self._host.update_priorities(
                idx, np.asarray(priorities, np.float64)
            )
            return
        self._insert_priorities(idx, priorities)

    def add_device_tree(
        self,
        tree: Dict[str, Any],
        priorities: Optional[np.ndarray] = None,
    ) -> None:
        """Device-resident insert with the host priority protocol:
        new rows enter the sum/min trees at max priority (or the
        caller's), exactly like :meth:`add_tree` — the priority
        stream stays bit-exact whichever side the rows came from."""
        tree = dict(tree)
        if not tree:
            return
        n = int(next(iter(tree.values())).shape[0])
        if n == 0:
            return
        if priorities is None:
            priorities = np.full(n, self._max_priority)
        if self._host is not None:
            import jax

            self._host.add_with_priorities(
                SampleBatch(jax.device_get(tree)), priorities
            )
            self._report_occupancy()
            return
        idx = (self._idx + np.arange(n)) % self.capacity
        DeviceReplayBuffer.add_device_tree(self, tree)
        if self._host is not None:  # this insert triggered the spill
            self._host.update_priorities(
                idx, np.asarray(priorities, np.float64)
            )
            return
        self._insert_priorities(idx, priorities)

    # -- sampling --------------------------------------------------------

    def _build_tree_sample_fn(self, num_items: int, row_sharded: bool):
        """ONE program: prefix-descent draw → clip → IS weights → row
        gather (docs/data_plane.md "device sum tree"). Built and
        called in the f64 scope (the tree inputs); rows/weights leave
        as the learner's f32/u8 world with the same out-shardings the
        two-step path emitted."""
        import jax.numpy as jnp

        from ray_tpu import sharding as sharding_lib
        from ray_tpu.ops.segment_tree import draw_body

        gather_fn = self._gather_fn()
        cap = self._dtree.capacity

        def fn(sum_t, min_t, store, rand, size, beta):
            idx, weights, _ = draw_body(
                sum_t, min_t, rand, size, beta, cap
            )
            idx32 = idx.astype(jnp.int32)
            out = gather_fn(store, idx32)
            out["weights"] = weights
            return out, idx32

        row_spec = (
            sharding_lib.batch_sharded(self.mesh)
            if row_sharded
            else sharding_lib.replicated(self.mesh)
        )
        rep = sharding_lib.replicated(self.mesh)
        out_cols = {k: row_spec for k in self._meta}
        out_cols["weights"] = row_spec
        return sharding_lib.sharded_jit(
            fn,
            out_specs=(out_cols, rep),
            label=f"replay_draw_sample[{self.label}:{num_items}]",
        )

    def _tree_sample(self, num_items: int, beta: float):
        from ray_tpu import sharding as sharding_lib
        from ray_tpu.telemetry import metrics as telemetry_metrics

        rand = self._rng.random(num_items)
        row_sharded = (
            num_items % self._num_shards() == 0 and num_items > 0
        )
        key = (num_items, row_sharded)
        fn = self._tree_sample_fns.get(key)
        if fn is None:
            fn = self._tree_sample_fns[key] = (
                self._build_tree_sample_fn(num_items, row_sharded)
            )
        with sharding_lib.f64_scope():
            rows, idx = fn(
                self._dtree.sum_value,
                self._dtree.min_value,
                self._store,
                rand,
                np.int64(self._size),
                np.float64(beta),
            )
        # the generator's raw uniform stream is the draw's only
        # host-fed input — counted apart from payload, which is zero
        telemetry_metrics.add_h2d_bytes("replay_rng", rand.nbytes)
        telemetry_metrics.inc_tree_op("sample", "device")
        return DeviceTrainBatch(dict(rows), num_items, indices=idx)

    def sample(self, num_items: int, beta: float = 0.4):
        if self._host is not None:
            return self._host.sample(num_items, beta=beta)
        from ray_tpu.util import tracing

        with tracing.start_span(
            "replay:sample", n=num_items, tree=self.tree_plane
        ):
            if self._dtree is not None:
                return self._tree_sample(num_items, beta)
            import jax

            from ray_tpu import sharding as sharding_lib
            from ray_tpu.telemetry import metrics as telemetry_metrics

            idx, weights = self._draw_prioritized(num_items, beta)
            batch = self.gather(idx)
            # same layout as the gathered rows, so the learn program's
            # committed-input check sees one consistent batch tree
            spec = (
                sharding_lib.batch_sharded(self.mesh)
                if num_items % self._num_shards() == 0
                else sharding_lib.replicated(self.mesh)
            )
            telemetry_metrics.add_h2d_bytes(
                "replay_sample", weights.nbytes
            )
            batch.tree["weights"] = jax.device_put(weights, spec)
            return batch

    def draw_prioritized_sets_device(
        self, k: int, k_max: int, num_items: int, beta: float
    ):
        """The superstep's pre-drawn schedule against the DEVICE tree:
        ``k`` sequential host generator calls (the exact per-update
        stream order), padded host-side to ``k_max`` rows, one draw
        program → ``(k_max, B)`` device index/weight matrices laid out
        for the scan feed (indices replicated, weights row-sharded
        like every stacked extra column). Draws see window-start
        priorities — the documented within-chain staleness."""
        from ray_tpu import sharding as sharding_lib
        from ray_tpu.telemetry import metrics as telemetry_metrics

        rand = np.zeros((k_max, num_items), np.float64)
        for i in range(k):
            rand[i] = self._rng.random(num_items)
        key = (k_max, num_items)
        fn = self._tree_draw_fns.get(key)
        if fn is None:
            import jax.numpy as jnp

            from ray_tpu.ops.segment_tree import draw_body

            cap = self._dtree.capacity

            def prog(sum_t, min_t, r, size, beta_):
                idx, weights, _ = draw_body(
                    sum_t, min_t, r, size, beta_, cap
                )
                return idx.astype(jnp.int32), weights

            fn = self._tree_draw_fns[key] = sharding_lib.sharded_jit(
                prog,
                out_specs=(
                    sharding_lib.replicated(self.mesh),
                    sharding_lib.batch_sharded(
                        self.mesh, ndim_prefix=2
                    ),
                ),
                label=f"tree_draw_sets[{self.label}:{k_max}x{num_items}]",
            )
        with sharding_lib.f64_scope():
            idx, weights = fn(
                self._dtree.sum_value,
                self._dtree.min_value,
                rand,
                np.int64(self._size),
                np.float64(beta),
            )
        telemetry_metrics.add_h2d_bytes(
            "replay_rng", k * num_items * 8
        )
        telemetry_metrics.inc_tree_op("sample", "device", k)
        return idx, weights

    def get_state(self) -> Dict:
        state = super().get_state()
        if self._host is None:
            state["priorities"] = self._priority_state()
        return state

    def set_state(self, state: Dict) -> None:
        super().set_state(state)
        if "priorities" in state and self._host is None:
            self._set_priority_state(state["priorities"])

    def _priority_state(self) -> Dict:
        if self._dtree is None:
            return _PrioritySampling._priority_state(self)
        # same layout as the host trees' state: checkpoints move
        # freely between tree planes
        return {
            "leaf_values": self._dtree.leaf_values(self._size),
            "max_priority": self._max_priority,
        }

    def _set_priority_state(self, state: Dict) -> None:
        if self._dtree is None:
            return _PrioritySampling._set_priority_state(self, state)
        self._dtree.set_leaf_values(state["leaf_values"])
        self._max_priority = float(state.get("max_priority", 1.0))


class MultiAgentReplayBuffer:
    """Per-policy buffers (reference multi_agent_replay_buffer.py).

    ``device_resident=True`` stores each policy's rows on the learner
    mesh (:class:`DeviceReplayBuffer`); ``replay_columns_fn(pid,
    SampleBatch) -> dict`` converts fragments to the column tree the
    policy's learn program consumes (``JaxPolicy.replay_columns``) —
    applied ONCE at insert, so sampled batches feed
    ``learn_on_device_batch`` with zero further host work."""

    def __init__(
        self,
        capacity: int = 10000,
        prioritized: bool = False,
        alpha: float = 0.6,
        seed: Optional[int] = None,
        device_resident: bool = False,
        mesh=None,
        memory_cap_bytes: Optional[int] = None,
        replay_columns_fn: Optional[Callable] = None,
        device_tree: bool = False,
    ):
        self.capacity = capacity
        self.prioritized = prioritized
        self.alpha = alpha
        self.seed = seed
        self.device_resident = device_resident
        self.mesh = mesh
        self.memory_cap_bytes = memory_cap_bytes
        self.replay_columns_fn = replay_columns_fn
        self.device_tree = device_tree
        self.buffers: Dict[str, ReplayBuffer] = {}

    def _buffer(self, pid: str) -> ReplayBuffer:
        if pid not in self.buffers:
            if self.device_resident:
                cls = (
                    DevicePrioritizedReplayBuffer
                    if self.prioritized
                    else DeviceReplayBuffer
                )
                kwargs = dict(
                    mesh=self.mesh,
                    memory_cap_bytes=self.memory_cap_bytes,
                    label=pid,
                )
                if self.prioritized:
                    self.buffers[pid] = cls(
                        self.capacity,
                        self.alpha,
                        self.seed,
                        device_tree=self.device_tree,
                        **kwargs,
                    )
                else:
                    self.buffers[pid] = cls(
                        self.capacity, self.seed, **kwargs
                    )
            elif self.prioritized:
                self.buffers[pid] = PrioritizedReplayBuffer(
                    self.capacity, self.alpha, self.seed
                )
            else:
                self.buffers[pid] = ReplayBuffer(self.capacity, self.seed)
        return self.buffers[pid]

    def add(self, batch) -> None:
        from ray_tpu.data.sample_batch import (
            DEFAULT_POLICY_ID,
            MultiAgentBatch,
        )

        if isinstance(batch, SampleBatch):
            batch = batch.as_multi_agent()
        for pid, sb in batch.policy_batches.items():
            buf = self._buffer(pid)
            if isinstance(buf, DeviceReplayBuffer):
                if self.replay_columns_fn is not None:
                    tree = self.replay_columns_fn(pid, sb)
                else:
                    tree = {
                        k: np.asarray(v)
                        for k, v in sb.items()
                        if isinstance(v, np.ndarray)
                        and v.dtype != object
                    }
                buf.add_tree(tree)
            else:
                buf.add(sb)

    def add_device_tree(
        self, tree: Dict[str, Any], policy_id: Optional[str] = None
    ) -> None:
        """Device-resident insert for the jax rollout lane: rows from
        an in-program rollout land in ``policy_id``'s buffer without
        touching the host. Requires ``device_resident=True`` (a host
        ring can't absorb device rows without the very D2H round trip
        this path exists to avoid)."""
        from ray_tpu.data.sample_batch import DEFAULT_POLICY_ID

        buf = self._buffer(policy_id or DEFAULT_POLICY_ID)
        if not isinstance(buf, DeviceReplayBuffer):
            raise TypeError(
                "add_device_tree needs a device-resident buffer "
                "(config replay_device_resident)"
            )
        buf.add_device_tree(tree)

    def sample(self, num_items: int, **kwargs):
        from ray_tpu.data.sample_batch import MultiAgentBatch

        out = {}
        for pid, buf in self.buffers.items():
            if len(buf) >= num_items:
                out[pid] = (
                    buf.sample(num_items, **kwargs)
                    if isinstance(
                        buf,
                        (
                            PrioritizedReplayBuffer,
                            DevicePrioritizedReplayBuffer,
                        ),
                    )
                    else buf.sample(num_items)
                )
        return MultiAgentBatch(out, num_items)

    def __len__(self) -> int:
        return max((len(b) for b in self.buffers.values()), default=0)

    def get_state(self) -> Dict:
        """Per-policy buffer states, checkpointable through
        ``Algorithm.save_checkpoint`` (all arrays host numpy — device
        rings are pulled back and re-uploaded on restore)."""
        return {pid: b.get_state() for pid, b in self.buffers.items()}

    def set_state(self, state: Dict) -> None:
        for pid, s in state.items():
            self._buffer(pid).set_state(s)
