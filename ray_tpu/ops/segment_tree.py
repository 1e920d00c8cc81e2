"""Vectorized segment trees for prioritized replay.

Counterpart of the reference's ``rllib/execution/segment_tree.py:172``
(SumSegmentTree/MinSegmentTree). The reference uses per-element python
recursion; here the tree is a flat numpy array with vectorized batch
operations (``set_items``, ``sample_idx`` for a whole batch at once) since
replay sampling happens on the host at batch granularity.
"""

from __future__ import annotations

import numpy as np


class SegmentTree:
    def __init__(self, capacity: int, operation, neutral_element: float):
        assert capacity > 0 and capacity & (capacity - 1) == 0, (
            "capacity must be a positive power of 2"
        )
        self.capacity = capacity
        self.operation = operation
        self.neutral_element = neutral_element
        self.value = np.full(2 * capacity, neutral_element, dtype=np.float64)

    def set_items(self, idx: np.ndarray, val: np.ndarray) -> None:
        idx = np.asarray(idx, dtype=np.int64) + self.capacity
        self.value[idx] = val
        idx //= 2
        while np.any(idx >= 1):
            live = idx[idx >= 1]
            self.value[live] = self.operation(
                self.value[2 * live], self.value[2 * live + 1]
            )
            idx //= 2
            idx = idx[idx >= 1]
            if len(idx) == 0:
                break

    def __setitem__(self, idx, val):
        self.set_items(np.atleast_1d(idx), np.atleast_1d(val))

    def __getitem__(self, idx):
        return self.value[self.capacity + idx]

    def reduce(self, start: int = 0, end: int | None = None) -> float:
        if end is None:
            end = self.capacity
        if end < 0:
            end += self.capacity
        result = self.neutral_element
        start += self.capacity
        end += self.capacity
        while start < end:
            if start & 1:
                result = self.operation(result, self.value[start])
                start += 1
            if end & 1:
                end -= 1
                result = self.operation(result, self.value[end])
            start //= 2
            end //= 2
        return result


class SumSegmentTree(SegmentTree):
    def __init__(self, capacity: int):
        super().__init__(capacity, np.add, 0.0)

    def sum(self, start: int = 0, end: int | None = None) -> float:
        return self.reduce(start, end)

    def find_prefixsum_idx(self, prefixsum: np.ndarray) -> np.ndarray:
        """Vectorized: for each p in prefixsum, find the highest leaf i such
        that sum(leaves[0..i-1]) <= p. Descends all queries in lockstep."""
        p = np.asarray(prefixsum, dtype=np.float64).copy()
        idx = np.ones(len(p), dtype=np.int64)
        while idx[0] < self.capacity:
            left = 2 * idx
            left_vals = self.value[left]
            go_right = p > left_vals
            p = np.where(go_right, p - left_vals, p)
            idx = np.where(go_right, left + 1, left)
        return idx - self.capacity


class MinSegmentTree(SegmentTree):
    def __init__(self, capacity: int):
        super().__init__(capacity, np.minimum, float("inf"))

    def min(self, start: int = 0, end: int | None = None) -> float:
        return self.reduce(start, end)


# -- device-resident tree (docs/data_plane.md "device sum tree") -------
#
# The same trees as float64 mesh arrays, with insert/update/
# prefix-sum-sample as jit'd programs. The determinism contract: given
# the SAME already-alpha-powered leaf stream the host trees receive,
# every device op is an exact-rounding f64 operation (add, sub, div,
# compare, min — all bitwise-reproducible between numpy and XLA on the
# measured backends), so index draws and sampled priorities reproduce
# the host trees bit-exactly. The alpha-power itself is NOT exact
# across backends (libm vs XLA pow differ in the last ulp), which is
# why `_PrioritySampling` keeps that transform on the host for both
# planes and ships powered leaf values here; the IS-weight beta-power
# runs in-program because its f64 last-ulp is absorbed by the f32
# cast the host path applies anyway (parity-suite asserted).


# ray-tpu: device-fn f64
def reduce_range_body(value, size, op, neutral, capacity: int):
    """In-program counterpart of ``SegmentTree.reduce(0, size)`` with a
    FIXED trip count (one executable serves every ``size``): the same
    node decomposition, visited in the same order, accumulated with the
    same f64 ops — bit-exact by construction. ``size`` is a traced
    scalar."""
    import jax.numpy as jnp

    levels = capacity.bit_length()  # log2(capacity) + 1

    s = jnp.int64(capacity)
    e = jnp.int64(capacity) + size
    r = jnp.float64(neutral)
    for _ in range(levels):
        active = s < e
        # host loop body order: the start-side node first, then the
        # end-side node — the f64 accumulation order is part of the
        # bit-exactness contract
        c1 = active & (s % 2 == 1)
        r = jnp.where(c1, op(r, value[s]), r)
        s = jnp.where(c1, s + 1, s)
        c2 = active & (e % 2 == 1)
        e2 = e - 1
        r = jnp.where(c2, op(r, value[e2]), r)
        e = jnp.where(c2, e2, e)
        # monotone: once s >= e, floor-halving keeps s >= e, so the
        # extra fixed-trip iterations are no-ops
        s = s // 2
        e = e // 2
    return r


# ray-tpu: device-fn f64
def find_prefixsum_body(value, prefixsum, capacity: int):
    """In-program ``SumSegmentTree.find_prefixsum_idx``: the lockstep
    root→leaf descent, one comparison + exact f64 subtraction per
    level. XLA only: the tree is f64 (the determinism contract above)
    and Mosaic has no f64 vectors, so no Pallas descent compiles on a
    TPU (PR 21)."""
    import jax.numpy as jnp

    p = prefixsum
    idx = jnp.ones(p.shape, jnp.int64)
    for _ in range(capacity.bit_length() - 1):
        left = 2 * idx
        left_vals = value[left]
        go_right = p > left_vals
        p = jnp.where(go_right, p - left_vals, p)
        idx = jnp.where(go_right, left + 1, left)
    return idx - capacity


# ray-tpu: device-fn f64
def draw_body(
    sum_value,
    min_value,
    rand,
    size,
    beta,
    capacity: int,
):
    """The whole stratified proportional draw of
    ``_PrioritySampling._draw_prioritized`` as one in-program body:
    ``rand`` is the host generator's raw uniform stream (the ONLY
    host-fed input — the bit-exact generator invariant), ``size`` /
    ``beta`` are traced scalars so buffer growth and beta annealing
    never retrace. Returns ``(idx int64, weights f32, p_sample f64)``;
    every op except the two beta-powers is exact."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("replay/draw"):
        num_items = rand.shape[-1]
        total = reduce_range_body(
            sum_value, size, jnp.add, 0.0, capacity
        )
        strata = jnp.arange(num_items, dtype=jnp.float64)
        mass = (rand + strata) / num_items * total
        idx = find_prefixsum_body(sum_value, mass, capacity)
        idx = jnp.clip(idx, 0, size - 1)

        p_min = (
            reduce_range_body(
                min_value, size, jnp.minimum, float("inf"), capacity
            )
            / total
        )
        max_weight = (p_min * size) ** (-beta)
        p_sample = sum_value[capacity + idx] / total
        weights = ((p_sample * size) ** (-beta) / max_weight).astype(
            jnp.float32
        )
    return idx, weights, p_sample


# ray-tpu: device-fn f64
def _rebuild_body(arr, op, capacity: int):
    """Recompute every internal node bottom-up. Bit-identical to the
    host's incremental ancestor updates: each node is always exactly
    ``op(child_left, child_right)`` of the FINAL children — the same
    two-operand f64 op the host applies.

    Each level is computed from the level just computed, held as a
    value of its own, and the ``2 x capacity`` array is written once:
    what a level costs is what it is wide. The children are taken
    apart by two ``lax.slice`` of stride 2, which the TPU runs as
    strided copies. Not ``pairs = level.reshape(n, 2)``: the chip
    pads a minor dimension of 2 to 128 lanes, and read out of the
    whole array that was a relayout of ALL of it at every level
    (64 x 90 us of a 6.2 ms program at 131,072 leaves, PR 37); not
    ``level[0::2]`` either, which ``jax.numpy`` lowers to a gather."""
    import jax.numpy as jnp
    from jax import lax

    level = arr[capacity:]
    levels = [level]
    while level.shape[0] > 1:
        n = level.shape[0]
        level = op(
            lax.slice(level, (0,), (n,), (2,)),
            lax.slice(level, (1,), (n,), (2,)),
        )
        levels.append(level)
    # slot 0 is the one the host layout never reads; the root is slot 1
    return jnp.concatenate([arr[:1]] + levels[::-1])


class DeviceSumTree:
    """The sum+min segment-tree pair as device-resident f64 mesh
    arrays (replicated placement: the draw is a global tree walk over
    ``2·capacity·8`` bytes — tiny next to the replay rows — and every
    shard needs the full prefix structure).

    All programs build AND run inside ``sharding.f64_scope()`` so the
    f64 state survives jax's x64-off canonicalization; outputs that
    feed the learner world (indices, IS weights) leave as i32/f32.
    Updates take ALREADY-POWERED leaf values (the host keeps the
    alpha-power — see module comment) padded to power-of-two row
    buckets with a validity mask, so ragged insert tails never
    retrace; masked rows scatter to flat index 0, the one slot the
    host layout never reads."""

    def __init__(
        self,
        capacity: int,
        mesh=None,
        label: str = "default_policy",
    ):
        assert capacity > 0 and capacity & (capacity - 1) == 0, (
            "capacity must be a positive power of 2"
        )
        import jax
        import jax.numpy as jnp

        from ray_tpu import sharding as sharding_lib

        self.capacity = int(capacity)
        self.mesh = mesh if mesh is not None else sharding_lib.get_mesh()
        self.label = label
        self._update_fns = {}
        self._draw_fns = {}
        with sharding_lib.f64_scope():
            rep = sharding_lib.replicated(self.mesh)
            self.sum_value = jax.device_put(
                jnp.zeros(2 * self.capacity, jnp.float64), rep
            )
            self.min_value = jax.device_put(
                jnp.full(2 * self.capacity, jnp.inf, jnp.float64), rep
            )

    # -- updates --------------------------------------------------------

    def _build_update_fn(self, u: int, bp: int):
        import jax
        import jax.numpy as jnp

        from ray_tpu import sharding as sharding_lib

        cap = self.capacity

        # ray-tpu: f64
        def fn(sum_t, min_t, idx, vals, mask):
            with jax.named_scope("replay/refresh"):
                for i in range(u):
                    flat = jnp.where(mask[i], cap + idx[i], 0)
                    sum_t = sum_t.at[flat].set(
                        jnp.where(mask[i], vals[i], sum_t[flat])
                    )
                    min_t = min_t.at[flat].set(
                        jnp.where(mask[i], vals[i], min_t[flat])
                    )
                sum_t = _rebuild_body(sum_t, jnp.add, cap)
                min_t = _rebuild_body(min_t, jnp.minimum, cap)
            return sum_t, min_t

        rep = sharding_lib.replicated(self.mesh)
        return sharding_lib.sharded_jit(
            fn,
            out_specs=(rep, rep),
            donate_argnums=(0, 1),
            label=f"tree_update[{self.label}:{u}x{bp}]",
        )

    def set_powered(self, idx, powered, active=None) -> None:
        """Write already-alpha-powered leaf values. ``idx``/``powered``
        are ``(n,)`` or ``(U, B)`` (the superstep's stacked refresh,
        applied in update order — cross-update overlapping draws
        resolve exactly as the host's sequential writes); either may
        live on host or device. ``active`` masks whole updates (the
        nan-guard's skipped slots refresh nothing)."""
        import jax
        import numpy as np_

        from ray_tpu import sharding as sharding_lib

        idx_arr = idx if isinstance(idx, jax.Array) else np_.asarray(idx)
        stacked = idx_arr.ndim == 2
        u = int(idx_arr.shape[0]) if stacked else 1
        n = int(idx_arr.shape[-1])
        bp = 1 << max(0, (n - 1).bit_length())  # next pow2 bucket
        mask = np_.zeros((u, bp), bool)
        mask[:, :n] = True
        if active is not None:
            mask &= np_.asarray(active, bool).reshape(u, 1)

        def pad(v, fill):
            if isinstance(v, jax.Array):
                v = v.reshape(u, n)
                if bp == n:
                    return v
                import jax.numpy as jnp

                return jnp.pad(
                    v, ((0, 0), (0, bp - n)), constant_values=fill
                )
            v = np_.asarray(v).reshape(u, n)
            if bp == n:
                return v
            out = np_.full((u, bp), fill, v.dtype)
            out[:, :n] = v
            return out

        key = (u, bp)
        fn = self._update_fns.get(key)
        if fn is None:
            fn = self._update_fns[key] = self._build_update_fn(u, bp)
        rep = sharding_lib.replicated(self.mesh)
        with sharding_lib.f64_scope():
            # jax types an argument by the mesh it lives on, so the
            # host and the device spelling of one (u, bp) argument
            # would each trace the program: host inputs go onto the
            # tree's mesh first and every call sees one type
            idx_p = pad(idx_arr, 0)
            if not isinstance(idx_p, jax.Array):
                idx_p = jax.device_put(idx_p.astype(np_.int32), rep)
            vals_p = pad(powered, 0.0)
            if not isinstance(vals_p, jax.Array):
                vals_p = jax.device_put(
                    vals_p.astype(np_.float64), rep
                )
            self.sum_value, self.min_value = fn(
                self.sum_value, self.min_value, idx_p, vals_p, mask
            )

    # -- draws ----------------------------------------------------------

    def draw(self, rand, size: int, beta: float):
        """Standalone draw program (tests, benches; the buffers fuse
        this body with their row gather instead): host uniform stream
        in, ``(idx i32, weights f32)`` device arrays out."""
        import numpy as np_

        from ray_tpu import sharding as sharding_lib

        rand = np_.asarray(rand, np_.float64)
        key = rand.shape
        fn = self._draw_fns.get(key)
        if fn is None:
            import jax.numpy as jnp

            cap = self.capacity

            # ray-tpu: f64
            def prog(sum_t, min_t, r, size_, beta_):
                idx, weights, _ = draw_body(
                    sum_t, min_t, r, size_, beta_, cap
                )
                return idx.astype(jnp.int32), weights

            rep = sharding_lib.replicated(self.mesh)
            fn = self._draw_fns[key] = sharding_lib.sharded_jit(
                prog,
                out_specs=(rep, rep),
                label=f"tree_draw[{self.label}:{'x'.join(map(str, key))}]",
            )
        with sharding_lib.f64_scope():
            return fn(
                self.sum_value,
                self.min_value,
                rand,
                np_.int64(size),
                np_.float64(beta),
            )

    # -- state ----------------------------------------------------------

    def leaf_values(self, size: int):
        """Host f64 copy of the first ``size`` (already-powered)
        leaves — checkpoint state, spill handover, tests. The slice
        happens host-side: an eager device op on an f64 array outside
        the x64 scope would be silently re-canonicalized."""
        import jax

        leaves = np.asarray(
            jax.device_get(self.sum_value), np.float64
        )
        return leaves[self.capacity : self.capacity + int(size)].copy()

    def set_leaf_values(self, vals) -> None:
        vals = np.asarray(vals, np.float64)
        if len(vals):
            self.set_powered(np.arange(len(vals)), vals)
