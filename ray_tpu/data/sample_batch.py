"""Columnar trajectory containers.

TPU-native counterpart of the reference's ``rllib/policy/sample_batch.py:30``
(SampleBatch) and ``:1028`` (MultiAgentBatch). A SampleBatch is a dict of
equal-length numpy arrays on the host; it converts losslessly to a JAX pytree
(``to_device``) so a whole batch can be fed to a jitted learner step in one
transfer. All mutation happens on host numpy; on-device data is immutable.

Design differences from the reference (deliberate, TPU-first):
  - No lazy compression codecs in the hot path; batches move through the
    shared-memory object plane zero-copy instead.
  - ``right_zero_pad`` / ``timeslices`` always produce *static* shapes: TPU
    compilation caches require fixed (B, T).
"""

from __future__ import annotations

import itertools
from typing import Dict, Iterator, List, Optional, Sequence, Union

import numpy as np

# Column name constants (parity with reference sample_batch.py:60-117).
OBS = "obs"
NEXT_OBS = "new_obs"
ACTIONS = "actions"
REWARDS = "rewards"
PREV_ACTIONS = "prev_actions"
PREV_REWARDS = "prev_rewards"
TERMINATEDS = "dones"
TRUNCATEDS = "truncateds"
INFOS = "infos"
EPS_ID = "eps_id"
UNROLL_ID = "unroll_id"
AGENT_INDEX = "agent_index"
T = "t"
ACTION_DIST_INPUTS = "action_dist_inputs"
ACTION_LOGP = "action_logp"
ACTION_PROB = "action_prob"
VF_PREDS = "vf_preds"
ADVANTAGES = "advantages"
VALUE_TARGETS = "value_targets"
# of a policy that commits a block of tokens a step: the pass that
# committed each token (models/sequence_lm/generation.py)
UNMASK_STEP = "unmask_step"
SEQ_LENS = "seq_lens"
STATE_IN_PREFIX = "state_in_"
STATE_OUT_PREFIX = "state_out_"


from ray_tpu.ops.framestack import FRAMES as _FRAME_POOL


def _is_array_col(key: str) -> bool:
    # the frame POOL (ops/framestack) is not a per-row column — its
    # length is rows + stack_k - 1 by design
    return key not in (SEQ_LENS, _FRAME_POOL)


def _reject_frame_pool(batch, op: str) -> None:
    """Row transforms (slice/shuffle/...) cannot preserve pool/index
    consistency; the frame-pool format is a TRANSFER format (built
    worker-side by ``compress_for_shipping`` or learner-side before
    ``learn_on_batch``), not a storage format. ``concat_samples`` is
    the one supported transform (pool merge + index offset). Fail
    loudly instead of silently dropping the pool."""
    if _FRAME_POOL in batch:
        raise ValueError(
            f"SampleBatch.{op} does not support the deduplicated "
            f"frame-pool format ({_FRAME_POOL!r}); materialize stacked "
            "observations first (ops/framestack.build_stacks) or "
            "apply the transform before decomposing"
        )


class SampleBatch(dict):
    """A dict of numpy arrays with equal leading dimension ("count").

    Reference parity: ``rllib/policy/sample_batch.py:30``.
    """

    # Re-export constants as class attributes for RLlib-style access
    # (SampleBatch.OBS etc).
    OBS = OBS
    NEXT_OBS = NEXT_OBS
    ACTIONS = ACTIONS
    REWARDS = REWARDS
    PREV_ACTIONS = PREV_ACTIONS
    PREV_REWARDS = PREV_REWARDS
    TERMINATEDS = TERMINATEDS
    DONES = TERMINATEDS
    TRUNCATEDS = TRUNCATEDS
    INFOS = INFOS
    EPS_ID = EPS_ID
    UNROLL_ID = UNROLL_ID
    AGENT_INDEX = AGENT_INDEX
    T = T
    ACTION_DIST_INPUTS = ACTION_DIST_INPUTS
    ACTION_LOGP = ACTION_LOGP
    ACTION_PROB = ACTION_PROB
    VF_PREDS = VF_PREDS
    ADVANTAGES = ADVANTAGES
    VALUE_TARGETS = VALUE_TARGETS
    UNMASK_STEP = UNMASK_STEP
    SEQ_LENS = SEQ_LENS

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        for k, v in list(self.items()):
            if isinstance(v, (list, tuple)) and k != INFOS:
                self[k] = np.asarray(v)
        lengths = {
            k: len(v)
            for k, v in self.items()
            if _is_array_col(k) and hasattr(v, "__len__")
        }
        if lengths:
            counts = set(lengths.values())
            if len(counts) != 1:
                raise ValueError(
                    f"All columns must have equal length, got {lengths}"
                )
            self.count = counts.pop()
        else:
            self.count = 0

    # -- Basic info ------------------------------------------------------

    def __len__(self) -> int:
        return self.count

    @property
    def agent_steps(self) -> int:
        return self.count

    @property
    def env_steps_(self) -> int:
        return self.count

    def env_steps(self) -> int:
        return self.count

    def size_bytes(self) -> int:
        return sum(
            v.nbytes for v in self.values() if isinstance(v, np.ndarray)
        )

    # -- Transformations --------------------------------------------------

    def copy(self, shallow: bool = False) -> "SampleBatch":
        if shallow:
            return SampleBatch({k: v for k, v in self.items()})
        return SampleBatch(
            {
                k: (v.copy() if isinstance(v, np.ndarray) else v)
                for k, v in self.items()
            }
        )

    def rows(self) -> Iterator[Dict[str, np.ndarray]]:
        for i in range(self.count):
            yield {k: v[i] for k, v in self.items() if _is_array_col(k)}

    def columns(self, keys: Sequence[str]) -> List[np.ndarray]:
        return [self[k] for k in keys]

    def slice(self, start: int, end: int) -> "SampleBatch":
        """Row-slice [start, end) of every column (reference :407)."""
        _reject_frame_pool(self, "slice")
        return SampleBatch(
            {k: v[start:end] for k, v in self.items() if _is_array_col(k)}
        )

    def __getitem__(self, key):
        if isinstance(key, slice):
            return self.slice(
                key.start or 0, key.stop if key.stop is not None else self.count
            )
        return super().__getitem__(key)

    def select(self, keys: Sequence[str]) -> "SampleBatch":
        return SampleBatch({k: self[k] for k in keys if k in self})

    def shuffle(self, rng: Optional[np.random.Generator] = None) -> "SampleBatch":
        """In-place row permutation (reference :317)."""
        rng = rng or np.random.default_rng()
        perm = rng.permutation(self.count)
        for k, v in self.items():
            if _is_array_col(k) and isinstance(v, np.ndarray):
                self[k] = v[perm]
        return self

    def timeslices(self, size: int) -> List["SampleBatch"]:
        """Chop into fixed-size row slices (reference :478). The final
        partial slice is dropped to keep static shapes for TPU."""
        return [
            self.slice(i, i + size)
            for i in range(0, self.count - size + 1, size)
        ]

    def minibatches(
        self, minibatch_size: int, num_epochs: int = 1,
        rng: Optional[np.random.Generator] = None,
    ) -> Iterator["SampleBatch"]:
        """Yield shuffled fixed-size minibatches for SGD epochs."""
        rng = rng or np.random.default_rng()
        for _ in range(num_epochs):
            perm = rng.permutation(self.count)
            for i in range(0, self.count - minibatch_size + 1, minibatch_size):
                idx = perm[i : i + minibatch_size]
                yield SampleBatch(
                    {
                        k: v[idx]
                        for k, v in self.items()
                        if _is_array_col(k) and isinstance(v, np.ndarray)
                    }
                )

    def right_zero_pad(self, max_len: int) -> "SampleBatch":
        """Pad every column's leading dim up to a multiple handling
        (reference :536). Produces exactly ``max_len`` rows."""
        if self.count > max_len:
            raise ValueError(f"count {self.count} > max_len {max_len}")
        pad = max_len - self.count
        out = {}
        for k, v in self.items():
            if _is_array_col(k) and isinstance(v, np.ndarray):
                pad_width = [(0, pad)] + [(0, 0)] * (v.ndim - 1)
                out[k] = np.pad(v, pad_width)
        sb = SampleBatch(out)
        sb[SEQ_LENS] = np.array([self.count], dtype=np.int32)
        return sb

    def split_by_episode(self) -> List["SampleBatch"]:
        """Split along EPS_ID boundaries (reference :350)."""
        if EPS_ID not in self:
            return [self]
        eps = np.asarray(self[EPS_ID])
        boundaries = np.where(eps[1:] != eps[:-1])[0] + 1
        starts = [0] + boundaries.tolist() + [self.count]
        return [
            self.slice(starts[i], starts[i + 1])
            for i in range(len(starts) - 1)
        ]

    def to_device(self, sharding=None):
        """Move all array columns to accelerator as one pytree transfer."""
        import jax

        arrs = {
            k: v for k, v in self.items()
            if isinstance(v, np.ndarray) and v.dtype != object
        }
        if sharding is not None:
            return jax.device_put(arrs, sharding)
        return jax.device_put(arrs)

    def as_multi_agent(self) -> "MultiAgentBatch":
        return MultiAgentBatch({DEFAULT_POLICY_ID: self}, self.count)

    def __repr__(self):
        return f"SampleBatch({self.count}: {list(self.keys())})"


DEFAULT_POLICY_ID = "default_policy"


def _concat_arrays(vals: List[np.ndarray]) -> np.ndarray:
    """Row-concat with a preallocated output for uniform-dtype columns.

    This concat sits on the sampling pipeline's critical path (the
    prefetch thread assembles train batches from rollout fragments while
    the SGD nest runs), so it avoids the generic ``np.concatenate``
    dtype-promotion machinery: one ``np.empty`` of the final column and
    a single-copy assemble. Mixed dtypes/shapes fall through to numpy's
    promotion rules unchanged."""
    if len(vals) == 1:
        # still a copy: fragments can be read-only views of the shm
        # object plane, and concat output has always been writable
        return vals[0].copy()
    first = vals[0]
    dtype, trail = first.dtype, first.shape[1:]
    if any(
        v.dtype != dtype or v.shape[1:] != trail for v in vals[1:]
    ):
        return np.concatenate(vals, axis=0)
    total = sum(v.shape[0] for v in vals)
    out = np.empty((total,) + trail, dtype)
    pos = 0
    for v in vals:
        n = v.shape[0]
        out[pos : pos + n] = v
        pos += n
    return out


def concat_samples(
    batches: Sequence[Union[SampleBatch, "MultiAgentBatch"]]
) -> Union[SampleBatch, "MultiAgentBatch"]:
    """Concatenate row-wise (reference module-level concat_samples :1245)."""
    if not batches:
        return SampleBatch()
    if isinstance(batches[0], MultiAgentBatch):
        return MultiAgentBatch.concat_samples(list(batches))
    from ray_tpu.ops.framestack import FRAME_IDX as _FRAME_IDX

    pooled = [_FRAME_POOL in b for b in batches]
    if any(pooled) and not all(pooled):
        # compression is per-fragment and data-dependent (the sliding
        # window verification can fail on one fragment and pass on its
        # siblings), so mixed inputs must degrade to stacks — losing
        # the dedup win, never correctness
        from ray_tpu.ops.framestack import materialize_fragment

        # stack depth comes from a stacked sibling's obs channel dim
        # (the mixed case guarantees one exists)
        stack_k = next(
            int(np.asarray(b[OBS]).shape[-1])
            for b in batches
            if _FRAME_POOL not in b and OBS in b
        )
        batches = [
            SampleBatch(materialize_fragment(dict(b), stack_k))
            if _FRAME_POOL in b
            else b
            for b in batches
        ]
        pooled = [False] * len(batches)
    if any(pooled):
        # frame-pool batches concatenate by merging pools and
        # offsetting each batch's first-frame indices — this keeps
        # worker-side compressed fragments compressed through the
        # driver concat (no re-materialization of stacks)
        out = {}
        pools = [np.asarray(b[_FRAME_POOL]) for b in batches]
        offsets = np.cumsum([0] + [len(p) for p in pools[:-1]])
        out[_FRAME_POOL] = _concat_arrays(pools)
        # offset-add straight into the preallocated index column (the
        # per-batch `idx + off` temporaries were a copy each)
        idxs = [np.asarray(b[_FRAME_IDX], np.int32) for b in batches]
        idx_out = np.empty(sum(len(i) for i in idxs), np.int32)
        pos = 0
        for v, off in zip(idxs, offsets):
            np.add(v, np.int32(off), out=idx_out[pos : pos + len(v)])
            pos += len(v)
        out[_FRAME_IDX] = idx_out
        keys = [
            k
            for k in batches[0].keys()
            if k not in (_FRAME_POOL, _FRAME_IDX)
        ]
    else:
        out = {}
        keys = batches[0].keys()
    for k in keys:
        if not _is_array_col(k):
            continue
        vals = [b[k] for b in batches if k in b]
        if vals and isinstance(vals[0], np.ndarray):
            out[k] = _concat_arrays(vals)
        else:
            out[k] = list(itertools.chain.from_iterable(vals))
    return SampleBatch(out)


class MultiAgentBatch:
    """Maps policy id -> SampleBatch (reference sample_batch.py:1028)."""

    def __init__(self, policy_batches: Dict[str, SampleBatch], env_steps: int):
        self.policy_batches = policy_batches
        self.count = env_steps

    def env_steps(self) -> int:
        return self.count

    def agent_steps(self) -> int:
        return sum(b.count for b in self.policy_batches.values())

    def size_bytes(self) -> int:
        return sum(b.size_bytes() for b in self.policy_batches.values())

    def timeslices(self, size: int) -> List["MultiAgentBatch"]:
        out = []
        slices = {
            pid: b.timeslices(size) for pid, b in self.policy_batches.items()
        }
        n = min(len(s) for s in slices.values()) if slices else 0
        for i in range(n):
            out.append(
                MultiAgentBatch(
                    {pid: s[i] for pid, s in slices.items()}, size
                )
            )
        return out

    @staticmethod
    def concat_samples(batches: List["MultiAgentBatch"]) -> "MultiAgentBatch":
        policy_batches: Dict[str, List[SampleBatch]] = {}
        env_steps = 0
        for b in batches:
            if isinstance(b, SampleBatch):
                b = b.as_multi_agent()
            env_steps += b.env_steps()
            for pid, sb in b.policy_batches.items():
                policy_batches.setdefault(pid, []).append(sb)
        return MultiAgentBatch(
            {pid: concat_samples(sbs) for pid, sbs in policy_batches.items()},
            env_steps,
        )

    @staticmethod
    def wrap_as_needed(
        policy_batches: Dict[str, SampleBatch], env_steps: int
    ) -> Union[SampleBatch, "MultiAgentBatch"]:
        if len(policy_batches) == 1 and DEFAULT_POLICY_ID in policy_batches:
            return policy_batches[DEFAULT_POLICY_ID]
        return MultiAgentBatch(policy_batches, env_steps)

    def copy(self) -> "MultiAgentBatch":
        return MultiAgentBatch(
            {pid: b.copy() for pid, b in self.policy_batches.items()},
            self.count,
        )

    def __repr__(self):
        return f"MultiAgentBatch({self.count}: {list(self.policy_batches)})"
