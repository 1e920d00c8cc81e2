"""Deduplicated framestack transfer (ops/framestack + JaxPolicy)."""

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.algorithms.ppo.ppo import PPOJaxPolicy
from ray_tpu.data.sample_batch import SampleBatch
from ray_tpu.ops.framestack import (
    FRAME_IDX,
    FRAMES,
    build_stacks,
    decompose_stacked_obs,
    frame_stream_columns,
)

H, W, K, A = 12, 12, 4, 3


def _stream(rng, n):
    return rng.integers(0, 255, (n + K - 1, H, W, 1)).astype(np.uint8)


def _stacked_from_stream(frames, n):
    return np.stack(
        [
            np.concatenate(
                [frames[i + j] for j in range(K)], axis=-1
            )
            for i in range(n)
        ]
    )


def test_build_stacks_matches_numpy():
    rng = np.random.default_rng(0)
    n = 10
    frames = _stream(rng, n)
    want = _stacked_from_stream(frames, n)
    got = np.asarray(
        build_stacks(
            jnp.asarray(frames),
            jnp.arange(n, dtype=jnp.int32),
            K,
        )
    )
    np.testing.assert_array_equal(got, want)


def test_decompose_roundtrip_and_rejection():
    rng = np.random.default_rng(1)
    n = 8
    frames = _stream(rng, n)
    stacked = _stacked_from_stream(frames, n)
    out = decompose_stacked_obs(stacked)
    assert out is not None
    stream, idx = out
    np.testing.assert_array_equal(stream, frames)
    rebuilt = np.asarray(
        build_stacks(jnp.asarray(stream), jnp.asarray(idx), K)
    )
    np.testing.assert_array_equal(rebuilt, stacked)
    # shuffled rows are not a sliding window
    assert decompose_stacked_obs(stacked[::-1].copy()) is None


def _ppo(mesh=None):
    cfg = {
        "model": {
            # conv stack sized for the 12x12 test frames
            "conv_filters": [[8, [4, 4], [2, 2]], [16, [5, 5], [1, 1]]],
            "post_fcnet_hiddens": [16],
        },
        "train_batch_size": 16,
        "sgd_minibatch_size": 8,
        "num_sgd_iter": 2,
        "lr": 1e-3,
        "seed": 0,
    }
    if mesh is not None:
        cfg["_mesh"] = mesh
    return PPOJaxPolicy(
        gym.spaces.Box(0, 255, (H, W, K), np.uint8),
        gym.spaces.Discrete(A),
        cfg,
    )


def _row_cols(rng, n):
    return {
        SampleBatch.ACTIONS: rng.integers(0, A, n).astype(np.int64),
        SampleBatch.ACTION_LOGP: np.full(n, -1.1, np.float32),
        SampleBatch.ACTION_DIST_INPUTS: rng.standard_normal(
            (n, A)
        ).astype(np.float32),
        SampleBatch.ADVANTAGES: rng.standard_normal(n).astype(
            np.float32
        ),
        SampleBatch.VALUE_TARGETS: rng.standard_normal(n).astype(
            np.float32
        ),
    }


def test_policy_learns_identically_from_stream_and_stacks():
    """The frames variant must be numerically identical to shipping
    materialized stacks (same seed → same rng stream → same losses)."""
    rng = np.random.default_rng(0)
    n = 16
    frames = _stream(rng, n)
    rows = _row_cols(rng, n)

    stacked = SampleBatch(
        {**rows, SampleBatch.OBS: _stacked_from_stream(frames, n)}
    )
    stream = SampleBatch(
        {**rows, **frame_stream_columns(frames, n, K)}
    )

    p1, p2 = _ppo(), _ppo()
    s1 = p1.learn_on_batch(stacked)
    s2 = p2.learn_on_batch(stream)
    assert abs(s1["total_loss"] - s2["total_loss"]) < 1e-5, (s1, s2)
    for a, b in zip(
        jax.tree_util.tree_leaves(p1.params),
        jax.tree_util.tree_leaves(p2.params),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-6
        )
    # byte accounting: the stream ships ~K x fewer obs bytes
    assert stream[FRAMES].nbytes * (K - 1) < stacked[
        SampleBatch.OBS
    ].nbytes


def test_stream_batch_on_8_device_mesh():
    """Replicated frame pool + data-sharded idx rows on a real mesh:
    the gather happens per shard with global indices."""
    from ray_tpu.sharding import get_mesh

    mesh = get_mesh(devices=jax.devices()[:8])
    rng = np.random.default_rng(0)
    n = 16
    frames = _stream(rng, n)
    rows = _row_cols(rng, n)
    batch = SampleBatch(
        {**rows, **frame_stream_columns(frames, n, K)}
    )
    policy = _ppo(mesh)
    stats = policy.learn_on_batch(batch)
    assert np.isfinite(stats["total_loss"]), stats

    # equivalence vs the stacked path on the same mesh
    policy2 = _ppo(mesh)
    stacked = SampleBatch(
        {**rows, SampleBatch.OBS: _stacked_from_stream(frames, n)}
    )
    stats2 = policy2.learn_on_batch(stacked)
    assert abs(stats["total_loss"] - stats2["total_loss"]) < 1e-5


def test_decompose_segmented_roundtrip():
    """Multiple fragments/episode resets in one batch: each segment is
    its own sliding window; rebuild must be exact."""
    from ray_tpu.ops.framestack import decompose_segmented_obs

    rng = np.random.default_rng(2)
    segs = [5, 3, 7]
    stacked_parts, seg_mask = [], []
    for L in segs:
        frames = _stream(rng, L)
        stacked_parts.append(_stacked_from_stream(frames, L))
        seg_mask.extend([True] + [False] * (L - 1))
    stacked = np.concatenate(stacked_parts)
    out = decompose_segmented_obs(stacked, np.asarray(seg_mask))
    assert out is not None
    stream, idx = out
    # each segment contributes K + (len-1) frames
    assert len(stream) == sum(L + K - 1 for L in segs)
    rebuilt = np.asarray(
        build_stacks(jnp.asarray(stream), jnp.asarray(idx), K)
    )
    np.testing.assert_array_equal(rebuilt, stacked)
    # a wrong mask (missing boundary) must be detected, not mis-built
    bad = np.asarray(seg_mask).copy()
    bad[segs[0]] = False
    assert decompose_segmented_obs(stacked, bad) is None


def _e2e_shaped_batch(rng, frag_lens):
    """Rollout-shaped pixel batch: per-fragment sliding windows with
    UNROLL_ID bookkeeping, as concat_samples produces in e2e runs."""
    parts = []
    for uid, L in enumerate(frag_lens):
        frames = _stream(rng, L)
        parts.append(
            {
                SampleBatch.OBS: _stacked_from_stream(frames, L),
                SampleBatch.UNROLL_ID: np.full(L, uid, np.int64),
                SampleBatch.EPS_ID: np.full(L, 100 + uid, np.int64),
                SampleBatch.T: np.arange(L, dtype=np.int64),
            }
        )
    n = sum(frag_lens)
    cols = _row_cols(rng, n)
    for k in parts[0]:
        cols[k] = np.concatenate([p[k] for p in parts])
    return SampleBatch(cols)


def test_policy_auto_dedups_rollout_batches():
    """A stacked rollout batch is auto-decomposed in prepare_batch and
    learns identically to shipping the stacks."""
    rng = np.random.default_rng(3)
    batch = _e2e_shaped_batch(rng, [8, 8])

    p1, p2 = _ppo(), _ppo()
    p1.config["dedup_framestack_min_bytes"] = 0
    p2.config["dedup_framestack"] = False
    tree1, _ = p1.prepare_batch(batch)
    assert FRAMES in tree1 and SampleBatch.OBS not in tree1
    tree2, _ = p2.prepare_batch(batch)
    assert SampleBatch.OBS in tree2
    s1 = p1.learn_on_batch(batch)
    s2 = p2.learn_on_batch(batch)
    assert abs(s1["total_loss"] - s2["total_loss"]) < 1e-5, (s1, s2)
    for a, b in zip(
        jax.tree_util.tree_leaves(p1.params),
        jax.tree_util.tree_leaves(p2.params),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-6
        )


def test_impala_unroll_dedup_equivalence():
    """IMPALA's (B, T)+bootstrap layout dedups to ~(T+k) frames per
    unroll and trains identically to the stacked path."""
    from ray_tpu.algorithms.impala.impala import ImpalaJaxPolicy
    from ray_tpu.ops.framestack import FRAMES as F

    T, n_frag = 6, 3
    rng = np.random.default_rng(4)
    cfg = {
        "model": {
            "conv_filters": [[8, [4, 4], [2, 2]], [16, [5, 5], [1, 1]]],
            "post_fcnet_hiddens": [16],
        },
        "rollout_fragment_length": T,
        "train_batch_size": T * n_frag,
        "lr": 1e-3,
        "seed": 0,
    }
    n = T * n_frag
    frames = _stream(rng, n + 1)  # one extra: the final bootstrap frame
    ext = _stacked_from_stream(frames, n + 1)
    stacked = ext[:n]
    batch = SampleBatch(
        {
            SampleBatch.OBS: stacked,
            SampleBatch.NEXT_OBS: ext[1:],
            SampleBatch.ACTIONS: rng.integers(0, A, n).astype(np.int64),
            SampleBatch.REWARDS: rng.standard_normal(n).astype(
                np.float32
            ),
            SampleBatch.TERMINATEDS: np.zeros(n, bool),
            SampleBatch.TRUNCATEDS: np.zeros(n, bool),
            SampleBatch.ACTION_LOGP: np.full(n, -1.1, np.float32),
        }
    )

    def mk():
        return ImpalaJaxPolicy(
            gym.spaces.Box(0, 255, (H, W, K), np.uint8),
            gym.spaces.Discrete(A),
            dict(cfg),
        )

    p1, p2 = mk(), mk()
    p1.config["dedup_framestack_min_bytes"] = 0
    p2.config["dedup_framestack"] = False
    tree1, _ = p1.prepare_batch(batch)
    assert F in tree1
    s1 = p1.learn_on_batch(batch)
    s2 = p2.learn_on_batch(batch)
    assert abs(s1["total_loss"] - s2["total_loss"]) < 1e-5, (s1, s2)
    for a, b in zip(
        jax.tree_util.tree_leaves(p1.params),
        jax.tree_util.tree_leaves(p2.params),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-6
        )


class _TinyPixelEnv(gym.Env):
    """Deterministic 12x12 single-channel pixel env (frame = step
    counter pattern) for sampler-level compression tests."""

    def __init__(self, episode_len=10):
        self.observation_space = gym.spaces.Box(
            0, 255, (H, W, 1), np.uint8
        )
        self.action_space = gym.spaces.Discrete(A)
        self._ep_len = episode_len
        self._t = 0
        self._seed = 0

    def _frame(self):
        f = np.full((H, W, 1), (self._seed * 37 + self._t) % 251, np.uint8)
        f[self._t % H, :, 0] = 255
        return f

    def reset(self, *, seed=None, options=None):
        self._t = 0
        self._seed += 1
        return self._frame(), {}

    def step(self, action):
        self._t += 1
        return (
            self._frame(),
            float(action == 1),
            False,
            self._t >= self._ep_len,
            {},
        )


def test_sampler_ships_compressed_fragments():
    """The rollout hot loop emits frame-pool fragments for on-policy
    pixel policies (compress_for_shipping), concat keeps them pooled,
    and the learner trains straight from the pool."""
    from ray_tpu.data.sample_batch import concat_samples
    from ray_tpu.env.vector_env import VectorEnv
    from ray_tpu.env.wrappers import FrameStack
    from ray_tpu.evaluation.sampler import SyncSampler

    policy = _ppo()
    policy.config["dedup_framestack_min_bytes"] = 0
    env = VectorEnv.vectorize_gym_envs(
        lambda i: FrameStack(_TinyPixelEnv(), K), num_envs=2
    )
    sampler = SyncSampler(
        vector_env=env,
        policy=policy,
        rollout_fragment_length=8,
        batch_mode="truncate_episodes",
    )
    b1, b2 = sampler.sample(), sampler.sample()
    assert FRAMES in b1 and SampleBatch.OBS not in b1, list(b1)
    assert SampleBatch.NEXT_OBS not in b1
    big = concat_samples([b1, b2])
    assert FRAMES in big and big.count == b1.count + b2.count
    # pool indices stay valid after the merge (stack gather in range)
    assert int(big[FRAME_IDX].max()) + K <= len(big[FRAMES])
    stats = policy.learn_on_batch(big)
    assert np.isfinite(stats["total_loss"]), stats


def test_sampler_compression_impala_unrolls():
    """Fixed-unroll (IMPALA) fragments compress too, including the
    bootstrap frame at idx[-1]+1, and V-trace trains from the pool."""
    from ray_tpu.algorithms.impala.impala import ImpalaJaxPolicy
    from ray_tpu.data.sample_batch import concat_samples
    from ray_tpu.env.vector_env import VectorEnv
    from ray_tpu.env.wrappers import FrameStack
    from ray_tpu.evaluation.sampler import SyncSampler

    T = 6
    policy = ImpalaJaxPolicy(
        gym.spaces.Box(0, 255, (H, W, K), np.uint8),
        gym.spaces.Discrete(A),
        {
            "model": {
                "conv_filters": [
                    [8, [4, 4], [2, 2]], [16, [5, 5], [1, 1]],
                ],
                "post_fcnet_hiddens": [16],
            },
            "rollout_fragment_length": T,
            "train_batch_size": T * 4,
            "lr": 1e-3,
            "seed": 0,
            "_fixed_unrolls": True,
        },
    )
    env = VectorEnv.vectorize_gym_envs(
        lambda i: FrameStack(_TinyPixelEnv(episode_len=9), K),
        num_envs=2,
    )
    sampler = SyncSampler(
        vector_env=env,
        policy=policy,
        rollout_fragment_length=T,
        batch_mode="truncate_episodes",
        flush_on_episode_end=False,  # fixed unrolls span episodes
    )
    batches = [sampler.sample() for _ in range(3)]
    assert all(FRAMES in b for b in batches), [list(b) for b in batches]
    big = concat_samples(batches)
    stats = policy.learn_on_batch(big)
    assert np.isfinite(stats["total_loss"]), stats


def test_prepare_batch_trims_rows_but_not_frames():
    policy = _ppo()
    rng = np.random.default_rng(0)
    n = 19  # trims to 16 (multiple of shards)
    frames = _stream(rng, n)
    batch = SampleBatch(
        {**_row_cols(rng, n), **frame_stream_columns(frames, n, K)}
    )
    tree, bsize = policy.prepare_batch(batch)
    assert bsize == len(tree[FRAME_IDX])
    assert len(tree[FRAMES]) == n + K - 1  # pool untouched
    stats = policy.learn_on_batch(batch)
    assert np.isfinite(stats["total_loss"])
