"""The compile layer's two dispatch paths, the replay plane's device
ops, and the program registry.

- **Dispatch parity**: ``ShardedFunction.__call__`` has a fast path
  (neither tracing nor the device ledger on: cached sharding trees,
  pre-validated donation, single clock pair) and an observed one; the
  choice is an observability/host-overhead matter only — fixed-seed
  learn results are BITWISE identical on both, steady-state calls
  never retrace, and a genuinely new signature still gets the full
  bookkeeping and retraces correctly.
- **Device ops against the host reference**: the replay row
  gather/scatter, the framestack build, the GAE fragment scan and the
  sum-tree prefix descent are XLA bodies (Mosaic refused a Pallas
  kernel for each on the v5e); each is held to the host code it
  stands in for — bitwise for data movement and the descent, the
  documented float32 tolerance for the GAE scan — and the device
  buffers to the host buffers' rows, indices and weights.
- **Program registry completeness**: ``sharding.registry`` enumerates
  every executable an AlgorithmConfig lowers — a fused-lane PPO run
  and a prioritized device-replay DQN run leave ZERO observed compile
  labels unmatched — and ``BatchedPolicyServer.warmup`` IS a registry
  sweep.
"""

import gymnasium as gym
import numpy as np

import jax
import jax.numpy as jnp

from ray_tpu import sharding as sharding_lib
from ray_tpu.data.sample_batch import SampleBatch as SB
from ray_tpu.ops import framestack as framestack_lib
from ray_tpu.ops import gae as gae_lib
from ray_tpu.ops import segment_tree as st_lib
from ray_tpu.sharding.compile import compile_stats, sharded_jit


def _one_shard_mesh():
    return sharding_lib.get_mesh(devices=jax.devices()[:1])


def _labels():
    return {s["label"] for s in compile_stats()["per_function"]}


# -- the two dispatch paths ---------------------------------------------


BS = 16


def _policy(seed=3, **over):
    from ray_tpu.algorithms.ppo.ppo import PPOJaxPolicy

    cfg = {
        "train_batch_size": BS,
        "sgd_minibatch_size": BS,
        "num_sgd_iter": 2,
        "lr": 1e-3,
        "seed": seed,
        "model": {"fcnet_hiddens": [32, 32]},
        # bitwise parity wants the 1-shard mesh (per-shard matmul
        # shapes differ on the 8-way virtual mesh)
        "_mesh": _one_shard_mesh(),
    }
    cfg.update(over)
    return PPOJaxPolicy(
        gym.spaces.Box(-1, 1, (8,), np.float32),
        gym.spaces.Discrete(4),
        cfg,
    )


def _batch(n=BS):
    rng = np.random.default_rng(11)
    return {
        SB.OBS: rng.standard_normal((n, 8)).astype(np.float32),
        SB.ACTIONS: rng.integers(0, 4, n).astype(np.int64),
        SB.ACTION_LOGP: np.full(n, -1.3, np.float32),
        SB.ACTION_DIST_INPUTS: rng.standard_normal((n, 4)).astype(
            np.float32
        ),
        SB.ADVANTAGES: rng.standard_normal(n).astype(np.float32),
        SB.VALUE_TARGETS: rng.standard_normal(n).astype(np.float32),
    }


def _leaves(policy):
    return [
        np.asarray(x)
        for x in jax.tree_util.tree_leaves(
            jax.device_get(policy.params)
        )
    ]


def test_diet_learn_bitwise_parity():
    """Fixed-seed learn through the fast dispatch path is BITWISE
    identical to the observed path (tracing + device ledger on, every
    call stamped, spanned and handed to the ledger) — the fast path
    drops host work, never bytes."""
    from ray_tpu.telemetry import device as device_ledger
    from ray_tpu.util import tracing

    batch = _batch()

    assert not tracing.is_enabled() and not device_ledger.enabled()
    p_fast = _policy()
    for _ in range(3):
        p_fast.learn_on_batch(batch)

    tracing.enable()
    device_ledger.enable(analyze=False)
    try:
        p_obs = _policy()
        for _ in range(3):
            p_obs.learn_on_batch(batch)
        observed = {
            sp["name"] for sp in tracing.get_spans()
        }
    finally:
        device_ledger.disable()
        device_ledger.clear()
        tracing.disable()
        tracing.clear()
    assert any(n.startswith("jit:learn[") for n in observed), observed

    a, b = _leaves(p_fast), _leaves(p_obs)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(
            x.view(np.uint8), y.view(np.uint8)
        )


def test_diet_steady_state_never_retraces():
    """Repeated same-signature calls ride the fast path: one trace,
    N calls, zero recompiles."""
    mesh = _one_shard_mesh()
    spec = sharding_lib.replicated(mesh)
    fn = sharded_jit(
        lambda a: a * 2.0 + 1.0,
        in_specs=[spec],
        out_specs=spec,
        label="diet_steady",
    )
    x = jnp.arange(8, dtype=jnp.float32)
    want = np.asarray(x) * 2.0 + 1.0
    for _ in range(10):
        np.testing.assert_allclose(np.asarray(fn(x)), want)
    st = fn.stats()
    assert st["traces"] == 1
    assert st["recompiles"] == 0
    assert st["calls"] == 10


def test_diet_new_signature_falls_back_and_retraces():
    """The fast path is signature-guarded: a genuinely new abstract
    signature drops to the full path, retraces, and still computes
    correctly (the post-hoc retrace fallback)."""
    mesh = _one_shard_mesh()
    spec = sharding_lib.replicated(mesh)
    fn = sharded_jit(
        lambda a: a + 1.0,
        in_specs=[spec],
        out_specs=spec,
        label="diet_resig",
    )
    x8 = jnp.zeros(8, jnp.float32)
    x16 = jnp.ones(16, jnp.float32)
    fn(x8)
    fn(x8)
    assert fn.stats()["traces"] == 1
    out = fn(x16)  # new shape on the fast path
    np.testing.assert_array_equal(np.asarray(out), np.full(16, 2.0))
    assert fn.stats()["traces"] == 2
    # and the old signature still rides its cached executable
    fn(x8)
    assert fn.stats()["traces"] == 2


def test_diet_superstep_k_sweep_zero_recompiles():
    """On the fast path (cached sharding trees), every k = 1..K_MAX
    rides the ONE compiled superstep executable — zero recompiles
    across the whole sweep (the active-mask contract survives the
    fast path)."""
    kmax, n = 8, BS
    p = _policy(num_sgd_iter=1)
    rng = np.random.default_rng(13)
    one = _batch(n)
    stacked = {
        c: np.stack(
            [
                rng.permutation(one[c]) if one[c].ndim else one[c]
                for _ in range(kmax)
            ]
        )
        for c in one
    }
    for k in range(1, kmax + 1):
        p.learn_superstep(k, n, stacked=stacked, k_max=kmax)
    (fn,) = p._superstep_fns.values()
    assert fn.traces == 1
    assert fn.recompiles == 0
    assert fn.calls == kmax


def test_sharding_tree_cache_clear_is_sound():
    """``clear_sharding_caches`` invalidates the resolved-tree memo
    without changing results."""
    mesh = _one_shard_mesh()
    tree = {"a": np.zeros((4, 3), np.float32), "b": np.zeros(4)}
    t1 = sharding_lib.sharding_tree(tree, mesh)
    sharding_lib.clear_sharding_caches()
    t2 = sharding_lib.sharding_tree(tree, mesh)
    assert jax.tree_util.tree_structure(
        t1
    ) == jax.tree_util.tree_structure(t2)
    for s1, s2 in zip(
        jax.tree_util.tree_leaves(t1), jax.tree_util.tree_leaves(t2)
    ):
        assert s1 == s2


# -- device ops against the host reference ------------------------------


def test_gather_scatter_rows_pallas_bitwise():
    """Row gather/scatter is pure data movement: bitwise against numpy
    fancy indexing, f32 and packed-uint32 rings alike; the scatter
    leaves unwritten ring rows untouched and a colliding write keeps
    one of its writers (XLA leaves the order open; numpy keeps the
    last)."""
    rng = np.random.default_rng(0)
    for dtype in (np.float32, np.uint32):
        if dtype is np.uint32:
            ring = rng.integers(
                0, 2**32, (32, 12), dtype=np.uint32
            )
            vals = rng.integers(0, 2**32, (5, 12), dtype=np.uint32)
        else:
            ring = rng.standard_normal((32, 12)).astype(dtype)
            vals = rng.standard_normal((5, 12)).astype(dtype)
        idx = rng.integers(0, 32, 7)

        got = framestack_lib.gather_rows(
            jnp.asarray(ring), jnp.asarray(idx)
        )
        np.testing.assert_array_equal(np.asarray(got), ring[idx])

        pos = np.array([3, 9, 9, 0, 31])  # includes a collision
        want_ring = ring.copy()
        want_ring[pos] = vals
        got_ring = np.asarray(
            framestack_lib.scatter_rows(
                jnp.asarray(ring), jnp.asarray(pos), jnp.asarray(vals)
            )
        )
        free = np.arange(32) != 9
        np.testing.assert_array_equal(got_ring[free], want_ring[free])
        assert any(
            np.array_equal(got_ring[9], vals[w]) for w in (1, 2)
        )


def test_build_stacks_pallas_bitwise():
    """The framestack build (uint32-packed frame pool, one gather) is
    bitwise the host's ``materialize_stacks_np``."""
    rng = np.random.default_rng(1)
    k, n = 4, 10
    frames = rng.integers(0, 255, (n + k - 1, 12, 12, 1)).astype(
        np.uint8
    )
    idx = rng.permutation(n).astype(np.int32)
    got = np.asarray(
        framestack_lib.build_stacks(
            jnp.asarray(frames), jnp.asarray(idx), k
        )
    )
    np.testing.assert_array_equal(
        got, framestack_lib.materialize_stacks_np(frames, idx, k)
    )


def test_gae_fragment_pallas_tolerance():
    """The associative GAE scan vs the host's sequential
    ``compute_gae_np`` on each row: same recurrence, different
    evaluation order — the documented float32 contract is
    max |Δ| < 1e-4 on both outputs."""
    rng = np.random.default_rng(2)
    b, t = 12, 40
    rewards = rng.standard_normal((b, t)).astype(np.float32)
    values = rng.standard_normal((b, t)).astype(np.float32)
    boot = rng.standard_normal(b).astype(np.float32)
    # no truncation, so every boundary bootstraps 0 and V(next obs) is
    # the next row's value: the single-mask semantics of the reference
    done = (rng.random((b, t)) < 0.08).astype(np.float32)
    nexts = np.concatenate([values[:, 1:], boot[:, None]], axis=1)
    adv, vt = gae_lib.compute_gae_fragment(
        *(jnp.asarray(a) for a in (rewards, values, nexts, done, done)),
        gamma=0.99,
        lambda_=0.95,
    )
    for i in range(b):
        adv_np, vt_np = gae_lib.compute_gae_np(
            rewards[i], values[i], done[i], boot[i], 0.99, 0.95
        )
        for got, want in ((adv[i], adv_np), (vt[i], vt_np)):
            d = np.abs(np.asarray(got) - want)
            assert np.isfinite(d).all()
            assert d.max() < 1e-4, d.max()


def test_sumtree_descent_pallas_bitwise():
    """The in-program f64 prefix-sum descent replays
    ``SumSegmentTree.find_prefixsum_idx``'s exact op sequence — drawn
    leaf indices are identical."""
    cap = 64
    rng = np.random.default_rng(3)
    host = st_lib.SumSegmentTree(cap)
    host.set_items(np.arange(cap), rng.random(cap) + 1e-3)
    prefix = rng.random(17) * host.sum()
    with sharding_lib.f64_scope():
        got = np.asarray(
            st_lib.find_prefixsum_body(
                jnp.asarray(host.value), jnp.asarray(prefix), cap
            )
        )
    np.testing.assert_array_equal(got, host.find_prefixsum_idx(prefix))


def test_device_replay_pallas_end_to_end_bitwise():
    """DevicePrioritizedReplayBuffer (rows on the device, uint8 rows
    packed into uint32 lanes, draws through the device tree) inserts
    and samples the host prioritized buffer's rows — same seed, same
    draw stream, same rows and weights."""
    from ray_tpu.execution.replay_buffer import (
        DevicePrioritizedReplayBuffer,
        PrioritizedReplayBuffer,
    )

    rng = np.random.default_rng(4)
    frags = [
        {
            "obs": rng.integers(0, 255, (8, 6, 6, 4)).astype(np.uint8),
            "rew": rng.standard_normal(8).astype(np.float32),
        }
        for _ in range(6)  # 48 rows into 32: the ring wraps
    ]
    pris = [rng.random(8) + 0.1 for _ in frags]

    dev = DevicePrioritizedReplayBuffer(
        capacity=32, seed=9, mesh=_one_shard_mesh(), device_tree=True
    )
    host = PrioritizedReplayBuffer(capacity=32, seed=9)
    for f, p in zip(frags, pris):
        dev.add_tree(dict(f), priorities=p)
        host.add_with_priorities(SB(dict(f)), p)
    got = dev.sample(16, beta=0.4)
    want = host.sample(16, beta=0.4)
    np.testing.assert_array_equal(
        np.asarray(got.indices), want["batch_indexes"]
    )
    for k in ("obs", "rew", "weights"):
        np.testing.assert_array_equal(
            np.asarray(got.tree[k]), want[k], err_msg=k
        )


def test_device_sumtree_pallas_end_to_end_bitwise():
    """DeviceSumTree draws match the host trees' stratified draw
    bit-for-bit: indices AND f32 IS weights."""
    cap, size, beta = 32, 29, 0.4
    rng = np.random.default_rng(5)
    powered = rng.random(size) * 2 + 1e-3
    rand = np.random.default_rng(6).random(16)

    dt = st_lib.DeviceSumTree(cap, mesh=_one_shard_mesh())
    dt.set_powered(np.arange(size), powered)
    idx, w = dt.draw(rand, size, beta)

    sum_t, min_t = st_lib.SumSegmentTree(cap), st_lib.MinSegmentTree(cap)
    sum_t.set_items(np.arange(size), powered)
    min_t.set_items(np.arange(size), powered)
    total = sum_t.sum(0, size)
    want_idx = np.clip(
        sum_t.find_prefixsum_idx((rand + np.arange(16)) / 16 * total),
        0,
        size - 1,
    )
    max_weight = (min_t.min(0, size) / total * size) ** (-beta)
    want_w = (
        (sum_t[want_idx] / total * size) ** (-beta) / max_weight
    ).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(idx), want_idx)
    np.testing.assert_array_equal(
        np.asarray(w).view(np.uint8), want_w.view(np.uint8)
    )


# -- program registry completeness --------------------------------------


def test_registry_ppo_fused_coverage():
    """A fused-lane PPO run compiles ONLY programs the registry
    predicted from the config: observed-labels diff before/after the
    run, coverage().unmatched == []."""
    from ray_tpu.algorithms.ppo.ppo import PPOConfig

    import ray_tpu.env.jax_control  # noqa: F401 (registers the env)

    cfg = (
        PPOConfig()
        .environment(
            "CartPoleJax-v0",
            env_config={"max_steps": 10},
            env_backend="jax",
        )
        .rollouts(
            num_rollout_workers=0,
            num_envs_per_worker=8,
            rollout_fragment_length=8,
        )
        .training(
            train_batch_size=64,
            sgd_minibatch_size=32,
            num_sgd_iter=2,
            model={"fcnet_hiddens": [32, 32]},
        )
        .debugging(seed=0)
    )
    pre = _labels()
    algo = cfg.build()
    try:
        algo.train()
        reg = algo.program_registry
        assert reg.specs(), "registry is empty"
        observed = sorted(_labels() - pre)
        cov = reg.coverage(observed=observed)
        assert cov["unmatched"] == [], cov["unmatched"]
        assert cov["matched"], "run compiled nothing?"
    finally:
        algo.stop()


def test_registry_dqn_prioritized_coverage():
    """Prioritized device-replay DQN: the replay/tree program families
    (insert, sample, draw, tree update/draw) are all enumerated —
    zero unmatched labels after a run that exercises them."""
    from ray_tpu.algorithms.dqn import DQNConfig

    cfg = (
        DQNConfig()
        .environment("CartPole-v1")
        .rollouts(num_rollout_workers=0, rollout_fragment_length=16)
        .training(
            train_batch_size=32,
            num_steps_sampled_before_learning_starts=32,
            replay_device_resident=True,
            model={"fcnet_hiddens": [32, 32]},
            replay_buffer_config={
                "capacity": 1024,
                "prioritized_replay": True,
            },
        )
        .debugging(seed=0)
    )
    pre = _labels()
    algo = cfg.build()
    try:
        for _ in range(2):
            algo.train()
        observed = sorted(_labels() - pre)
        cov = algo.program_registry.coverage(observed=observed)
        assert cov["unmatched"] == [], cov["unmatched"]
    finally:
        algo.stop()


def test_serve_warmup_walks_registry():
    """BatchedPolicyServer.warmup IS a registry sweep: one warmable
    spec per bucket, sweep warms them all, and every serve program
    the warmup compiled matches a registry spec."""
    from ray_tpu.serve.policy_server import BatchedPolicyServer

    policy = _policy(seed=7)
    pre = _labels()
    srv = BatchedPolicyServer(policy, max_batch_size=4, start=False)
    assert srv.fused
    specs = srv.program_registry.specs(kind="serve")
    assert len(specs) == len(srv.buckets)
    warmed = srv.warmup()
    assert warmed == len(srv.buckets)
    for lbl in sorted(_labels() - pre):
        if lbl.startswith("serve["):
            assert srv.program_registry.match(lbl) is not None, lbl
