"""On-device training superstep: one dispatch per K updates.

The uniform learner contract of docs/data_plane.md
(``AlgorithmConfig.training(superstep=...)``,
``JaxPolicy.learn_superstep``, ``sharding/superstep.py``):

- fixed-seed BIT-parity of ``superstep=k`` vs k individual deferred
  learn calls (PPO stacked feed on the 8-shard mesh; SAC device-ring
  and DQN-prioritized host+device feeds on a single-shard mesh — on
  multi-shard meshes cross-program collective lowering rounds the last
  ulp differently, an XLA property, so there the asserted invariant is
  the program-level one: scan(K) ≡ scan(1)^K through ONE executable,
  plus allclose vs the classic path);
- deferred-stats stacking/drain semantics (per-update stats bitwise
  equal to the per-call deferred fetches);
- prioritized-replay refresh: ONE stacked (k, B) D2H, applied to the
  host sum tree in exact update order;
- one compiled program serves every k ≤ K (no per-K recompile,
  ``compile_stats``-asserted);
- the in-scan replay gather adds no collective to the program;
- the nan guard runs INSIDE the scan body (skip mask in the stats
  tree, masked updates are exact no-ops);
- checkpoint restore mid-superstep-cadence resumes fused training.
"""

import numpy as np
import pytest

import jax

from ray_tpu import sharding as sharding_lib
from ray_tpu.data.sample_batch import SampleBatch as SB


BS = 16


def _eq_trees(a, b):
    la = jax.tree_util.tree_leaves(jax.device_get(a))
    lb = jax.tree_util.tree_leaves(jax.device_get(b))
    assert len(la) == len(lb)
    return all(np.array_equal(x, y) for x, y in zip(la, lb))


def _mesh(n):
    return sharding_lib.get_mesh(devices=jax.devices()[:n])


def _ppo_policy(mesh=None, **over):
    import gymnasium as gym

    from ray_tpu.algorithms.ppo.ppo import PPOJaxPolicy

    cfg = {
        "train_batch_size": 4 * BS,
        "sgd_minibatch_size": 2 * BS,
        "num_sgd_iter": 2,
        "lr": 1e-3,
        "seed": 0,
    }
    if mesh is not None:
        cfg["_mesh"] = mesh
    cfg.update(over)
    return PPOJaxPolicy(
        gym.spaces.Box(-1, 1, (8,), np.float32),
        gym.spaces.Discrete(4),
        cfg,
    )


def _ppo_batch(rng, n=4 * BS):
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


def _sac_policy(mesh=None, seed=0):
    import gymnasium as gym

    from ray_tpu.algorithms.sac.sac import SACJaxPolicy

    cfg = {"seed": seed, "gamma": 0.99, "tau": 0.005}
    if mesh is not None:
        cfg["_mesh"] = mesh
    return SACJaxPolicy(
        gym.spaces.Box(-1, 1, (6,), np.float32),
        gym.spaces.Box(-1, 1, (2,), np.float32),
        cfg,
    )


def _sac_rows(rng, n):
    return {
        SB.OBS: rng.standard_normal((n, 6)).astype(np.float32),
        SB.NEXT_OBS: rng.standard_normal((n, 6)).astype(np.float32),
        SB.ACTIONS: rng.uniform(-1, 1, (n, 2)).astype(np.float32),
        SB.REWARDS: rng.standard_normal(n).astype(np.float32),
        SB.TERMINATEDS: np.zeros(n, np.float32),
    }


def _dqn_policy(mesh=None, **over):
    import gymnasium as gym

    from ray_tpu.algorithms.dqn.dqn import DQNJaxPolicy

    cfg = {
        "seed": 0,
        "lr": 1e-3,
        "train_batch_size": BS,
        "dueling": False,
        "double_q": True,
    }
    if mesh is not None:
        cfg["_mesh"] = mesh
    cfg.update(over)
    return DQNJaxPolicy(
        gym.spaces.Box(-1, 1, (6,), np.float32),
        gym.spaces.Discrete(4),
        cfg,
    )


def _dqn_rows(rng, n):
    return {
        SB.OBS: rng.standard_normal((n, 6)).astype(np.float32),
        SB.NEXT_OBS: rng.standard_normal((n, 6)).astype(np.float32),
        SB.ACTIONS: rng.integers(0, 4, n).astype(np.int64),
        SB.REWARDS: rng.standard_normal(n).astype(np.float32),
        SB.TERMINATEDS: np.zeros(n, np.float32),
    }


# -- bit parity: superstep == k individual calls -----------------------


def test_ppo_superstep_bit_parity_and_stats_stacking():
    """superstep=k on the 8-shard mesh: params AND opt-state bitwise
    equal to k sequential deferred learn calls on the same (host
    stacked) batches, and the drained (k,)-stacked stats bitwise equal
    to the per-call deferred fetches, in update order. Afterwards the
    SAME compiled program serves k = 1, 2, 4 with zero recompiles
    (compile_stats-asserted: one executable for every K in a run)."""
    rng = np.random.default_rng(0)
    K, KMAX, n = 3, 4, 4 * BS
    batches = [_ppo_batch(rng, n) for _ in range(K)]

    p_seq = _ppo_policy()
    seq_stats = []
    for b in batches:
        dev = jax.device_put(b, p_seq.batch_shardings(b))
        seq_stats.append(
            jax.device_get(
                p_seq.learn_on_device_batch(dev, n, defer_stats=True)
            )
        )

    p_sup = _ppo_policy()
    stacked = {
        c: np.stack([b[c] for b in batches] + [batches[0][c]])
        for c in batches[0]
    }
    infos, pri, skipped = p_sup.learn_superstep(
        K, n, stacked=stacked, k_max=KMAX
    )
    assert pri is None and skipped == [False] * K
    assert _eq_trees(p_seq.params, p_sup.params)
    assert _eq_trees(p_seq.opt_state, p_sup.opt_state)
    assert len(infos) == K
    for i in range(K):
        for name, v in seq_stats[i].items():
            assert float(v) == infos[i][name], (i, name)
    # num_grad_updates advances like k calls would
    assert p_sup.num_grad_updates == p_seq.num_grad_updates

    # zero-recompile across chain lengths: every k ≤ K_MAX rides the
    # ONE compiled executable
    for k in (1, 2, 4):
        p_sup.learn_superstep(k, n, stacked=stacked, k_max=KMAX)
    (fn,) = p_sup._superstep_fns.values()
    assert fn.traces == 1 and fn.recompiles == 0 and fn.calls == 4
    per_fn = {
        s["label"]: s
        for s in sharding_lib.compile_stats()["per_function"]
    }
    label = f"superstep[PPOJaxPolicy:{n}x{KMAX}]"
    assert per_fn[label]["recompiles"] == 0


@pytest.mark.slow  # ~10 s; moved out of tier-1 by the PR-1 budget
# rule — tier-1 keeps the PPO superstep bit-parity + zero-recompile
# pin above, the DQN prioritized-superstep parity below, and the SAC
# device-vs-host bitwise pin in test_device_replay.py
def test_sac_superstep_device_rings_parity():
    """Device-resident replay rings consumed IN PLACE by the scan:
    bit-identical to k sequential sample+learn calls on a single-shard
    mesh (same host generator call order, same rng splits); on the
    8-shard mesh the chain is bit-identical THROUGH the superstep
    program (scan(K) == scan(1)^K, one executable); vs the classic
    path it agrees to collective-rounding (cross-program lowering
    rounds the last ulp differently — an XLA property, not a data-path
    one; docs/data_plane.md)."""
    from ray_tpu.execution.replay_buffer import DeviceReplayBuffer

    rng = np.random.default_rng(1)
    rows = _sac_rows(rng, 8 * BS)
    K = 3

    # single-shard mesh: exact parity vs the classic per-update path
    m1 = _mesh(1)
    p_seq, p_sup = _sac_policy(m1), _sac_policy(m1)
    b_seq = DeviceReplayBuffer(capacity=8 * BS, seed=7, mesh=m1)
    b_sup = DeviceReplayBuffer(capacity=8 * BS, seed=7, mesh=m1)
    b_seq.add_tree(dict(rows))
    b_sup.add_tree(dict(rows))
    lazy = []
    for _ in range(K):
        db = b_seq.sample(BS)
        lazy.append(
            p_seq.learn_on_device_batch(
                dict(db.tree), BS, defer_stats=True
            )
        )
    jax.device_get(lazy)
    idx = b_sup.draw_index_sets(K, BS)
    infos, _, _ = p_sup.learn_superstep(
        K, BS, rings=b_sup.superstep_feed(idx), k_max=K
    )
    assert _eq_trees(p_seq.params, p_sup.params)
    assert _eq_trees(p_seq.opt_state, p_sup.opt_state)
    assert _eq_trees(p_seq.aux_state, p_sup.aux_state)
    # the pre-drawn index matrix consumed the generator exactly like
    # k sequential draws
    assert (
        b_seq._rng.bit_generator.state == b_sup._rng.bit_generator.state
    )

    # 8-shard mesh: program-level exactness. One policy, one compiled
    # program: snapshot the initial state, run scan(K), restore, run
    # scan(1)^K through the SAME executable.
    p_a = _sac_policy()
    buf = DeviceReplayBuffer(capacity=8 * BS, seed=7)
    buf.add_tree(dict(rows))
    idx = buf.draw_index_sets(K, BS)
    snap = (
        jax.device_get(p_a.params),
        jax.device_get(p_a.opt_state),
        jax.device_get(p_a.aux_state),
        p_a._rng,
    )
    p_a.learn_superstep(
        K, BS, rings=buf.superstep_feed(idx), k_max=K
    )
    fused = (
        jax.device_get(p_a.params), jax.device_get(p_a.opt_state),
        jax.device_get(p_a.aux_state),
    )
    from ray_tpu.policy.jax_policy import _tree_to_device

    p_a.params = _tree_to_device(snap[0], p_a._param_sharding)
    p_a.opt_state = _tree_to_device(snap[1], p_a._param_sharding)
    p_a.aux_state = _tree_to_device(snap[2], p_a._param_sharding)
    p_a._rng = snap[3]
    for i in range(K):
        one = np.repeat(idx[i : i + 1], K, axis=0)
        p_a.learn_superstep(
            1, BS, rings=buf.superstep_feed(one), k_max=K
        )
    (fn,) = p_a._superstep_fns.values()
    assert fn.traces == 1  # literally the same executable
    assert _eq_trees(fused[0], p_a.params)
    assert _eq_trees(fused[1], p_a.opt_state)
    assert _eq_trees(fused[2], p_a.aux_state)


@pytest.mark.slow  # ~5 s; fails at seed under jax 0.9.0, passes since
# PR 21 — moved out of tier-1 by that PR's budget rule (the newly
# passing tests compile and run where they used to fail at trace
# time; only newly passing ones may leave); tier-1 keeps the PPO
# superstep bit-parity pin and the device-tree update-order tests
def test_dqn_prioritized_superstep_parity():
    """DQN + prioritized replay, host AND device buffers, single-shard
    mesh: superstep_train_replay is bit-identical — params, opt-state,
    sum-tree leaves, max-priority, generator state — to the per-update
    reference (pre-drawn index sets, learn → td → refresh per update,
    priorities applied in update order)."""
    from ray_tpu.execution.replay_buffer import (
        DevicePrioritizedReplayBuffer,
        PrioritizedReplayBuffer,
    )
    from ray_tpu.execution.train_ops import superstep_train_replay

    rng = np.random.default_rng(2)
    rows = _dqn_rows(rng, 8 * BS)
    K, beta = 3, 0.4
    m1 = _mesh(1)

    def fill(buf):
        if isinstance(buf, DevicePrioritizedReplayBuffer):
            buf.add_tree(dict(rows))
        else:
            buf.add(SB(dict(rows)))
        buf.update_priorities(
            np.arange(16), np.linspace(1.0, 5.0, 16)
        )
        return buf

    from ray_tpu.policy.jax_policy import _tree_to_device

    # one policy pair serves both buffer modes (compiled programs
    # reused; state + host rng rewound between modes)
    p_ref, p_sup = _dqn_policy(m1), _dqn_policy(m1)
    snaps = [
        (
            jax.device_get(p.params),
            jax.device_get(p.opt_state),
            jax.device_get(p.aux_state),
            p._rng,
        )
        for p in (p_ref, p_sup)
    ]

    for device_buf in (False, True):
        for p, snap in zip((p_ref, p_sup), snaps):
            p.params = _tree_to_device(snap[0], p._param_sharding)
            p.opt_state = _tree_to_device(snap[1], p._param_sharding)
            p.aux_state = _tree_to_device(snap[2], p._param_sharding)
            p._rng = snap[3]
        if device_buf:
            b_ref = fill(
                DevicePrioritizedReplayBuffer(
                    capacity=8 * BS, alpha=0.6, seed=9, mesh=m1
                )
            )
            b_sup = fill(
                DevicePrioritizedReplayBuffer(
                    capacity=8 * BS, alpha=0.6, seed=9, mesh=m1
                )
            )
        else:
            b_ref = fill(
                PrioritizedReplayBuffer(
                    capacity=8 * BS, alpha=0.6, seed=9
                )
            )
            b_sup = fill(
                PrioritizedReplayBuffer(
                    capacity=8 * BS, alpha=0.6, seed=9
                )
            )

        # reference: pre-drawn sets (the superstep's documented
        # within-chain priority staleness), then per-update
        # learn → td → in-order refresh
        idx, w = b_ref.draw_prioritized_sets(K, BS, beta)
        for i in range(K):
            if device_buf:
                db = b_ref.gather(idx[i])
                tree = dict(db.tree)
                tree["weights"] = jax.device_put(
                    w[i], sharding_lib.batch_sharded(m1)
                )
                td_src = b_ref.gather(idx[i])
            else:
                b = b_ref._make_batch(idx[i])
                b["weights"] = w[i]
                b["batch_indexes"] = idx[i].astype(np.int64)
                host, n = p_ref.prepare_batch(b)
                assert n == BS
                tree = jax.device_put(
                    host, p_ref.batch_shardings(host)
                )
                td_src = b_ref._make_batch(idx[i])
            jax.device_get(
                p_ref.learn_on_device_batch(
                    tree, BS, defer_stats=True
                )
            )
            td = p_ref.compute_td_error(td_src)
            b_ref.update_priorities(idx[i], td + 1e-6)

        info = superstep_train_replay(
            None, p_sup, b_sup, K, K, BS, prioritized=True, beta=beta
        )
        assert info and np.isfinite(info["mean_td_error"])
        assert _eq_trees(p_ref.params, p_sup.params), device_buf
        assert _eq_trees(p_ref.opt_state, p_sup.opt_state), device_buf
        i_all = np.arange(8 * BS)
        assert np.array_equal(
            np.asarray(b_ref._sum_tree[i_all]),
            np.asarray(b_sup._sum_tree[i_all]),
        ), device_buf
        assert b_ref._max_priority == b_sup._max_priority
        assert (
            b_ref._rng.bit_generator.state
            == b_sup._rng.bit_generator.state
        ), device_buf


def test_priority_refresh_update_order_exactness():
    """Overlapping index sets: the stacked refresh applied in update
    order produces exactly the per-update tree (last write wins per
    leaf); applying the same matrix in reverse does not."""
    from ray_tpu.execution.replay_buffer import PrioritizedReplayBuffer

    rng = np.random.default_rng(3)
    rows = _dqn_rows(rng, 64)

    def fresh():
        b = PrioritizedReplayBuffer(capacity=64, alpha=0.6, seed=0)
        b.add(SB(dict(rows)))
        return b

    idx = np.array([[1, 2, 3, 4], [3, 4, 5, 6], [1, 6, 7, 8]])
    pri = rng.uniform(0.1, 2.0, idx.shape)

    interleaved, ordered, reverse = fresh(), fresh(), fresh()
    for i in range(3):  # the per-update cadence
        interleaved.update_priorities(idx[i], pri[i])
    for i in range(3):  # the superstep's end-of-chain application
        ordered.update_priorities(idx[i], pri[i])
    for i in reversed(range(3)):
        reverse.update_priorities(idx[i], pri[i])
    leaves = np.arange(64)
    assert np.array_equal(
        np.asarray(interleaved._sum_tree[leaves]),
        np.asarray(ordered._sum_tree[leaves]),
    )
    assert not np.array_equal(
        np.asarray(interleaved._sum_tree[leaves]),
        np.asarray(reverse._sum_tree[leaves]),
    )


# -- layout-matched in-program gather ----------------------------------


def test_superstep_ring_gather_adds_no_collective():
    """Layout-matched in-program replay gather (8-shard mesh): the
    rings-fed superstep lowers with exactly the collectives of the
    stacked-fed program — the gather's explicit row-sharded
    out-shardings mean no resharding collective fires at the
    scan-body boundary, and no gather/all-to-all appears at all.
    (Lower-only: the programs are traced and inspected, not
    executed.)"""
    import re

    from ray_tpu.execution.replay_buffer import DeviceReplayBuffer
    from ray_tpu.sharding.superstep import build_superstep_fn

    rng = np.random.default_rng(5)
    rows = _sac_rows(rng, 8 * BS)
    K = 2
    p = _sac_policy()
    buf = DeviceReplayBuffer(capacity=8 * BS, seed=7)
    buf.add_tree(dict(rows))
    idx = buf.draw_index_sets(K, BS)
    feed = buf.superstep_feed(idx)
    common = dict(mesh=p.mesh, k=K)
    fn_rings = build_superstep_fn(
        p._device_update_fn(BS),
        label="rings",
        rings=feed,
        **common,
    )
    cols = tuple(sorted(feed.store))
    fn_stacked = build_superstep_fn(
        p._device_update_fn(BS),
        label="stacked",
        stacked_cols=cols,
        **common,
    )

    def collectives(fn, *args):
        txt = fn.lower(*args).as_text()
        return {
            name: len(re.findall(name, txt))
            for name in (
                "all_reduce", "all_gather", "all_to_all",
                "collective_permute",
            )
        }

    active = np.ones(K, np.float32)
    rngs = np.zeros((K, 2), np.uint32)
    c_rings = collectives(
        fn_rings,
        p.params, p.opt_state, p.aux_state,
        (feed.store, feed.idx, feed.extra), active, rngs, {},
    )
    stacked_shape = {
        c: jax.ShapeDtypeStruct(
            (K, BS) + tuple(rows[c].shape[1:]), rows[c].dtype
        )
        for c in cols
    }
    c_stacked = collectives(
        fn_stacked,
        p.params, p.opt_state, p.aux_state,
        stacked_shape, active, rngs, {},
    )
    assert c_rings == c_stacked, (c_rings, c_stacked)
    assert c_rings["all_to_all"] == 0
    assert c_rings["all_gather"] == 0


# -- nan guard inside the scan body ------------------------------------


def test_superstep_nan_guard_in_scan():
    """With ``nan_guard`` on, a non-finite batch inside the chain is
    detected ON DEVICE (device-resident batches never pass the host
    choke points): its update is an exact no-op (params bitwise equal
    to the chain without that slot active), the per-update skip flag
    lands in the stats tree."""
    from ray_tpu.policy.jax_policy import _tree_to_device

    rng = np.random.default_rng(6)
    n = 4 * BS
    m1 = _mesh(1)
    good = _ppo_batch(rng, n)
    bad = dict(good)
    bad[SB.ADVANTAGES] = good[SB.ADVANTAGES].copy()
    bad[SB.ADVANTAGES][3] = np.nan

    p = _ppo_policy(m1, nan_guard=True)
    snap = (
        jax.device_get(p.params), jax.device_get(p.opt_state), p._rng,
    )
    stacked_bad = {
        c: np.stack([good[c], bad[c]]) for c in good
    }
    infos, _, skipped = p.learn_superstep(
        2, n, stacked=stacked_bad, k_max=2
    )
    assert skipped == [False, True]
    guarded = (jax.device_get(p.params), jax.device_get(p.opt_state))
    # rewind and run only the finite slot through the SAME program
    p.params = _tree_to_device(snap[0], p._param_sharding)
    p.opt_state = _tree_to_device(snap[1], p._param_sharding)
    p._rng = snap[2]
    stacked_ok = {c: np.stack([good[c], good[c]]) for c in good}
    infos_ok, _, sk_ok = p.learn_superstep(
        1, n, stacked=stacked_ok, k_max=2
    )
    assert sk_ok == [False]
    # the poisoned slot was an exact no-op
    assert _eq_trees(guarded[0], p.params)
    assert _eq_trees(guarded[1], p.opt_state)

    # without the guard the NaN batch corrupts the params
    p_unguarded = _ppo_policy(m1)
    infos_u, _, sk_u = p_unguarded.learn_superstep(
        2, n, stacked=stacked_bad, k_max=2
    )
    assert sk_u == [False, False]
    assert not _eq_trees(guarded[0], p_unguarded.params)


# -- wiring: learner thread + chained updates + recovery ---------------


def test_learner_thread_superstep_fuses_queued_batches():
    """A LearnerThread whose policy enables ``superstep=2`` fuses
    queued batches into K-update dispatches: the compiled superstep
    program exists and num_steps counts every update. (The thread only
    fuses on its deferred path — policies with host-side
    ``after_learn_on_batch`` hooks keep per-update dispatch — so the
    policy here is hook-free, like the IMPALA family.)"""
    import time

    import gymnasium as gym

    from ray_tpu.algorithms.ppo.ppo import PPOJaxPolicy
    from ray_tpu.execution.learner_thread import LearnerThread
    from ray_tpu.policy.jax_policy import JaxPolicy

    class _HookFreePolicy(PPOJaxPolicy):
        # no host-side per-update stat reaction (IMPALA-style): the
        # thread's deferred/superstep path applies
        after_learn_on_batch = JaxPolicy.after_learn_on_batch

    rng = np.random.default_rng(7)
    n = 4 * BS
    p = _HookFreePolicy(
        gym.spaces.Box(-1, 1, (8,), np.float32),
        gym.spaces.Discrete(4),
        {
            "train_batch_size": n,
            "sgd_minibatch_size": 2 * BS,
            "num_sgd_iter": 2,
            "lr": 1e-3,
            "seed": 0,
            "superstep": 2,
        },
    )
    assert p.supports_superstep
    lt = LearnerThread(p, inqueue_size=16)
    assert lt._superstep_k == 2
    for _ in range(4):
        lt.add_batch(SB(_ppo_batch(rng, n)))
    lt.start()
    deadline = time.time() + 60
    while lt.num_steps < 4 and time.time() < deadline:
        assert lt.healthy(), lt.error
        time.sleep(0.05)
    lt.stop()
    assert lt.num_steps == 4
    assert p._superstep_fns, "no fused dispatch happened"
    infos = []
    while not lt.outqueue.empty():
        infos.append(lt.outqueue.get_nowait())
    assert infos and all(np.isfinite(i[1]["total_loss"]) for i in infos)


@pytest.mark.slow  # ~5 s; fails at seed under jax 0.9.0, passes since
# PR 21 — moved out of tier-1 by that PR's budget rule (the newly
# passing tests compile and run where they used to fail at trace
# time; only newly passing ones may leave)
def test_dqn_chained_updates_superstep_and_recovery(tmp_path):
    """DQN end-to-end with ``superstep=2`` + training_intensity: the
    chained path runs fused windows (superstep counter moves), a
    checkpoint saved mid-cadence restores into a fresh algorithm, and
    fused training resumes after the restore."""
    from ray_tpu.algorithms.dqn.dqn import DQNConfig
    from ray_tpu.telemetry import metrics as telemetry_metrics

    def counter():
        return telemetry_metrics.counter_total(
            telemetry_metrics.SUPERSTEP_UPDATES_TOTAL
        )

    cfg = (
        DQNConfig()
        .environment("CartPole-v1")
        .rollouts(num_rollout_workers=0, rollout_fragment_length=16)
        .training(
            train_batch_size=32,
            lr=1e-3,
            superstep=2,
            replay_buffer_config={"capacity": 2000},
            num_steps_sampled_before_learning_starts=32,
        )
        .reporting(min_time_s_per_iteration=0)
        .debugging(seed=0)
    )
    cfg.training_intensity = 8.0
    algo = cfg.build()
    try:
        before = counter()
        for _ in range(2):
            algo.train()
        assert counter() > before, "no fused superstep ran"
        trained = algo._counters["num_env_steps_trained"]
        assert trained > 0
        ckpt = str(tmp_path / "ckpt")
        import os

        os.makedirs(ckpt, exist_ok=True)
        algo.save_checkpoint(ckpt)
    finally:
        algo.cleanup()

    algo2 = cfg.build()
    try:
        algo2.load_checkpoint(ckpt)
        mid = counter()
        algo2.train()
        assert counter() > mid, "superstep did not resume post-restore"
        assert (
            algo2._counters["num_env_steps_trained"] >= trained
        )
    finally:
        algo2.cleanup()
