"""Device-resident sum tree & sharded Ape-X tests (docs/data_plane.md
"device sum tree & sharded Ape-X"): bit-exact index-draw/priority
parity between the host numpy trees and the mesh-resident f64 tree
programs, zero-recompile across buffer growth and beta annealing,
fixed-seed learn-result parity for DQN and sharded Ape-X across tree
planes, the shared initial-priority TD route, the learn-while-rollout
interleave, and the sample-path zero-copy telemetry."""

import numpy as np
import pytest

import jax

from ray_tpu.data.sample_batch import SampleBatch
from ray_tpu.execution.replay_buffer import (
    DevicePrioritizedReplayBuffer,
    PrioritizedReplayBuffer,
    powered_priorities,
)
from ray_tpu.ops.segment_tree import (
    DeviceSumTree,
    MinSegmentTree,
    SumSegmentTree,
)


def _tree(n, base):
    return {
        "obs": base + np.arange(n * 4, dtype=np.float32).reshape(n, 4),
        "rewards": np.arange(n, dtype=np.float32) + base,
    }


@pytest.mark.parametrize("alpha", [0.6, 1.0])
def test_device_tree_matches_host_stream(alpha):
    """Property test: the SAME random priority/insert/update/draw
    stream through the host SumSegmentTree/MinSegmentTree and the
    device tree — bit-exact index draws, sampled priorities (leaf
    gathers), and final leaf state, across ring wraparound and beta
    annealing."""
    cap = 64
    hs, hm = SumSegmentTree(cap), MinSegmentTree(cap)
    dt = DeviceSumTree(cap)
    rng = np.random.default_rng(0)
    size, ptr, max_pri = 0, 0, 1.0

    for step in range(120):
        # ragged insert at max priority (wraps several times)
        n = int(rng.integers(1, 9))
        pos = (ptr + np.arange(n)) % cap
        ptr = (ptr + n) % cap
        size = min(size + n, cap)
        pv, _ = powered_priorities(np.full(n, max_pri), alpha)
        hs.set_items(pos, pv)
        hm.set_items(pos, pv)
        dt.set_powered(pos, pv)
        # random priority refresh
        m = int(rng.integers(1, 7))
        uidx = rng.integers(0, size, m)
        pri = rng.random(m) * 3
        max_pri = max(max_pri, float(np.maximum(pri, 1e-6).max()))
        pv2, _ = powered_priorities(pri, alpha)
        hs.set_items(uidx, pv2)
        hm.set_items(uidx, pv2)
        dt.set_powered(uidx, pv2)
        if size >= 16 and step % 3 == 0:
            beta = 0.4 + 0.6 * step / 120  # annealing
            B = 16
            rand = rng.random(B)
            # host oracle draw (_PrioritySampling._draw_prioritized)
            total = hs.sum(0, size)
            mass = (rand + np.arange(B)) / B * total
            hidx = np.clip(hs.find_prefixsum_idx(mass), 0, size - 1)
            p_min = hm.min(0, size) / total
            max_w = (p_min * size) ** (-beta)
            p_s = hs[hidx] / total
            hw = ((p_s * size) ** (-beta) / max_w).astype(np.float32)
            didx, dw = dt.draw(rand, size, beta)
            assert np.array_equal(hidx, np.asarray(didx)), step
            assert np.array_equal(hw, np.asarray(dw)), step
            # sampled priorities: the drawn leaves match bit-for-bit
            assert np.array_equal(
                np.asarray(hs[hidx]).view(np.uint64),
                dt.leaf_values(size)[hidx].view(np.uint64),
            )
    lv = dt.leaf_values(size)
    assert np.array_equal(
        lv.view(np.uint64),
        np.asarray(hs[np.arange(size)], np.float64).view(np.uint64),
    )


def test_device_tree_stacked_update_order_and_skip():
    """The superstep's stacked (K, B) refresh: cross-update
    overlapping indices resolve in update order (last write wins,
    like the host's sequential set_items), and masked (nan-skipped)
    slots write nothing."""
    cap = 32
    hs, hm = SumSegmentTree(cap), MinSegmentTree(cap)
    dt = DeviceSumTree(cap)
    rng = np.random.default_rng(1)
    base, _ = powered_priorities(rng.random(cap) * 2, 0.6)
    hs.set_items(np.arange(cap), base)
    hm.set_items(np.arange(cap), base)
    dt.set_powered(np.arange(cap), base)

    K, B = 4, 8
    idx = rng.integers(0, cap, (K, B))
    idx[1, 0] = idx[3, 0] = idx[0, 0]  # force cross-update overlap
    powered, _ = powered_priorities(rng.random((K, B)) * 3, 0.6)
    active = np.array([True, False, True, True])
    for i in range(K):
        if active[i]:
            hs.set_items(idx[i], powered[i])
            hm.set_items(idx[i], powered[i])
    dt.set_powered(idx, powered, active=active)
    assert np.array_equal(
        dt.leaf_values(cap).view(np.uint64),
        np.asarray(hs[np.arange(cap)], np.float64).view(np.uint64),
    )
    # the min tree followed too: root min identical
    rand = np.random.default_rng(2).random(4)
    hidx = np.clip(
        hs.find_prefixsum_idx(
            (rand + np.arange(4)) / 4 * hs.sum(0, cap)
        ),
        0,
        cap - 1,
    )
    didx, _ = dt.draw(rand, cap, 0.4)
    assert np.array_equal(hidx, np.asarray(didx))


def _nodes_equal(dt, hs, hm):
    """ALL ``2 x capacity`` nodes of both trees, as the host reads
    them, against the host trees' arrays bit for bit."""
    for dev, host in ((dt.sum_value, hs), (dt.min_value, hm)):
        got = np.asarray(jax.device_get(dev), np.float64)
        assert got.shape == host.value.shape
        bad = np.flatnonzero(got.view(np.uint64) != host.value.view(np.uint64))
        assert bad.size == 0, (type(host).__name__, bad[:8])


@pytest.mark.parametrize(
    "shape", [(1, 1), (1, 512), (8, 512)], ids=lambda s: "x".join(map(str, s))
)
@pytest.mark.parametrize("cap", [8, 1024, 131072])
def test_device_tree_rebuild_every_node_bitwise(cap, shape):
    """The rebuild of the internal nodes at the capacities and update
    shapes that matter (the DQN cell's are 131,072 x (1, 512) and
    (8, 512)): after every call, with indices repeated across the
    updates of a call and across calls and one update (or the whole
    call) masked out, every node of both trees equals the host's, and
    a draw returns the host's indices."""
    u, b = shape
    hs, hm = SumSegmentTree(cap), MinSegmentTree(cap)
    dt = DeviceSumTree(cap)
    rng = np.random.default_rng(1000 * cap + 10 * u + b)
    base, _ = powered_priorities(rng.random(cap) * 2 + 1e-3, 0.6)
    hs.set_items(np.arange(cap), base)
    hm.set_items(np.arange(cap), base)
    dt.set_powered(np.arange(cap), base)
    _nodes_equal(dt, hs, hm)

    draws = min(16, cap)
    for call in range(3):
        idx = rng.integers(0, cap, (u, b))
        idx[-1, 0] = idx[0, 0]  # repeated across the call's updates
        # a row wider than the tree repeats leaves INSIDE an update,
        # where a scatter's order is not defined: one value a leaf and
        # update, so any order writes the same
        table, _ = powered_priorities(rng.random((u, cap)) * 3, 0.6)
        powered = np.take_along_axis(table, idx, axis=1)
        active = np.ones(u, bool)
        if call == 1:
            active[u // 2] = False  # at u == 1 the whole call
        for i in range(u):
            if active[i]:
                hs.set_items(idx[i], powered[i])
                hm.set_items(idx[i], powered[i])
        dt.set_powered(idx, powered, active=active)
        _nodes_equal(dt, hs, hm)

        rand = rng.random(draws)
        mass = (rand + np.arange(draws)) / draws * hs.sum(0, cap)
        hidx = np.clip(hs.find_prefixsum_idx(mass), 0, cap - 1)
        didx, _ = dt.draw(rand, cap, 0.4)
        assert np.array_equal(hidx, np.asarray(didx)), call


def _full_length_equations(cap, u=2, b=8):
    """``(count, reshapes)`` over the update program's jaxpr, nested
    ones included: equations that take or produce an array of the
    tree's full ``2 x capacity`` length, and those among them that
    reshape it."""
    from ray_tpu import sharding as sharding_lib

    full = 2 * cap
    with sharding_lib.f64_scope():
        fn = DeviceSumTree(cap)._build_update_fn(u, b)
        tree = jax.ShapeDtypeStruct((full,), np.float64)
        with fn.uncounted_traces():
            closed = jax.make_jaxpr(fn._jitted)(
                tree,
                tree,
                jax.ShapeDtypeStruct((u, b), np.int32),
                jax.ShapeDtypeStruct((u, b), np.float64),
                jax.ShapeDtypeStruct((u, b), np.bool_),
            )

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            inner = list(jax.core.jaxprs_in_params(eqn.params))
            if not inner:
                yield eqn
            for sub in inner:  # a nested call is its body, not an equation
                yield from walk(sub)

    def touches(eqn):
        return any(
            int(np.prod(v.aval.shape)) == full
            for v in list(eqn.invars) + list(eqn.outvars)
            if hasattr(v.aval, "shape")
        )

    hits = [e for e in walk(closed.jaxpr) if touches(e)]
    return len(hits), [e for e in hits if e.primitive.name == "reshape"]


def test_device_tree_rebuild_touches_full_array_once():
    """Structure of the update program, on the CPU: the rebuild reads
    each level out of the level it just computed and writes the array
    once, so the equations on the full-length array do not multiply
    with the tree's depth, and none of them reshapes it."""
    small, big = 1 << 6, 1 << 14
    n_small, reshapes_small = _full_length_equations(small)
    n_big, reshapes_big = _full_length_equations(big)
    why = (
        "PR 37: a rebuild that re-read every level out of the 2 x "
        "capacity array (a slice AND a scatter-set of it a level and "
        "tree) made the TPU compiler relayout the WHOLE array at every "
        "level, `reshape f32[131072,2]` padded to 128 lanes: 64 x 90 us "
        "of each 6.2-6.9 ms jit_tree_update in the DQN cell, against "
        "0.4 ms of arithmetic. Carry the level as a value of its own."
    )
    assert not reshapes_small and not reshapes_big, why
    # at most the one write a level and tree beyond what the depth
    # does not change (the leaves' scatters, one read, one write)
    levels = big.bit_length() - small.bit_length()
    assert n_big - n_small <= 2 * levels, (n_small, n_big, why)


def test_device_tree_buffer_zero_recompiles_and_zero_copy():
    """One executable per program across buffer growth, wraparound,
    and beta annealing (size/beta are traced scalars), and the sample
    path ships ZERO payload bytes H2D — only the generator's raw
    uniform stream (counted apart) crosses."""
    from ray_tpu.sharding.compile import compile_stats
    from ray_tpu.telemetry import metrics as telemetry_metrics

    def path(p):
        return telemetry_metrics.h2d_bytes_by_path().get(p, 0.0)

    buf = DevicePrioritizedReplayBuffer(
        capacity=32, alpha=0.6, seed=3, device_tree=True,
        label="ztree",
    )
    rng = np.random.default_rng(4)
    buf.add_tree(_tree(8, 0.0))
    buf.sample(8, beta=0.4)  # warmup: traces draw+gather once
    buf.update_priorities(np.arange(4), rng.random(4))
    before = compile_stats()["traces"]
    sample_b, rng_b = path("replay_sample"), path("replay_rng")
    for i in range(6):
        buf.add_tree(_tree(8, float(i + 1)))  # grows, then wraps
        batch = buf.sample(8, beta=0.4 + 0.05 * i)
        buf.update_priorities(batch.indices, rng.random(8))
    assert compile_stats()["traces"] == before, "retraced"
    assert path("replay_sample") == sample_b  # zero payload bytes
    assert path("replay_rng") - rng_b == 6 * 8 * 8  # uniforms only
    # indices never existed host-side
    assert isinstance(batch.indices, jax.Array)


def test_device_tree_spill_and_cross_plane_state():
    """A memory-cap spill hands the priorities to the host ring
    without perturbing the index stream, and checkpoint state moves
    freely between tree planes."""
    ref = DevicePrioritizedReplayBuffer(
        capacity=64, alpha=0.6, seed=11, device_tree=True
    )
    sp = DevicePrioritizedReplayBuffer(
        capacity=64, alpha=0.6, seed=11, device_tree=True,
        memory_cap_bytes=500,
    )
    t = _tree(8, 0.0)
    ref.add_tree(dict(t))
    sp.add_tree(dict(t))
    assert not ref.spilled and sp.spilled
    assert sp.tree_plane == "host" and ref.tree_plane == "device"
    out = sp.sample(4, beta=0.4)
    dev_out = ref.sample(4, beta=0.4)
    assert np.array_equal(
        np.asarray(out["batch_indexes"]),
        np.asarray(dev_out.indices).astype(np.int64),
    )
    assert np.array_equal(
        out["weights"], jax.device_get(dev_out.tree["weights"])
    )
    # host-tree checkpoint restores into a device-tree buffer
    host = DevicePrioritizedReplayBuffer(
        capacity=64, alpha=0.6, seed=11, device_tree=False
    )
    host.add_tree(dict(t))
    host.update_priorities(np.arange(4), np.linspace(0.2, 2.0, 4))
    d2 = DevicePrioritizedReplayBuffer(
        capacity=64, alpha=0.6, seed=77, device_tree=True
    )
    d2.set_state(host.get_state())
    assert np.array_equal(
        d2._priority_state()["leaf_values"].view(np.uint64),
        host._priority_state()["leaf_values"].view(np.uint64),
    )
    assert d2._max_priority == host._max_priority


def _dqn_config(device_tree, **over):
    from ray_tpu.algorithms.dqn.dqn import DQNConfig

    cfg = (
        DQNConfig()
        .environment("CartPole-v1")
        .rollouts(num_rollout_workers=0, rollout_fragment_length=16)
        .training(
            train_batch_size=32,
            num_steps_sampled_before_learning_starts=48,
            replay_buffer_config={
                "prioritized_replay": True,
                "capacity": 2000,
            },
            training_intensity=8.0,
            superstep=2,
            replay_device_resident=True,
            replay_device_tree=device_tree,
            target_network_update_freq=128,
            model={"fcnet_hiddens": [16, 16]},
        )
        .debugging(seed=0)
    )
    for k, v in over.items():
        setattr(cfg, k, v)
    return cfg


@pytest.mark.slow  # ~8 s DQN e2e; moved out of tier-1 by the PR-1
# budget rule — tier-1 keeps the host/device tree parity pins above,
# test_superstep's DQN prioritized-superstep parity, and the
# prioritized device-replay DQN run in test_dispatch_diet.py
def test_dqn_per_device_tree_bitwise_parity():
    """Acceptance: fixed-seed DQN learn results are bitwise identical
    device-tree vs host-tree on the 1-shard mesh — params, sum-tree
    leaves, max-priority watermark, and generator state — through the
    fused K=2 superstep INCLUDING the stacked in-scan PER refresh."""

    def run(device_tree):
        algo = _dqn_config(device_tree).build()
        try:
            for _ in range(4):
                algo.train()
            buf = algo.local_replay_buffer.buffers["default_policy"]
            assert (buf._dtree is not None) is device_tree
            return (
                jax.device_get(algo.get_policy().params),
                algo._counters["num_env_steps_trained"],
                buf._priority_state(),
                buf._rng.bit_generator.state,
            )
        finally:
            algo.cleanup()

    ph, th, sh, gh = run(False)
    pd, td, sd, gd = run(True)
    assert th == td and th > 0
    for a, b in zip(
        jax.tree_util.tree_leaves(ph), jax.tree_util.tree_leaves(pd)
    ):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(
        sh["leaf_values"].view(np.uint64),
        sd["leaf_values"].view(np.uint64),
    )
    assert sh["max_priority"] == sd["max_priority"]
    assert gh == gd


def _apex_config(device_tree, **over):
    from ray_tpu.algorithms.apex_dqn import ApexDQNConfig

    cfg = (
        ApexDQNConfig()
        .environment("CartPole-v1")
        .rollouts(num_rollout_workers=0, rollout_fragment_length=16)
        .training(
            train_batch_size=32,
            num_steps_sampled_before_learning_starts=64,
            num_replay_buffer_shards=2,
            superstep=2,
            replay_device_resident=True,
            replay_device_tree=device_tree,
            target_network_update_freq=256,
            model={"fcnet_hiddens": [16, 16]},
        )
        .debugging(seed=0)
    )
    for k, v in over.items():
        setattr(cfg, k, v)
    return cfg


@pytest.mark.slow  # ~17 s on this container; moved out of
# tier-1 with PR 12 (budget rule: suite at ~892 s vs the 870 s cap)
@pytest.mark.slow  # ~17 s on this container; moved out of
# tier-1 with PR 12 (budget rule: suite at ~892 s vs the 870 s cap)
def test_apex_device_shards_bitwise_parity():
    """Ape-X e2e on sharded device replay: fixed-seed param parity —
    device sum trees vs host sum trees behind the SAME mesh-placed
    shard rings (round-robin routing, per-shard seeds, superstep
    learn loop all shared) — plus shard occupancy and per-shard
    priority-state parity."""

    def run(device_tree):
        algo = _apex_config(device_tree).build()
        try:
            assert algo._apex_device and len(algo.replay_shards) == 2
            assert (
                algo.replay_shards[0]._dtree is not None
            ) is device_tree
            for _ in range(4):
                algo.train()
            return (
                jax.device_get(algo.get_policy().params),
                [len(s) for s in algo.replay_shards],
                algo._counters["num_env_steps_trained"],
                [s._priority_state() for s in algo.replay_shards],
            )
        finally:
            algo.cleanup()

    ph, szh, th, sth = run(False)
    pd, szd, td, std = run(True)
    assert szh == szd and all(s > 0 for s in szh)
    assert th == td and th > 0
    for a, b in zip(
        jax.tree_util.tree_leaves(ph), jax.tree_util.tree_leaves(pd)
    ):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(sth, std):
        assert np.array_equal(
            a["leaf_values"].view(np.uint64),
            b["leaf_values"].view(np.uint64),
        )
        assert a["max_priority"] == b["max_priority"]


def test_apex_initial_priorities_shared_td_route():
    """Regression pin: the mesh plane's initial-priority computation
    (the shared ``_td_error_device_fn`` run on the ONE uploaded
    insert tree) produces priorities bitwise identical to the legacy
    host route ``compute_td_error(batch) + 1e-6``."""
    from ray_tpu.algorithms.dqn.dqn import adjust_nstep

    # 1-shard mesh: the device route's TD forward is row-sharded, and
    # multi-shard per-shard matmul shapes round the last ulp (the
    # documented mesh property) — the bit-pin belongs on one shard
    algo = (
        _apex_config(True, worker_side_prioritization=True)
        .resources(learner_devices=1)
        .build()
    )
    try:
        policy = algo.get_policy()
        w = algo.workers.local_worker()
        batch = w.sample()
        if hasattr(batch, "policy_batches"):
            batch = batch.policy_batches["default_policy"]
        # the legacy route: n-step fold, then the host-batch TD
        # forward (fold a copy — _route_to_replay folds the original)
        ref = SampleBatch(
            {k: np.copy(np.asarray(v)) for k, v in batch.items()}
        )
        adjust_nstep(
            algo.config["n_step"], algo.config["gamma"], ref
        )
        host_prios = policy.compute_td_error(ref) + 1e-6

        captured = {}
        shard = algo.replay_shards[0]
        orig = shard.add_device_tree

        def spy(tree, priorities=None):
            captured["prios"] = priorities
            return orig(tree, priorities=priorities)

        shard.add_device_tree = spy
        algo._shard_rr = 0  # route to the spied shard
        algo._route_to_replay(batch)
        assert captured["prios"] is not None
        assert np.array_equal(
            np.asarray(host_prios), np.asarray(captured["prios"])
        )
    finally:
        algo.cleanup()


@pytest.mark.slow  # ~14 s on this container; moved out of
# tier-1 with PR 12 (budget rule: suite at ~892 s vs the 870 s cap)
@pytest.mark.slow  # ~14 s on this container; moved out of
# tier-1 with PR 12 (budget rule: suite at ~892 s vs the 870 s cap)
def test_learn_while_rollout_interleave():
    """The off-policy jax-lane interleave: deterministic fixed-seed
    results, identical sampled/trained step accounting vs the serial
    cadence, and the telemetry roll-up reports the device tree with a
    zero-payload sample path."""
    from ray_tpu.algorithms.dqn.dqn import DQNConfig
    from ray_tpu.util import tracing

    def build(interleave):
        return (
            DQNConfig()
            .environment("CartPoleJax-v0", env_backend="jax")
            .resources(learner_devices=1)
            .rollouts(
                num_rollout_workers=0,
                rollout_fragment_length=8,
                num_envs_per_worker=4,
            )
            .training(
                train_batch_size=32,
                num_steps_sampled_before_learning_starts=64,
                replay_buffer_config={
                    "prioritized_replay": True,
                    "capacity": 2000,
                },
                replay_device_resident=True,
                replay_device_tree=True,
                learn_while_rollout=interleave,
                training_intensity=4.0,
                superstep=2,
                target_network_update_freq=256,
                model={"fcnet_hiddens": [16, 16]},
            )
            .debugging(seed=0)
            .build()
        )

    def run(interleave, trace=False):
        algo = build(interleave)
        if trace:
            algo.config["telemetry_config"] = {"trace": True}
            tracing.enable()
        try:
            r = {}
            for _ in range(4):
                r = algo.train()
            return (
                jax.device_get(algo.get_policy().params),
                algo._counters["num_env_steps_sampled"],
                algo._counters["num_env_steps_trained"],
                r,
            )
        finally:
            algo.cleanup()
            if trace:
                tracing.disable()

    p0, s0, t0, _ = run(False)
    p1, s1, t1, r1 = run(True, trace=True)
    assert s0 == s1 and t0 == t1 and t1 > 0
    replay = r1["info"]["telemetry"]["replay"]
    assert replay["tree"] == "device"
    assert replay["sample_h2d_bytes"] == 0.0
    assert replay["rng_h2d_bytes"] > 0
    assert replay["d2h_bytes"] > 0  # the PER refresh |td| pull
    # the interleaved cadence is itself deterministic
    p2, s2, t2, _ = run(True)
    assert (s1, t1) == (s2, t2)
    for a, b in zip(
        jax.tree_util.tree_leaves(p1), jax.tree_util.tree_leaves(p2)
    ):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_replay_tree_ops_counters():
    """ray_tpu_replay_tree_ops_total{op=insert|update|sample,
    tree=host|device} counts each plane's tree walks."""
    from ray_tpu.telemetry import metrics as telemetry_metrics

    def series():
        m = telemetry_metrics.get_metric(
            telemetry_metrics.REPLAY_TREE_OPS_TOTAL
        )
        out = {}
        for tags, v in (m.series() if m else ()):
            d = dict(tags)
            out[(d["op"], d["tree"])] = v
        return out

    before = series()

    def delta(op, tree):
        return series().get((op, tree), 0.0) - before.get(
            (op, tree), 0.0
        )

    rng = np.random.default_rng(0)
    host = PrioritizedReplayBuffer(capacity=32, alpha=0.6, seed=1)
    host.add(SampleBatch(_tree(8, 0.0)))
    host.sample(4, beta=0.4)
    host.update_priorities(np.arange(4), rng.random(4))
    assert delta("insert", "host") == 1
    assert delta("sample", "host") == 1
    assert delta("update", "host") == 1

    dev = DevicePrioritizedReplayBuffer(
        capacity=32, alpha=0.6, seed=1, device_tree=True
    )
    dev.add_tree(_tree(8, 0.0))
    b = dev.sample(4, beta=0.4)
    dev.update_priorities(b.indices, rng.random(4))
    assert delta("insert", "device") == 1
    assert delta("sample", "device") == 1
    assert delta("update", "device") == 1
