"""A minibatch that is the whole per-shard batch is taken as it lies
(``JaxPolicy._nest_device_fn``, docs/data_plane.md "the rows' path"):
no permutation, no gather, no uint8 -> uint32 pack; a strict subset of
rows is still packed, gathered and unpacked.

Held here, on uint8-pixel DQN policies with prioritized replay:

- (a) the K=4 superstep against 4 per-update calls, over a host ring
  and over a device ring (whose feed hands the scan WORDS and unpacks
  an update's rows inside it): bit-identical, as before;
- (b) the whole-batch nest against the parent commit's permuted nest,
  kept below as ``_permuted_nest``, with the convolutions in float32:
  a float32 mean adds its rows in another order, nothing else —
  weights, Adam state and priorities agree to 1e-6 after 4 updates,
  and a row's priority is still at its own place;
- (c) 4 minibatches a batch: bit-identical to that copy; with NoisyNet
  the loss draws from ``mb_rngs``, so (b) and (c) also hold the key
  stream to the parent's;
- (d) the trace-time counter says which form a nest took.
"""

import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from ray_tpu import sharding as sharding_lib
from ray_tpu.data.sample_batch import SampleBatch as SB
from ray_tpu.policy import jax_policy as jax_policy_lib
from ray_tpu.telemetry import metrics as telemetry_metrics

BS, K = 32, 4
PIXELS = (8, 8, 4)


def _policy(**over):
    import gymnasium as gym

    from ray_tpu.algorithms.dqn.dqn import DQNJaxPolicy

    cfg = {
        "seed": 0,
        "lr": 1e-3,
        "train_batch_size": BS,
        "dueling": True,
        "double_q": True,
        "model": {
            "conv_filters": [[8, [4, 4], [2, 2]]],
            "post_fcnet_hiddens": [16],
        },
        "_mesh": sharding_lib.get_mesh(devices=jax.devices()[:1]),
    }
    cfg.update(over)
    return DQNJaxPolicy(
        gym.spaces.Box(0, 255, PIXELS, np.uint8), gym.spaces.Discrete(3), cfg
    )


def _rows(rng, n):
    return {
        SB.OBS: rng.integers(0, 255, (n,) + PIXELS, dtype=np.uint8),
        SB.NEXT_OBS: rng.integers(0, 255, (n,) + PIXELS, dtype=np.uint8),
        SB.ACTIONS: rng.integers(0, 3, n).astype(np.int64),
        SB.REWARDS: rng.standard_normal(n).astype(np.float32),
        SB.TERMINATEDS: (rng.random(n) < 0.1).astype(np.float32),
    }


def _leaves(tree):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(jax.device_get(tree))]


def _identical(a, b):
    return all(np.array_equal(x, y) for x, y in zip(_leaves(a), _leaves(b), strict=True))


def _rel(a, b):
    """Largest relative L2 distance over the float leaves of two trees."""
    worst = 0.0
    for x, y in zip(_leaves(a), _leaves(b), strict=True):
        if np.issubdtype(x.dtype, np.floating) and x.size:
            scale = max(float(np.linalg.norm(y)), 1e-12)
            worst = max(worst, float(np.linalg.norm(x - y)) / scale)
        else:
            assert np.array_equal(x, y)
    return worst


# -- the parent commit's nest (94d520d), flat rows, no frame pool ---------


def _permuted_nest(self, batch_size, with_frames=False):
    """``JaxPolicy._nest_device_fn`` as the parent commit had it: every
    uint8 column packed to uint32 words, every minibatch gathered by a
    permutation's indices (the only minibatch too) and unpacked."""
    assert not with_frames and self._unroll_T == 1
    n_shards = self.n_shards
    b_loc = max(1, batch_size // n_shards)
    mb_loc = min(b_loc, max(1, self.minibatch_size // n_shards))
    num_mb = max(1, b_loc // mb_loc)
    num_iters = self.num_sgd_iter
    tx = self._tx
    axis = sharding_lib.BATCH_AXIS
    loss_fn = self.loss_with_aux

    def device_fn(params, opt_state, aux, batch, rng, coeffs):
        rng = jax.random.fold_in(rng, jax.lax.axis_index(axis))
        packed_shapes = {}
        batch = dict(batch)
        for k, v in list(batch.items()):
            if (
                v.dtype == jnp.uint8
                and v.ndim >= 2
                and int(np.prod(v.shape[1:])) % 4 == 0
            ):
                packed_shapes[k] = v.shape
                batch[k] = jax.lax.bitcast_convert_type(
                    v.reshape(v.shape[0], -1, 4), jnp.uint32
                )

        def _unpack(k, v):
            shp = packed_shapes.get(k)
            if shp is None:
                return v
            u8 = jax.lax.bitcast_convert_type(v, jnp.uint8)
            return u8.reshape((v.shape[0],) + shp[1:])

        def mb_step(carry, mb_rng_idx):
            params, opt_state = carry
            idx, mb_rng, is_last = mb_rng_idx
            mb = {k: _unpack(k, v[idx]) for k, v in batch.items()}
            (loss, stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                sharding_lib.varying(params, axis), aux, mb, mb_rng, coeffs
            )
            grads = jax.lax.pmean(grads, axis)
            updates, opt_state = tx.update(grads, opt_state, params)
            lr = coeffs["lr"]
            updates = jax.tree_util.tree_map(
                lambda u: -lr * u.astype(jnp.float32), updates
            )
            params = optax.apply_updates(params, updates)
            gnorm = jax.lax.cond(
                is_last,
                lambda: jax_policy_lib._global_norm(grads),
                lambda: jnp.float32(0.0),
            )
            stats = dict(stats, total_loss=loss, grad_gnorm=gnorm)
            return (params, opt_state), stats

        def epoch(carry, rng_e_i):
            rng_e, ep_i = rng_e_i
            perm_rng, scan_rng = jax.random.split(rng_e)
            perm = jax.random.permutation(perm_rng, b_loc)
            idx = perm[: num_mb * mb_loc].reshape(num_mb, mb_loc)
            mb_rngs = jax.random.split(scan_rng, num_mb)
            is_last = (ep_i == num_iters - 1) & (
                jnp.arange(num_mb) == num_mb - 1
            )
            return jax.lax.scan(mb_step, carry, (idx, mb_rngs, is_last))

        rngs = jax.random.split(rng, num_iters)
        (params, opt_state), stats = jax.lax.scan(
            epoch, (params, opt_state), (rngs, jnp.arange(num_iters))
        )

        def reduce_stat(name, x):
            agg = x.sum() if name == "grad_gnorm" else x.mean()
            return jax.lax.pmean(agg, axis)

        stats = {k: reduce_stat(k, v) for k, v in stats.items()}
        return params, opt_state, stats

    return device_fn


def _pair(**over):
    """Two policies of one seed: this tree's nest, and the parent's."""
    new, old = _policy(**over), _policy(**over)
    assert _identical(new.params, old.params)
    old._nest_device_fn = types.MethodType(_permuted_nest, old)
    assert old.supports_superstep  # the patch is the instance's alone
    return new, old


def _stacked(policy, rng, k):
    trees = []
    for _ in range(k):
        b = SB(_rows(rng, BS))
        b["weights"] = rng.uniform(0.5, 1.0, BS).astype(np.float32)
        tree, n = policy.prepare_batch(b)
        assert n == BS and tree[SB.OBS].dtype == np.uint8
        trees.append(tree)
    return {c: np.stack([t[c] for t in trees]) for c in trees[0]}


def _superstep(policy, stacked):
    """K fused updates with the priority pass; ``(priorities, stats)``."""
    infos, pri, skipped = policy.learn_superstep(
        K, BS, stacked=stacked, refresh_priorities=True
    )
    assert not any(skipped)
    return np.asarray(pri), infos


# -- (a) ----------------------------------------------------------------------


@pytest.mark.parametrize("ring", ["host-ring", "device-ring"])
def test_superstep_is_its_updates_one_by_one(ring):
    """uint8 observations, prioritized replay, K=4: the fused chain
    leaves the weights, the optimizer state, the sum tree's leaves and
    the generator where 4 per-update calls (learn, TD error, refresh)
    on the same draws leave them, bit for bit, over either ring."""
    from ray_tpu.execution.replay_buffer import (
        DevicePrioritizedReplayBuffer,
        PrioritizedReplayBuffer,
    )
    from ray_tpu.execution.train_ops import superstep_train_replay

    rows = _rows(np.random.default_rng(2), 8 * BS)
    mesh = sharding_lib.get_mesh(devices=jax.devices()[:1])
    device = ring == "device-ring"

    def filled():
        if device:
            buf = DevicePrioritizedReplayBuffer(
                capacity=8 * BS, alpha=0.6, seed=9, mesh=mesh
            )
            buf.add_tree(dict(rows))
        else:
            buf = PrioritizedReplayBuffer(capacity=8 * BS, alpha=0.6, seed=9)
            buf.add(SB(dict(rows)))
        buf.update_priorities(np.arange(16), np.linspace(1.0, 5.0, 16))
        return buf

    p_ref, p_sup = _policy(), _policy()
    b_ref, b_sup = filled(), filled()
    idx, w = b_ref.draw_prioritized_sets(K, BS, 0.4)
    for i in range(K):
        if device:
            tree = dict(b_ref.gather(idx[i]).tree)
            assert tree[SB.OBS].dtype == jnp.uint8
            tree["weights"] = jax.device_put(w[i], sharding_lib.batch_sharded(mesh))
            td_src = b_ref.gather(idx[i])
        else:
            b = b_ref._make_batch(idx[i])
            b["weights"] = w[i]
            b["batch_indexes"] = idx[i].astype(np.int64)
            host, n = p_ref.prepare_batch(b)
            assert n == BS
            tree = jax.device_put(host, p_ref.batch_shardings(host))
            td_src = b_ref._make_batch(idx[i])
        jax.device_get(p_ref.learn_on_device_batch(tree, BS, defer_stats=True))
        b_ref.update_priorities(idx[i], p_ref.compute_td_error(td_src) + 1e-6)

    info = superstep_train_replay(
        None, p_sup, b_sup, K, K, BS, prioritized=True, beta=0.4
    )
    assert info and np.isfinite(info["mean_td_error"])
    assert _identical(p_ref.params, p_sup.params)
    assert _identical(p_ref.opt_state, p_sup.opt_state)
    every = np.arange(8 * BS)
    assert np.array_equal(
        np.asarray(b_ref._sum_tree[every]), np.asarray(b_sup._sum_tree[every])
    )
    assert b_ref._rng.bit_generator.state == b_sup._rng.bit_generator.state


def test_host_and_device_ring_feed_the_same_bytes():
    """One chain of draws over a host ring (the stacked feed: uint8
    columns) and over a device ring (words through the scan, bytes made
    in it): the same weights and leaves, bit for bit."""
    from ray_tpu.execution.replay_buffer import (
        DevicePrioritizedReplayBuffer,
        PrioritizedReplayBuffer,
    )
    from ray_tpu.execution.train_ops import superstep_train_replay

    rows = _rows(np.random.default_rng(3), 8 * BS)
    host = PrioritizedReplayBuffer(capacity=8 * BS, alpha=0.6, seed=9)
    host.add(SB(dict(rows)))
    dev = DevicePrioritizedReplayBuffer(
        capacity=8 * BS, alpha=0.6, seed=9,
        mesh=sharding_lib.get_mesh(devices=jax.devices()[:1]),
    )
    dev.add_tree(dict(rows))
    assert dev._store[SB.OBS].dtype == jnp.uint32  # stored as words
    p_host, p_dev = _policy(), _policy()
    for p, buf in ((p_host, host), (p_dev, dev)):
        assert superstep_train_replay(
            None, p, buf, K, K, BS, prioritized=True, beta=0.4
        )
    assert _identical(p_host.params, p_dev.params)
    assert _identical(p_host.opt_state, p_dev.opt_state)
    every = np.arange(8 * BS)
    assert np.array_equal(
        np.asarray(host._sum_tree[every]), np.asarray(dev._sum_tree[every])
    )


# -- (b) ----------------------------------------------------------------------


@pytest.fixture
def float32_convs(monkeypatch):
    """The DQN model with float32 convolutions. At its own precision a
    convolution's bias gradient is a bfloat16 sum over rows and
    positions, which reads another order of its rows at 1e-2; (b) is
    about the order of float32 sums."""
    from ray_tpu.algorithms.dqn import dqn

    class Float32Convs(dqn.DQNModel):
        conv_dtype: str = "float32"

    monkeypatch.setattr(dqn, "DQNModel", Float32Convs)



@pytest.mark.parametrize("noisy", [False, True], ids=["plain", "noisy-loss"])
def test_whole_batch_nest_is_the_permuted_nest_to_the_last_ulps(
    noisy, float32_convs
):
    new, old = _pair(noisy=noisy)
    stacked = _stacked(new, np.random.default_rng(4), K)
    before = telemetry_metrics.learn_minibatch_lowerings()
    pri_new, _ = _superstep(new, stacked)
    after = telemetry_metrics.learn_minibatch_lowerings()
    assert after.get("whole", 0) - before.get("whole", 0) == 1
    assert after.get("gathered", 0) == before.get("gathered", 0)
    pri_old, _ = _superstep(old, stacked)
    assert _rel(new.params, old.params) < 1e-6
    assert _rel(new.opt_state, old.opt_state) < 1e-6
    assert _rel(pri_new, pri_old) < 1e-6
    # keys a noisy loss draws from: another key reads 1e-1 above
    assert not _identical(new.params, _policy(noisy=noisy).params)
    if not noisy:
        # row i's priority is at place i: the last update's priorities
        # against the finished weights' own TD errors, row by row
        last = SB({c: v[K - 1] for c, v in stacked.items()})
        np.testing.assert_allclose(
            np.abs(pri_new[K - 1]), new.compute_td_error(last), rtol=1e-5, atol=1e-6
        )
        assert np.std(pri_new[K - 1]) > 1e-3  # rows differ: an order would show


def test_whole_batch_nest_over_several_epochs(float32_convs):
    """``num_sgd_iter`` 3 over one minibatch a batch: still the batch as
    it lies, each epoch's key from the same split."""
    new, old = _pair(num_sgd_iter=3, noisy=True)
    stacked = _stacked(new, np.random.default_rng(5), K)
    pri_new, _ = _superstep(new, stacked)
    pri_old, _ = _superstep(old, stacked)
    assert _rel(new.params, old.params) < 1e-6
    assert _rel(new.opt_state, old.opt_state) < 1e-6
    assert _rel(pri_new, pri_old) < 1e-6


# -- (c) ----------------------------------------------------------------------


@pytest.mark.parametrize("noisy", [False, True], ids=["plain", "noisy-loss"])
def test_four_minibatches_are_gathered_as_the_parent_gathered_them(noisy):
    new, old = _pair(sgd_minibatch_size=BS // 4, num_sgd_iter=2, noisy=noisy)
    stacked = _stacked(new, np.random.default_rng(6), K)
    before = telemetry_metrics.learn_minibatch_lowerings()
    pri_new, infos_new = _superstep(new, stacked)
    after = telemetry_metrics.learn_minibatch_lowerings()
    assert after.get("gathered", 0) - before.get("gathered", 0) == 1
    assert after.get("whole", 0) == before.get("whole", 0)
    pri_old, infos_old = _superstep(old, stacked)
    assert _identical(new.params, old.params)
    assert _identical(new.opt_state, old.opt_state)
    assert np.array_equal(pri_new, pri_old)
    assert [i["total_loss"] for i in infos_new] == [i["total_loss"] for i in infos_old]


# -- (d) ----------------------------------------------------------------------


@pytest.mark.parametrize(
    "minibatch, form", [(None, "whole"), (16, "gathered")], ids=["whole", "gathered"]
)
def test_counter_names_the_form_a_ppo_nest_took(minibatch, form):
    """PPO, 64 rows: one minibatch of 64 is ``whole`` (for any number of
    epochs), 4 of 16 are ``gathered``; counted once a traced nest."""
    import gymnasium as gym

    from ray_tpu.algorithms.ppo.ppo import PPOJaxPolicy

    rng = np.random.default_rng(7)
    n = 64
    policy = PPOJaxPolicy(
        gym.spaces.Box(-1, 1, (8,), np.float32),
        gym.spaces.Discrete(4),
        {
            "train_batch_size": n, "sgd_minibatch_size": minibatch or n,
            "num_sgd_iter": 2, "lr": 1e-3, "seed": 0,
            "_mesh": sharding_lib.get_mesh(devices=jax.devices()[:1]),
        },
    )
    batch = SB({
        SB.OBS: rng.standard_normal((n, 8)).astype(np.float32),
        SB.ACTIONS: rng.integers(0, 4, n).astype(np.int64),
        SB.ACTION_LOGP: np.full(n, -1.3, np.float32),
        SB.ACTION_DIST_INPUTS: rng.standard_normal((n, 4)).astype(np.float32),
        SB.ADVANTAGES: rng.standard_normal(n).astype(np.float32),
        SB.VALUE_TARGETS: rng.standard_normal(n).astype(np.float32),
    })
    before = telemetry_metrics.learn_minibatch_lowerings()
    for _ in range(2):  # the second call traces nothing
        assert np.isfinite(policy.learn_on_batch(batch)["total_loss"])
    after = telemetry_metrics.learn_minibatch_lowerings()
    grown = {k: after.get(k, 0) - before.get(k, 0) for k in ("whole", "gathered")}
    assert grown == {form: 1, ("gathered" if form == "whole" else "whole"): 0}
