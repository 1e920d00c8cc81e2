"""ray_tpu.sharding runtime tests (ISSUE 2).

All run on the 8-device simulated CPU platform conftest.py forces
(``--xla_force_host_platform_device_count=8``): mesh construction and
caching, spec builders incl. the ragged-leading-dim fallback, donation,
compile-cache stats, and device-count parity on a fixed-seed PPO learn
step.
"""

import functools

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu import sharding as sl
from ray_tpu.data.sample_batch import SampleBatch


# ---------------------------------------------------------------------------
# mesh
# ---------------------------------------------------------------------------


def test_mesh_default_is_1d_batch_over_all_devices():
    mesh = sl.get_mesh()
    assert mesh.axis_names == ("batch",)
    assert sl.data_axis(mesh) == "batch"
    assert sl.num_shards(mesh) == len(jax.devices()) == 8


def test_mesh_is_cached_per_process():
    assert sl.get_mesh() is sl.get_mesh()
    sub = sl.get_mesh(devices=jax.devices()[:4])
    assert sub is sl.get_mesh(devices=jax.devices()[:4])
    assert sub is not sl.get_mesh()
    assert sl.num_shards(sub) == 4


def test_mesh_axis_shapes_and_oversubscription():
    mesh = sl.get_mesh(axis_shapes=[("batch", 4), ("model", 2)])
    assert mesh.axis_names == ("batch", "model")
    assert dict(mesh.shape) == {"batch": 4, "model": 2}
    with pytest.raises(ValueError):
        sl.get_mesh(axis_shapes=[("batch", 16)])


def test_resolve_mesh_default_and_injected():
    assert sl.resolve_mesh({}).axis_names == ("batch",)
    injected = sl.get_mesh(devices=jax.devices()[:2])
    assert sl.resolve_mesh({"_mesh": injected}) is injected


# ---------------------------------------------------------------------------
# specs
# ---------------------------------------------------------------------------


def test_leaf_sharding_ragged_fallback():
    mesh = sl.get_mesh()
    even = np.zeros((16, 3), np.float32)
    ragged = np.zeros((13, 3), np.float32)  # 13 % 8 != 0
    scalar = np.float32(1.0)
    assert tuple(sl.leaf_sharding(even, mesh).spec) == ("batch",)
    assert tuple(sl.leaf_sharding(ragged, mesh).spec) == ()
    assert tuple(sl.leaf_sharding(scalar, mesh).spec) == ()


def test_sharding_tree_per_leaf_and_replicate_keys():
    mesh = sl.get_mesh()
    tree = {
        "rows": np.zeros((32, 4), np.float32),
        "ragged": np.zeros((9,), np.float32),
        "pool": np.zeros((16, 8), np.float32),
    }
    specs = sl.sharding_tree(tree, mesh, replicate_keys=("pool",))
    assert tuple(specs["rows"].spec) == ("batch",)
    assert tuple(specs["ragged"].spec) == ()
    # divisible but pinned replicated by key
    assert tuple(specs["pool"].spec) == ()


def test_shard_batch_places_rows_across_devices():
    mesh = sl.get_mesh()
    dev = sl.shard_batch(
        {"x": np.arange(64, dtype=np.float32)}, mesh, block=True
    )
    x = dev["x"]
    assert x.sharding.is_equivalent_to(sl.batch_sharded(mesh), x.ndim)
    assert len(x.addressable_shards) == 8
    assert x.addressable_shards[0].data.shape == (8,)


# ---------------------------------------------------------------------------
# compile (sharded_jit)
# ---------------------------------------------------------------------------


def test_sharded_jit_donation_releases_buffers():
    mesh = sl.get_mesh()
    rep = sl.replicated(mesh)
    fn = sl.sharded_jit(
        lambda x: x * 2.0,
        in_specs=(rep,),
        out_specs=rep,
        donate_argnums=(0,),
    )
    x = jax.device_put(jnp.ones((128,)), rep)
    y = fn(x)
    assert x.is_deleted()  # donated into the output
    assert not y.is_deleted()
    np.testing.assert_allclose(np.asarray(y), 2.0)


def test_sharded_jit_compile_cache_stats():
    mesh = sl.get_mesh()
    dat = sl.batch_sharded(mesh)
    fn = sl.sharded_jit(
        lambda x: x.sum(), in_specs=(dat,), label="sum"
    )
    a = jax.device_put(jnp.ones((16,)), dat)
    fn(a)
    assert fn.stats()["traces"] == 1
    fn(a)  # same shape: cache hit
    assert fn.traces == 1 and fn.recompiles == 0 and fn.calls == 2
    fn(jax.device_put(jnp.ones((32,)), dat))  # new shape: retrace
    assert fn.traces == 2 and fn.recompiles == 1
    agg = sl.compile_stats()
    assert agg["calls"] >= 3
    assert any(
        s["label"] == "sum" for s in agg["per_function"]
    )


# ---------------------------------------------------------------------------
# device-count parity: fixed-seed PPO learn step, n devices vs one
# ---------------------------------------------------------------------------


def _ppo_policy(n_dev):
    from ray_tpu.algorithms.ppo.ppo import PPOJaxPolicy

    return PPOJaxPolicy(
        gym.spaces.Box(-1.0, 1.0, (8,), np.float32),
        gym.spaces.Discrete(4),
        {
            "_mesh": sl.get_mesh(devices=jax.devices()[:n_dev]),
            "model": {"fcnet_hiddens": [16]},
            "train_batch_size": 32,
            # full-batch minibatches: the per-shard shuffle then picks
            # no rows, so every device count takes the same two steps
            "sgd_minibatch_size": 32,
            "num_sgd_iter": 2,
            "lr": 1e-3,
            "seed": 0,
        },
    )


def _ppo_batch(b=32):
    rng = np.random.default_rng(42)
    return SampleBatch(
        {
            SampleBatch.OBS: rng.standard_normal((b, 8)).astype(
                np.float32
            ),
            SampleBatch.ACTIONS: rng.integers(0, 4, b).astype(
                np.int64
            ),
            SampleBatch.ACTION_LOGP: np.full(b, -1.4, np.float32),
            SampleBatch.ACTION_DIST_INPUTS: rng.standard_normal(
                (b, 4)
            ).astype(np.float32),
            SampleBatch.ADVANTAGES: rng.standard_normal(b).astype(
                np.float32
            ),
            SampleBatch.VALUE_TARGETS: rng.standard_normal(b).astype(
                np.float32
            ),
        }
    )


@pytest.mark.parametrize("n_dev", [1, 8])
def test_mesh_parity_fixed_seed_ppo(n_dev):
    """Acceptance: a fixed-seed PPO learn_on_batch on an n-device mesh
    takes the steps of the one-device mesh — bitwise on 1 device
    (two policies, one program), to float32 reduction order on 8
    simulated host devices — and the compiled program does not
    retrace across constant-shape steps."""
    results = {}
    for n in (n_dev, 1):
        pol = _ppo_policy(n)
        pol.learn_on_batch(_ppo_batch())
        stats = pol.learn_on_batch(_ppo_batch())
        fn = pol.learn_fn(32)
        assert fn.traces == 1 and fn.recompiles == 0, n
        # the batch really lands sharded over "batch"
        assert sl.data_axis(pol.mesh) == "batch"
        assert pol.n_shards == n
        results[n] = (stats, jax.device_get(pol.params))
    s_n, w_n = results[n_dev]
    s_1, w_1 = results[1]
    same = (
        np.testing.assert_array_equal
        if n_dev == 1
        else functools.partial(
            np.testing.assert_allclose, rtol=1e-4, atol=1e-6
        )
    )
    same(s_n["total_loss"], s_1["total_loss"])
    for a, b in zip(
        jax.tree_util.tree_leaves(w_n),
        jax.tree_util.tree_leaves(w_1),
    ):
        same(a, b)


def test_gradient_is_the_mean_over_shards_not_the_sum():
    """One full-batch step has ONE true gradient, whatever the mesh:
    ``grad_gnorm`` must not scale with the shard count. (jax types a
    replicated param inside ``shard_map`` as invarying and sums its
    gradient over shards itself; the nest differentiates a per-shard
    view so the explicit pmean stays the only reduction.)"""
    from ray_tpu.algorithms.ppo.ppo import PPOJaxPolicy

    norms = {}
    for n_dev in (1, 4):
        pol = PPOJaxPolicy(
            gym.spaces.Box(-1.0, 1.0, (8,), np.float32),
            gym.spaces.Discrete(4),
            {
                "_mesh": sl.get_mesh(devices=jax.devices()[:n_dev]),
                "model": {"fcnet_hiddens": [16]},
                "train_batch_size": 32,
                "sgd_minibatch_size": 32,
                "num_sgd_iter": 1,
                "lr": 1e-3,
                "seed": 0,
            },
        )
        norms[n_dev] = pol.learn_on_batch(_ppo_batch())["grad_gnorm"]
    np.testing.assert_allclose(norms[4], norms[1], rtol=1e-5)


def test_learn_timers_and_train_results(tmp_path):
    """Per-stage learner timers ride the policy and train() results;
    save_checkpoint survives (and is atomic — temp names never leak)."""
    import os

    from ray_tpu.algorithms.ppo import PPOConfig

    algo = (
        PPOConfig()
        .environment("CartPole-v1")
        .rollouts(num_rollout_workers=0, rollout_fragment_length=64)
        .training(
            train_batch_size=128,
            sgd_minibatch_size=64,
            num_sgd_iter=2,
            lr=3e-4,
        )
        .debugging(seed=0)
        .build()
    )
    result = algo.train()
    timers = result["info"]["timers"]["default_policy"]
    assert timers["learn_transfer_s"] >= 0.0
    assert timers["learn_step_s"] > 0.0
    assert timers["learn_compile_s"] > 0.0  # first step compiled
    assert timers["learn_recompiles"] == 1.0
    result = algo.train()
    timers = result["info"]["timers"]["default_policy"]
    assert timers["learn_compile_s"] == 0.0  # steady state: cache hit
    assert timers["learn_recompiles"] == 0.0
    # the same stages are exported as metrics series
    from ray_tpu.utils.metrics import get_metric

    for name in (
        "ray_tpu_learner_step_seconds",
        "ray_tpu_learner_transfer_seconds",
        "ray_tpu_learner_total_seconds",
    ):
        m = get_metric(name)
        assert m is not None and m.series(), name
    ckpt = str(tmp_path / "ckpt")
    os.makedirs(ckpt, exist_ok=True)
    algo.save_checkpoint(ckpt)
    names = sorted(os.listdir(ckpt))
    assert "algorithm_state.pkl" in names
    assert "rllib_checkpoint.json" in names
    assert not [n for n in names if ".tmp." in n]
    algo.cleanup()
