"""Elastic-joiner AOT warmup of the learn program (ROADMAP item 2
leftover, wired at the ``JaxPolicy._build_learn_fn`` call sites):

- the FIRST policy to learn with ``aot_cache_dir`` set compiles ahead
  of time once (``aot_source == "aot_live"``) and seeds the
  fleet-shared cache;
- a freshly built second policy (the "joiner") warms its learn
  program from the cache with ZERO fresh compiles
  (``aot_source == "aot_cache"``, ``traces == 0``);
- the restored executable is the same program: fixed-seed params
  after one learn step are BITWISE identical across the seeder, the
  joiner, and a plain live-jit policy (1-shard mesh — the parity
  geometry);
- without ``aot_cache_dir`` the wiring is inert (no aot path, no
  cache directory touched).
"""

import numpy as np

import jax

from ray_tpu import sharding as sharding_lib
from ray_tpu.data.sample_batch import SampleBatch as SB

BS = 16


def _policy(aot_dir=None, seed=0):
    import gymnasium as gym

    from ray_tpu.algorithms.ppo.ppo import PPOJaxPolicy

    cfg = {
        "train_batch_size": BS,
        "sgd_minibatch_size": BS,
        "num_sgd_iter": 1,
        "lr": 1e-3,
        "seed": seed,
        # bitwise parity needs the 1-shard mesh (per-shard matmul
        # shapes differ on the 8-way virtual mesh)
        "_mesh": sharding_lib.get_mesh(devices=jax.devices()[:1]),
    }
    if aot_dir is not None:
        cfg["aot_cache_dir"] = str(aot_dir)
    return PPOJaxPolicy(
        gym.spaces.Box(-1, 1, (8,), np.float32),
        gym.spaces.Discrete(4),
        cfg,
    )


def _batch(n=BS):
    rng = np.random.default_rng(7)
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


def _params(policy):
    return [
        np.asarray(x)
        for x in jax.tree_util.tree_leaves(
            jax.device_get(policy.params)
        )
    ]


def _learn_fn(policy):
    fns = list(policy._learn_fns.values())
    assert len(fns) == 1
    return fns[0]


def test_joiner_warms_with_zero_fresh_compiles(tmp_path):
    cache_dir = tmp_path / "aot"
    batch = _batch()

    # the seeder: compiles ahead of time ONCE and populates the cache
    seeder = _policy(cache_dir)
    seeder.learn_on_batch(dict(batch))
    fn1 = _learn_fn(seeder)
    assert fn1.aot_source == "aot_live"
    assert fn1.traces == 1  # the one AOT compile, honestly counted
    cache1 = seeder._learn_aot_cache()
    cache1.flush()
    assert cache1.stats()["saves"] == 1

    # the joiner: fresh policy, same config/topology — learn program
    # restores from disk, ZERO fresh compiles
    joiner = _policy(cache_dir)
    joiner.learn_on_batch(dict(batch))
    fn2 = _learn_fn(joiner)
    assert fn2.aot_source == "aot_cache"
    assert fn2.traces == 0, "joiner paid an XLA compile"
    assert joiner._learn_aot_cache().stats()["hits"] == 1

    # live-jit reference: no cache configured
    live = _policy(None)
    live.learn_on_batch(dict(batch))
    assert _learn_fn(live).aot_source is None

    # same program, bitwise: seeder ≡ joiner ≡ live after one step
    p1, p2, p3 = _params(seeder), _params(joiner), _params(live)
    for a, b in zip(p1, p2):
        assert np.array_equal(a, b)
    for a, b in zip(p1, p3):
        assert np.array_equal(a, b)


def test_unconfigured_policy_never_touches_aot(tmp_path):
    p = _policy(None)
    p.learn_on_batch(dict(_batch()))
    fn = _learn_fn(p)
    assert fn.aot_source is None
    assert p._learn_aot_cache() is None
