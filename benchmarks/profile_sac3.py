"""Micro-timing of the fused-SAC round's device interactions on the
TPU: device_put of the stacked batch, program issue (deferred
stats), the blocking stats fetch, and device_get of the actor tree
(per-leaf) vs a single flattened vector — isolating per-call RTT from
bandwidth so the fixes target the right one.

Run: python benchmarks/profile_sac3.py
"""

import time

import gymnasium as gym
import jax
import numpy as np


def med(fn, n=7):
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)) * 1e3


def main():
    from ray_tpu.algorithms.sac.sac import SACJaxPolicy

    obs_sp = gym.spaces.Box(-np.inf, np.inf, (17,), np.float64)
    act_sp = gym.spaces.Box(-1.0, 1.0, (6,), np.float32)
    pol = SACJaxPolicy(
        obs_sp, act_sp, {"seed": 0, "gamma": 0.99, "tau": 0.005}
    )
    rng = np.random.default_rng(0)
    k, bs = 32, 256
    stacked64 = {
        "obs": rng.standard_normal((k, bs, 17)),
        "new_obs": rng.standard_normal((k, bs, 17)),
        "actions": rng.uniform(-1, 1, (k, bs, 6)).astype(np.float32),
        "rewards": rng.standard_normal((k, bs)).astype(np.float32),
        "terminateds": np.zeros((k, bs), np.float32),
    }
    stacked32 = {
        kk: (
            v.astype(np.float32)
            if v.dtype == np.float64
            else v
        )
        for kk, v in stacked64.items()
    }
    b64 = sum(v.nbytes for v in stacked64.values())
    b32 = sum(v.nbytes for v in stacked32.values())

    import jax.sharding as jshard
    from jax.sharding import PartitionSpec as P

    sharding = jshard.NamedSharding(pol.mesh, P(None, "data"))

    def put64():
        d = jax.device_put(stacked64, sharding)
        jax.block_until_ready(d)
        return d

    def put32():
        d = jax.device_put(stacked32, sharding)
        jax.block_until_ready(d)
        return d

    print(f"device_put f64 stacked ({b64/1e6:.1f} MB): {med(put64):7.1f} ms")
    print(f"device_put f32 stacked ({b32/1e6:.1f} MB): {med(put32):7.1f} ms")

    # fused program issue vs block
    from ray_tpu.data.sample_batch import SampleBatch as SB

    tree = {
        SB.OBS: stacked32["obs"],
        SB.NEXT_OBS: stacked32["new_obs"],
        SB.ACTIONS: stacked32["actions"],
        SB.REWARDS: stacked32["rewards"],
        SB.TERMINATEDS: stacked32["terminateds"],
    }
    pol.learn_on_stacked_batch(tree, k, bs)  # compile

    def issue_only():
        pol.learn_on_stacked_batch(tree, k, bs, defer_stats=True)

    def issue_and_block():
        s = pol.learn_on_stacked_batch(tree, k, bs, defer_stats=True)
        jax.device_get(s)

    print(f"fused k=32 issue (defer):      {med(issue_only):7.1f} ms")
    print(f"fused k=32 issue+block stats:  {med(issue_and_block):7.1f} ms")

    # weight fetch: per-leaf tree vs one flat vector
    def get_tree():
        jax.device_get(pol.params["actor"])

    leaves = jax.tree_util.tree_leaves(pol.params["actor"])
    n_leaves = len(leaves)
    sizes = [int(np.prod(x.shape)) for x in leaves]

    @jax.jit
    def flat_actor(p):
        import jax.numpy as jnp

        return jnp.concatenate(
            [x.reshape(-1) for x in jax.tree_util.tree_leaves(p)]
        )

    flat_actor(pol.params["actor"])  # compile

    def get_flat():
        jax.device_get(flat_actor(pol.params["actor"]))

    tot = sum(sizes) * 4
    print(
        f"device_get actor tree ({n_leaves} leaves, {tot/1e3:.0f} KB):"
        f" {med(get_tree):7.1f} ms"
    )
    print(f"device_get flat actor (1 leaf):{med(get_flat):8.1f} ms")


if __name__ == "__main__":
    main()
