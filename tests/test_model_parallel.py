"""2-D (data x model) partitioned policies (docs/sharding.md "2-D mesh
& param partitioning", ROADMAP item 4):

- ordered name-pattern rules -> per-leaf PartitionSpecs (first match
  wins, default replicate, mesh-absent axes prune, with_logical_rules
  escape hatch);
- optimizer/aux state inherits param placement by path-suffix+shape
  matching (adam moments split, counts replicate, target nets split);
- fixed-seed transformer PPO + DQN learn steps at model_parallel=1 are
  BIT-identical to the replicated legacy path on a 1-shard mesh (the
  container parity rule); at model_parallel=2 the Megatron-boundary
  math agrees with the replicated program to float-assoc tolerance;
- per-leaf specs flow through the superstep scan + donation with zero
  recompiles across chain lengths (compile_stats-asserted);
- checkpoints written under one mesh geometry restore under another
  (8x1 -> 4x2) with bitwise-equal gathered params, re-placed per the
  active rules;
- model-sharded params gate the serve plane's fused forward
  (supports_batched_serve) and fall back to the per-request path;
- the ragged-leading-dim replication fallback and per-shard param
  bytes are observable (telemetry counter + gauge).
"""

import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec as P

from ray_tpu import sharding as sharding_lib
from ray_tpu.data.sample_batch import SampleBatch as SB

MODEL = {
    "use_transformer": True,
    "transformer_dim": 32,
    "transformer_num_layers": 2,
    "transformer_num_heads": 2,
    "transformer_seq_len": 4,
    "transformer_ff_dim": 64,
}


def _mesh2d(d_batch, d_model):
    return sharding_lib.get_mesh(
        devices=jax.devices()[: d_batch * d_model],
        axis_shapes=[("batch", d_batch), ("model", d_model)],
    )


def _mesh1d(n=1):
    return sharding_lib.get_mesh(devices=jax.devices()[:n])


def _ppo_policy(mesh, **over):
    import gymnasium as gym

    from ray_tpu.algorithms.ppo.ppo import PPOJaxPolicy

    cfg = {
        "train_batch_size": 64,
        "sgd_minibatch_size": 32,
        "num_sgd_iter": 2,
        "lr": 1e-3,
        "seed": 0,
        "model": dict(MODEL),
        "_mesh": mesh,
    }
    cfg.update(over)
    return PPOJaxPolicy(
        gym.spaces.Box(-1, 1, (8,), np.float32),
        gym.spaces.Discrete(4),
        cfg,
    )


def _ppo_batch(rng, n=64):
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


def _leaves(tree):
    return jax.tree_util.tree_leaves(jax.device_get(tree))


def _bitwise(a, b):
    la, lb = _leaves(a), _leaves(b)
    assert len(la) == len(lb)
    return all(np.array_equal(x, y) for x, y in zip(la, lb))


# -- rule grammar ------------------------------------------------------


def test_param_pspecs_rules_ordered_default_and_pruning():
    mesh = _mesh2d(4, 2)
    tree = {
        "layer_0": {
            "attn": {
                "wq": np.zeros((8, 4, 2), np.float32),
                "wo": np.zeros((4, 2, 8), np.float32),
                "bo": np.zeros((8,), np.float32),
            },
            "mlp": {
                "w_up": np.zeros((8, 16), np.float32),
                "w_down": np.zeros((16, 8), np.float32),
            },
            "ln1": {"scale": np.ones(8, np.float32)},
        },
        "logits": {"kernel": np.zeros((8, 3), np.float32)},
    }
    ps = sharding_lib.param_pspecs(
        tree, mesh, sharding_lib.default_partition_rules()
    )
    a = ps["layer_0"]["attn"]
    assert a["wq"] == P(None, "model")
    assert a["wo"] == P("model")
    assert a["bo"] == P()  # reduced-output bias replicates
    assert ps["layer_0"]["mlp"]["w_up"] == P(None, "model")
    assert ps["layer_0"]["mlp"]["w_down"] == P("model")
    assert ps["layer_0"]["ln1"]["scale"] == P()  # default replicate
    assert ps["logits"]["kernel"] == P()

    # ordered: FIRST match wins
    ordered = (
        (r"attn/wq$", P()),
        (r"attn/.*", P(None, "model")),
    )
    ps2 = sharding_lib.param_pspecs(tree, mesh, ordered)
    assert ps2["layer_0"]["attn"]["wq"] == P()
    assert ps2["layer_0"]["attn"]["wo"] == P(None, "model")

    # axes absent from the mesh prune to replication
    ps1d = sharding_lib.param_pspecs(
        tree, _mesh1d(), sharding_lib.default_partition_rules()
    )
    assert all(
        s == P()
        for s in jax.tree_util.tree_leaves(
            ps1d, is_leaf=lambda x: isinstance(x, P)
        )
    )

    # a rule whose named axis can't fit the leaf rank replicates
    # instead of silently mis-placing
    bad = ((r"ln1/scale$", P(None, "model")),)
    ps3 = sharding_lib.param_pspecs(tree, mesh, bad)
    assert ps3["layer_0"]["ln1"]["scale"] == P()


def test_with_logical_rules_escape_hatch():
    from ray_tpu.models.transformer import TransformerPolicyNet

    rules = ((r"mlp/w_up$", P(None, "model")),)
    cls = TransformerPolicyNet.with_logical_rules(rules)
    net = cls(num_outputs=4, d_model=16, num_layers=1, num_heads=2,
              seq_len=2)
    assert net.partition_rules() == rules
    # policy-level: only the escape-hatch rule shards anything
    mesh = _mesh2d(1, 2)
    policy = _ppo_policy(
        mesh,
        model={**MODEL, "partition_rules": list(rules)},
    )
    ps = policy.param_pspecs
    assert ps["layer_0"]["mlp"]["w_up"] == P(None, "model")
    assert ps["layer_0"]["attn"]["wq"] == P()


def test_state_pspecs_suffix_matching():
    mesh = _mesh2d(1, 2)
    policy = _ppo_policy(mesh)
    o_ps = policy._opt_pspecs
    flat, _ = jax.tree_util.tree_flatten_with_path(o_ps)
    by_path = {
        "/".join(str(k) for k in path): spec for path, spec in flat
    }
    # adam mu inherits the kernel's split; count replicates
    mu_wup = [v for k, v in by_path.items() if "mu" in k and "w_up" in k]
    assert mu_wup and all(s == P(None, "model") for s in mu_wup)
    counts = [v for k, v in by_path.items() if "count" in k]
    assert counts and all(s == P() for s in counts)


# -- learn-path parity -------------------------------------------------


@pytest.mark.slow  # ~11 s on this container; moved out of
# tier-1 with PR 12 (budget rule: suite at ~892 s vs the 870 s cap)
@pytest.mark.slow  # ~11 s on this container; moved out of
# tier-1 with PR 12 (budget rule: suite at ~892 s vs the 870 s cap)
def test_ppo_transformer_mp1_bitwise_vs_replicated():
    rng = np.random.default_rng(0)
    batch = _ppo_batch(rng)
    leg = _ppo_policy(_mesh1d(1))
    mp1 = _ppo_policy(_mesh2d(1, 1))
    assert leg.param_pspecs is None
    assert mp1.param_pspecs is not None  # per-leaf specs engaged
    r_leg = leg.learn_on_batch(SB(dict(batch)))
    r_mp1 = mp1.learn_on_batch(SB(dict(batch)))
    assert _bitwise(leg.params, mp1.params)
    assert _bitwise(leg.opt_state, mp1.opt_state)
    assert r_leg["total_loss"] == r_mp1["total_loss"]


def test_dqn_transformer_mp1_bitwise_vs_replicated():
    import gymnasium as gym

    from ray_tpu.algorithms.dqn.dqn import DQNJaxPolicy

    def make(mesh):
        return DQNJaxPolicy(
            gym.spaces.Box(-1, 1, (8,), np.float32),
            gym.spaces.Discrete(4),
            {
                "train_batch_size": 32,
                "lr": 1e-3,
                "seed": 0,
                "gamma": 0.97,
                "model": dict(MODEL),
                "_mesh": mesh,
            },
        )

    rng = np.random.default_rng(1)
    n = 32
    batch = {
        SB.OBS: rng.standard_normal((n, 8)).astype(np.float32),
        SB.NEXT_OBS: rng.standard_normal((n, 8)).astype(np.float32),
        SB.ACTIONS: rng.integers(0, 4, n).astype(np.int64),
        SB.REWARDS: rng.standard_normal(n).astype(np.float32),
        SB.TERMINATEDS: (rng.random(n) < 0.1).astype(np.float32),
    }
    leg, mp1 = make(_mesh1d(1)), make(_mesh2d(1, 1))
    assert mp1.param_pspecs is not None
    # aux target nets inherit the params' per-leaf placement
    a_ps = mp1._carry_pspecs()[2]
    assert (
        a_ps["target_params"]["layer_0"]["attn"]["wq"]
        == P(None, "model")
    )
    leg.learn_on_batch(SB(dict(batch)))
    mp1.learn_on_batch(SB(dict(batch)))
    assert _bitwise(leg.params, mp1.params)
    assert _bitwise(leg.aux_state, mp1.aux_state)


@pytest.mark.slow  # ~12 s; moved out of tier-1 by the PR-1 budget
# rule — tier-1 keeps the mp=1 bitwise-vs-replicated pin
# (test_dqn_transformer_mp1_bitwise_vs_replicated) + the pspec units
def test_mp2_learn_matches_replicated_math():
    """2-way tensor parallelism: kernels actually split, the Megatron
    boundary collectives reproduce the replicated program's math
    (float-assoc tolerance — cross-shard reduction order differs;
    bitwise holds only at M=1, like every multi-shard contract in
    this repo)."""
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    rng = np.random.default_rng(2)
    batch = _ppo_batch(rng)
    leg = _ppo_policy(_mesh1d(1))
    mp2 = _ppo_policy(_mesh2d(1, 2))
    assert mp2.is_model_sharded
    wq = mp2.params["layer_0"]["attn"]["wq"]
    assert wq.addressable_shards[0].data.shape == (32, 1, 16)
    r_leg = leg.learn_on_batch(SB(dict(batch)))
    r_mp2 = mp2.learn_on_batch(SB(dict(batch)))
    assert np.isclose(
        r_leg["total_loss"], r_mp2["total_loss"], atol=1e-5
    )
    for a, b in zip(_leaves(leg.params), _leaves(mp2.params)):
        np.testing.assert_allclose(a, b, atol=5e-3)
    # per-shard bytes: the kernel-heavy tree sits near total/2
    total = sharding_lib.tree_nbytes(mp2.params)
    per_shard = sharding_lib.tree_shard_nbytes(
        mp2.params, mp2.param_pspecs, mp2.mesh
    )
    assert per_shard < total
    sharded_frac = 1.0 - (2 * per_shard - total) / total
    assert sharded_frac > 0.5  # most bytes actually split


# -- superstep ---------------------------------------------------------


@pytest.mark.slow  # ~14 s on this container; moved out of
# tier-1 with PR 12 (budget rule: suite at ~892 s vs the 870 s cap)
@pytest.mark.slow  # ~14 s on this container; moved out of
# tier-1 with PR 12 (budget rule: suite at ~892 s vs the 870 s cap)
def test_superstep_partitioned_zero_recompile_and_parity():
    from ray_tpu.policy.jax_policy import JaxPolicy  # noqa: F401

    rng = np.random.default_rng(3)
    host = _ppo_batch(rng)

    def stacked(k):
        return {
            c: np.repeat(np.asarray(v)[None], k, axis=0)
            for c, v in host.items()
        }

    # parity on the 1-shard 2-D mesh: fused k=2 bitwise vs 2
    # sequential deferred learn calls through the SAME per-leaf specs
    a = _ppo_policy(_mesh2d(1, 1))
    b = _ppo_policy(_mesh2d(1, 1))
    prep, bsize = a.prepare_batch(dict(host))
    dev = jax.device_put(prep, a.batch_shardings(prep))
    a.learn_superstep(2, bsize, stacked=stacked(3), k_max=3)
    for _ in range(2):
        b.learn_on_device_batch(dict(dev), bsize, defer_stats=True)
    assert _bitwise(a.params, b.params)
    assert _bitwise(a.opt_state, b.opt_state)

    # zero recompiles across k <= K with split params on a 2x2 mesh
    if len(jax.devices()) >= 4:
        p = _ppo_policy(_mesh2d(2, 2))
        assert p.supports_superstep
        for k in (3, 1, 2):
            p.learn_superstep(k, bsize, stacked=stacked(3), k_max=3)
        fn = next(iter(p._superstep_fns.values()))
        assert fn.traces == 1 and fn.recompiles == 0
        assert all(
            np.isfinite(x).all() for x in _leaves(p.params)
        )


# -- checkpoint reshard ------------------------------------------------


@pytest.mark.slow  # ~4 s; fails at seed under jax 0.9.0, passes since
# PR 21 — moved out of tier-1 by that PR's budget rule (the newly
# passing tests compile and run where they used to fail at trace
# time; only newly passing ones may leave); tier-1 keeps the
# mp=1 bitwise-vs-replicated pin on the same learn path
def test_checkpoint_reshard_roundtrip_across_geometries():
    rng = np.random.default_rng(4)
    batch = _ppo_batch(rng)
    a = _ppo_policy(_mesh2d(8, 1))
    a.learn_on_batch(SB(dict(batch)))
    state = a.get_state()
    want = a.get_weights()

    b = _ppo_policy(_mesh2d(4, 2))
    b.set_state(state)
    got = b.get_weights()
    assert _bitwise(want, got)  # gather-on-save stays the format
    # ...and the restore actually RE-PLACED per the active rules
    wq = b.params["layer_0"]["attn"]["wq"]
    assert wq.addressable_shards[0].data.shape == (32, 1, 16)
    assert b._params_match_active_rules()
    # opt state re-placed too, values preserved
    assert _bitwise(a.opt_state, b.opt_state)

    # back onto the original geometry: still bitwise
    c = _ppo_policy(_mesh2d(8, 1))
    c.set_state(b.get_state())
    assert _bitwise(want, c.get_weights())


# -- serve gating ------------------------------------------------------


def test_serve_gates_model_sharded_params():
    from ray_tpu.serve.policy_server import BatchedPolicyServer

    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    rng = np.random.default_rng(5)
    obs = rng.standard_normal((6, 8)).astype(np.float32)

    policy = _ppo_policy(_mesh2d(1, 2))
    assert policy.is_model_sharded
    assert policy.supports_batched_serve  # placement matches rules
    srv = BatchedPolicyServer(policy, max_batch_size=4, explore=False)
    try:
        assert srv.fused
        acts, _ = srv.compute_actions(obs)
        ref = _ppo_policy(_mesh2d(1, 2))
        ref_acts, _, _ = ref.compute_actions(obs, explore=False)
        assert np.array_equal(acts, ref_acts)
    finally:
        srv.stop()

    # params NOT placed per the rules (raw replicated device_put, e.g.
    # a serve mesh that doesn't match the training rules): the fused
    # forward gates off and the SAME queue serves per-request
    policy2 = _ppo_policy(_mesh2d(1, 2))
    policy2.params = jax.device_put(
        jax.device_get(policy2.params),
        sharding_lib.replicated(policy2.mesh),
    )
    assert not policy2.supports_batched_serve
    srv2 = BatchedPolicyServer(
        policy2, max_batch_size=4, explore=False
    )
    try:
        assert not srv2.fused
        acts2, _ = srv2.compute_actions(obs)
        assert acts2.shape == (6,)
    finally:
        srv2.stop()


# -- observability -----------------------------------------------------


def test_ragged_fallback_counter_and_params_bytes_gauge():
    from ray_tpu.telemetry import metrics as tm

    mesh = sharding_lib.get_mesh(devices=jax.devices()[:8])
    c = tm.counter(tm.SHARDING_FALLBACK_TOTAL)
    before = dict(c.series())
    sharding_lib.leaf_sharding(np.zeros((7, 3), np.float32), mesh)
    after = dict(c.series())
    assert after.get((), 0.0) == before.get((), 0.0) + 1.0
    # divisible leading dims and scalars don't count
    sharding_lib.leaf_sharding(np.zeros((8, 3), np.float32), mesh)
    sharding_lib.leaf_sharding(np.float32(1.0), mesh)
    assert dict(c.series()).get((), 0.0) == after.get((), 0.0)

    if len(jax.devices()) >= 2:
        policy = _ppo_policy(_mesh2d(1, 2))
        g = tm.gauge(tm.PARAMS_BYTES)
        vals = {
            dict(k).get("placement"): v
            for k, v in g.series()
            if dict(k).get("policy") == "PPOJaxPolicy"
        }
        assert vals["global"] == sharding_lib.tree_nbytes(
            policy.params
        )
        assert 0 < vals["per_shard"] < vals["global"]
