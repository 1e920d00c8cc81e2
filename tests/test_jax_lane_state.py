"""Policy state on the device rollout lane: a model's per-stream state
rides the rollout carry, is reset with the episode, and the state at
the start of every unroll goes to the learner. The LSTM is the cheap
proof (against the in-process sampler); the sequence model on the
token env is the lane's real user.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from ray_tpu.algorithms.ppo.ppo import PPOConfig, PPOJaxPolicy
from ray_tpu.env.jax_control import CartPoleJax
from ray_tpu.evaluation.rollout_worker import RolloutWorker
from ray_tpu.execution.jax_rollout import (
    JaxRolloutEngine,
    supports_jax_rollout_lane,
)
from ray_tpu.telemetry import metrics as telemetry_metrics


def _lstm_cfg(**over):
    from ray_tpu import sharding as sharding_lib

    cfg = PPOConfig().to_dict()
    cfg.update(
        seed=5, num_workers=0, num_envs_per_worker=8,
        rollout_fragment_length=8, train_batch_size=64,
        sgd_minibatch_size=32, num_sgd_iter=2, lr=3e-4,
        model={"use_lstm": True, "lstm_cell_size": 16,
               "fcnet_hiddens": [16], "max_seq_len": 4},
        _mesh=sharding_lib.get_mesh(devices=jax.devices()[:1]),
    )
    cfg["lambda"] = 0.95
    cfg.update(over)
    return cfg


def _policy(env, cfg):
    return PPOJaxPolicy(env.observation_space, env.action_space, cfg)


def test_lstm_policy_lowers_into_the_device_lane():
    env = CartPoleJax({})
    pol = _policy(env, _lstm_cfg())
    assert pol.model.is_recurrent and pol.supports_jax_rollout
    assert supports_jax_rollout_lane(pol, env) == (True, "")
    cfg = _lstm_cfg()
    cfg["model"] = dict(cfg["model"], lstm_use_prev_action=True)
    fed = _policy(env, cfg)
    ok, reason = supports_jax_rollout_lane(fed, env)
    assert not ok and "previous" in reason and "recurrent" not in reason


def test_fragment_must_be_whole_unrolls():
    env = CartPoleJax({})
    cfg = _lstm_cfg()
    cfg["model"] = dict(cfg["model"], max_seq_len=3)
    with pytest.raises(ValueError, match="max_seq_len"):
        JaxRolloutEngine(_policy(env, cfg), env, 8, 8, seed=5)


def test_lstm_lane_parity_with_the_in_process_sampler():
    """The same seed through the in-process sampler and through the
    device lane's state carry: the same trajectories, values and GAE
    columns, and the state at the start of each 4-step unroll is the
    state the sampler recorded there."""
    cfg = _lstm_cfg()
    rw = RolloutWorker(
        env_creator=lambda c: CartPoleJax(dict(c)),
        policy_cls=PPOJaxPolicy, config=cfg, worker_index=0, num_workers=0,
    )
    host = rw.sampler.sample()
    env = CartPoleJax({})
    pol = _policy(env, dict(cfg))
    eng = JaxRolloutEngine(pol, env, 8, 8, seed=5, standardize_advantages=False)
    assert eng.stateful and eng.unroll == 4
    dev = jax.device_get(eng.rollout()[0])
    order = np.argsort(np.asarray(host["agent_index"]), kind="stable")

    def col(name):
        return np.asarray(host[name])[order]

    assert host.count == 64 == len(dev["obs"])
    for name in ("obs", "actions", "rewards", "dones", "truncateds", "t"):
        h, d = col(name), np.asarray(dev[name])
        assert np.array_equal(h.astype(d.dtype), d), name
    for name in ("action_logp", "action_dist_inputs", "vf_preds"):
        np.testing.assert_allclose(col(name), dev[name], atol=1e-5, err_msg=name)
    for name in ("advantages", "value_targets"):
        np.testing.assert_allclose(col(name), dev[name], atol=1e-4, err_msg=name)
    # rows that open an episode
    np.testing.assert_array_equal(dev["resets"], (col("t") == 0).astype(np.float32))
    # the state handed to the learner: one row per unroll, env-major
    for k in range(2):
        starts = dev[f"__chunk__state_in_{k}"]
        assert starts.shape == (16, 16)
        np.testing.assert_allclose(
            starts, col(f"state_in_{k}")[::4], atol=1e-5, err_msg=f"state {k}"
        )
    # the carry holds the advanced state, one row per env
    assert [tuple(s.shape) for s in eng._carry["state"]] == [(8, 16), (8, 16)]


def test_lstm_fused_superstep_matches_unfused_dispatches():
    def run(fused):
        env = CartPoleJax({})
        pol = _policy(env, _lstm_cfg())
        eng = JaxRolloutEngine(pol, env, 8, 8, seed=5)
        for _ in range(2):
            if fused:
                infos, carry, metrics, _ = pol.learn_rollout_superstep(
                    1, 64, eng.superstep_feed(), k_max=1
                )
                eng.advance(carry, metrics)
            else:
                batch, bsize = eng.rollout()
                pol.learn_on_device_batch(eng.learn_batch(batch), bsize)
        return pol.get_weights(), jax.device_get(eng._carry["state"])

    (wa, sa), (wb, sb) = run(True), run(False)
    for a, b in zip(jax.tree_util.tree_leaves((wa, sa)),
                    jax.tree_util.tree_leaves((wb, sb))):
        np.testing.assert_allclose(a, b, atol=1e-6)


LM = {
    "hidden_size": 32, "num_hidden_layers": 4, "full_attention_interval": 4,
    "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 16,
    "partial_rotary_factor": 0.25, "rope_theta": 10000000,
    "rms_norm_eps": 1e-6, "max_position_embeddings": 24,
    "linear_num_key_heads": 1, "linear_num_value_heads": 2,
    "linear_key_head_dim": 8, "linear_value_head_dim": 8,
    "linear_conv_kernel_dim": 4,
    "num_experts": 2, "router_outputs": 8, "experts_held": [0, 2],
    "num_experts_per_tok": 3, "norm_topk_prob": True,
    "moe_intermediate_size": 16, "shared_expert_intermediate_size": 16,
}


def test_sequence_model_trains_on_the_token_env_through_the_fused_lane():
    """PPO, ``env_backend: jax``, 0 workers: tokens are generated on
    the lane from carried state (episodes of 24 against fragments of
    16: resets fall inside fragments) and trained from the stored
    start states. The learn form reproduces the rollout's logits, so
    the first epoch's KL and policy loss are rounding."""
    from ray_tpu.algorithms.registry import get_algorithm_class

    before = telemetry_metrics.expert_load_totals().get("updates", 0.0)
    chunked_before = dict(telemetry_metrics.deltanet_chunked_lowerings())
    algo = get_algorithm_class("PPO")(config={
        "env": "TokenStreamJax-v0",
        "env_config": {"vocab_size": 32, "episode_length": 24, "phase_stride": 5},
        "env_backend": "jax", "num_workers": 0, "num_envs_per_worker": 8,
        "rollout_fragment_length": 16, "train_batch_size": 128,
        "sgd_minibatch_size": 128, "num_sgd_iter": 1, "superstep": 1,
        "gamma": 1.0, "lambda": 0.95, "lr": 1e-4, "grad_clip": 1.0,
        "kl_coeff": 0.0, "entropy_coeff": 0.0, "seed": 3,
        "model": {"use_sequence_lm": True, "sequence_lm": LM, "max_seq_len": 16,
                  "dtype": "float32"},
    })
    try:
        policy = algo.get_policy()
        assert policy.model.is_recurrent and policy._unroll_T == 16
        for _ in range(3):
            info = algo.train()["info"]["learner"]["default_policy"]
            assert np.isfinite(info["total_loss"])
            assert abs(info["kl"]) < 1e-5 and abs(info["policy_loss"]) < 1e-4
            # a shard's tokens x 3 slots x 4 layers: held + absent add
            # up (the counts are means over the data shards)
            held = info["moe_tokens_per_held_expert"] * 2 * 4
            tokens = 128 // policy.n_shards
            assert held + info["moe_slots_on_absent_experts"] == pytest.approx(
                tokens * 3 * 4)
            assert info["moe_max_tokens_per_held_expert"] >= info[
                "moe_tokens_per_held_expert"]
        eng = algo._jax_rollout_engine
        position = np.asarray(eng._carry["state"][-1])
        assert position.min() >= 0 and position.max() < 24
        assert algo._counters["num_env_steps_trained"] == 3 * 128
        totals = telemetry_metrics.expert_load_totals()
        assert totals["updates"] - before == 3 and totals["max"] >= totals["mean"]
        # the learn form's DeltaNet layers (a run of three, traced once), a
        # decay a head, on the path this platform chose (here the CPU:
        # XLA's text)
        chunked = telemetry_metrics.deltanet_chunked_lowerings()
        took = {k: v - chunked_before.get(k, 0) for k, v in chunked.items()
                if v != chunked_before.get(k, 0)}
        path = "kernel" if jax.default_backend() == "tpu" else "xla"
        assert set(took) == {path + "/head"}
    finally:
        algo.cleanup()


def test_token_env_lane_hands_back_the_tokens_it_generated():
    """An env whose actions are its product (``report_actions``) gets
    every step's actions back with the episode metrics, on the lane's
    one drain; an env without the flag changes nothing."""
    from ray_tpu.env.jax_tokens import TokenStreamJax

    cfg = _lstm_cfg()
    cfg["model"] = {"fcnet_hiddens": [16]}
    env = TokenStreamJax({"vocab_size": 16, "episode_length": 6, "phase_stride": 1})
    eng = JaxRolloutEngine(_policy(env, cfg), env, 8, 8, seed=5)
    assert eng.last_actions is None
    batch, _ = eng.rollout()
    tokens = np.asarray(eng.last_actions)  # (T, N)
    assert tokens.shape == (8, 8) and tokens.dtype == np.int32
    np.testing.assert_array_equal(
        tokens.T.reshape(-1), np.asarray(batch["actions"]))  # rows are env-major
    # the observation is the token just emitted, but for the seeded
    # first token of an episode
    obs = np.asarray(batch["obs"]).reshape(8, 8)
    inside = np.asarray(batch["t"]).reshape(8, 8)[:, 1:] > 0
    np.testing.assert_array_equal(obs[:, 1:][inside], tokens.T[:, :-1][inside])

    plain = CartPoleJax({})
    other = JaxRolloutEngine(_policy(plain, _lstm_cfg()), plain, 8, 8, seed=5)
    other.rollout()
    assert other.last_actions is None


def test_model_counts_travel_with_the_loss_and_nothing_stays_on_the_policy():
    """A model that counts in its learn-form forward (expert load)
    hands the counts to the loss that asked for them; a forward
    outside such a loss leaves no tracer on the policy."""
    from ray_tpu.env.jax_tokens import TokenStreamJax

    env = TokenStreamJax({"vocab_size": 32, "episode_length": 24})
    cfg = _lstm_cfg()
    cfg["model"] = {"use_sequence_lm": True, "sequence_lm": LM,
                    "max_seq_len": 4, "dtype": "float32"}
    pol = _policy(env, cfg)
    assert pol.model.train_stats
    batch = {"obs": np.arange(8, dtype=np.int32).reshape(8, 1) % 32}
    for k, leaf in enumerate(pol.model.initial_state(2)):  # two unrolls of 4
        batch[f"__chunk__state_in_{k}"] = leaf
    attrs = set(pol.__dict__)

    def forward(params, batch):
        counts = {}
        with_counts = pol.model_forward_train(params, batch, stats_out=counts)
        without = pol.model_forward_train(params, batch)
        return with_counts[0], without[0], counts

    for _ in range(2):  # a second trace would meet the first one's leftovers
        a, b, counts = jax.jit(forward)(pol.params, batch)
        jax.clear_caches()
    np.testing.assert_allclose(a, b, atol=1e-6)
    assert set(counts) == {
        "moe_tokens_per_held_expert", "moe_max_tokens_per_held_expert",
        "moe_slots_on_absent_experts", "moe_rows_computed_share",
        "attn_key_blocks_skipped_share", "moe_decode_held_experts_touched_share",
        "attn_decode_key_blocks_skipped_share",
    }
    assert set(pol.__dict__) == attrs


# -- the lane's contract around an episode's end --------------------------

def _contract_engine():
    """A small sequence model on the standalone lane: 8 streams x 16
    steps in two unrolls of 8, episodes of 24 with stream ``i`` starting
    ``4 i`` tokens in, so streams 4 and 5 end inside the fragment and
    stream 2 on its LAST step (the reset shows in the carry)."""
    from ray_tpu.env.jax_tokens import TokenStreamJax

    env = TokenStreamJax({"vocab_size": 32, "episode_length": 24, "phase_stride": 4})
    cfg = _lstm_cfg(rollout_fragment_length=16, train_batch_size=128)
    cfg["model"] = {"use_sequence_lm": True, "sequence_lm": LM,
                    "max_seq_len": 8, "dtype": "float32"}
    pol = _policy(env, cfg)
    return JaxRolloutEngine(pol, env, 8, 16, seed=5, standardize_advantages=False)


def _contract_arrays():
    eng = _contract_engine()
    batch, _ = eng.rollout()
    out = {f"carry_{i}": leaf for i, leaf in enumerate(
        jax.tree_util.tree_leaves(jax.device_get(eng._carry)))}
    for name in ("resets", "actions", "action_dist_inputs", "vf_preds", "t"):
        out[name] = batch[name]
    out.update({k: v for k, v in batch.items() if k.startswith("__chunk__state_in_")})
    return {k: np.asarray(v) for k, v in out.items()}


def test_lane_contract_around_an_episode_end_equals_the_plain_reset(monkeypatch):
    """The carry, the start states handed to the learner, the stored
    ``resets``, logits and values of a fragment in which streams end
    (one on the last step) equal, to the bit, what the lane gives when
    every step resets by a plain ``where`` (the reference: the one
    ``cond`` of the step that carries the state as its operand takes
    its true branch when it is traced)."""
    got = _contract_arrays()
    real, plain = jax.lax.cond, []

    def cond(pred, true_fn, false_fn, *operands):
        if not operands:
            return real(pred, true_fn, false_fn)
        plain.append(operands)
        return true_fn(*operands)

    monkeypatch.setattr(jax.lax, "cond", cond)
    want = _contract_arrays()
    monkeypatch.undo()
    (state,), = plain  # the reset, traced once, and nothing else
    assert len(state) == len(_contract_engine()._carry["state"])
    assert set(got) == set(want)
    ended = got["t"].reshape(8, 16)[:, 1:] == 0
    assert ended.any() and got["carry_0"].shape[0] == 8
    for name in sorted(got):
        assert got[name].dtype == want[name].dtype, name
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)


def _eqns_outside_conds(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "cond":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns_outside_conds(sub)


def test_no_select_over_the_deltanet_state_outside_a_cond():
    """A step on which no stream ends runs no pass over the DeltaNet
    matrices for the reset: in the lane's program every ``select`` of a
    matrix's shape sits inside a ``cond`` (the act hands the model no
    ``resets``, so the model resets nothing; the lane resets under
    ``any(done)``)."""
    eng = _contract_engine()
    pol = eng.policy
    keys = pol._rollout_keys(eng.T)
    jaxpr = jax.make_jaxpr(eng._rollout_program()._jitted)(
        pol.params, eng._carry, keys, eng._pre_dispatch()
    )
    matrix = tuple(eng._carry["state"][0].shape)
    assert len(matrix) == 4
    selects = [e for e in _eqns_outside_conds(jaxpr.jaxpr)
               if e.primitive.name == "select_n"]
    assert selects  # the env's and the small leaves' are there
    assert not [e for e in selects if tuple(e.outvars[0].aval.shape) == matrix]
    conds = [e for e in _eqns_outside_conds(jaxpr.jaxpr) if e.primitive.name == "cond"]
    inside = [
        e for c in conds for b in c.params["branches"]
        for e in _eqns_outside_conds(b.jaxpr)
        if e.primitive.name == "select_n"
        and tuple(e.outvars[0].aval.shape) == matrix
    ]
    assert len(inside) == 3  # the lane's reset of the three DeltaNet layers
