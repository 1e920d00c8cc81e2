"""A model that commits a BLOCK of tokens a step (SDAR's block
diffusion: ``models/sequence_lm/generation.py``), on the CPU in float32
at a small size, against ``perf/reference/sdar.py``: the update's replay
of the trace and every gradient leaf (a), generation through the cache
against the reference's one ``2T``-row forward a denoise step (b), the
replay against the rollout (c), five readings that are wrong on purpose
(d), the commit rule (e), the device lane (f), the expert shares (g) and
the byte model and FLOP rule against the tree's own shapes (h).
"""

import hashlib
import importlib.util
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from ray_tpu.models.sequence_lm import SequenceLM
from ray_tpu.models.sequence_lm.generation import BlockDiffusion
from ray_tpu.ops import cached_attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 64
T = 16
BLOCK, STEPS = 4, 2
EPISODE = 32
LOGIT_TOL = 3e-4
GRAD_LEAF_TOL = 2e-3


def _load(kind, name):
    spec = importlib.util.spec_from_file_location(
        "perf_" + name, os.path.join(ROOT, "perf", kind, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load("reference", "sdar")


def small_config(held=(0, 4), dtype="float32"):
    lm = {
        "model_type": "sdar_moe", "hidden_size": 64, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "rope_theta": 1e6, "num_experts": held[1], "router_outputs": 8,
        "experts_held": list(held), "num_experts_per_tok": 2,
        "moe_intermediate_size": 32, "norm_topk_prob": True,
        "max_position_embeddings": EPISODE, "rms_norm_eps": 1e-6,
        "tie_word_embeddings": False, "block_length": BLOCK,
        "denoising_steps": STEPS, "mask_token_id": VOCAB - 1,
    }
    algo = {
        "gamma": 1.0, "lambda": 0.95, "clip_param": 0.2, "lr": 1e-4,
        "grad_clip": 1.0, "kl_coeff": 0.0, "entropy_coeff": 0.0,
        "vf_loss_coeff": 1.0, "vf_clip_param": 10.0, "num_sgd_iter": 1,
        "model": {"use_sequence_lm": True, "max_seq_len": T, "dtype": dtype,
                  "sequence_lm": lm},
    }
    return dict(lm, vocab_size=VOCAB, algo_config=algo)


def _model(config, dtype="float32"):
    model = SequenceLM(
        VOCAB, config["algo_config"]["model"]["sequence_lm"], dtype=dtype)
    model.learn_streams = 2
    return model


def _f32(state):
    return tuple(jnp.asarray(s, jnp.int32 if s.dtype == np.int32 else jnp.float32)
                 for s in state)


@pytest.fixture(scope="module")
def setup():
    config = small_config()
    params = ref.init_params(jax.random.PRNGKey(7), config, VOCAB)
    batch = ref.make_batch(np.random.default_rng(3), config, 4 * T, VOCAB)
    return config, params, _model(config), batch


def _generate(model, params, state, blocks, seed, commit_from=None):
    """``blocks`` lane steps of every stream through the carried caches:
    ``(tokens, trace, logits, values (N, blocks x B ...), state)``.
    ``commit_from``: what the commit forward reads in place of the
    committed block (wrong on purpose)."""
    gen = model.generation

    @jax.jit
    def step(state, key):
        def forward(tokens, st, commit):
            if commit and commit_from is not None:
                tokens = commit_from(tokens)
            return model.apply(params, tokens, st, commit=commit)

        return gen.generate(forward, state, key)

    out = {"tokens": [], "trace": [], "logits": [], "value": []}
    for b in range(blocks):
        tokens, state, kept = step(state, jax.random.fold_in(jax.random.PRNGKey(seed), b))
        for k, v in dict(kept, tokens=tokens).items():
            if k in out:
                out[k].append(v)
    return {k: jnp.concatenate(v, axis=1) for k, v in out.items()}, state


def _start(config, depths, seed):
    rng = np.random.default_rng(seed)
    state = list(ref.make_state(rng, ref.sizes(config, VOCAB), len(depths), T))
    state[-1] = np.asarray(depths, np.int32)
    fresh = np.zeros((len(depths), T), bool)
    fresh[:, 0] = np.asarray(depths) == 0
    return _f32(state), fresh


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _rollout_against_reference(config, params, model, commit_from=None):
    """Four blocks of three streams from junk-filled caches at depths 0,
    4 and 12, held to the reference over the same tokens and trace:
    relative distances of the stored logits, values and end caches."""
    state, fresh = _start(config, [0, 4, 12], seed=11)
    rolled, end = _generate(model, params, state, T // BLOCK, 5, commit_from)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p: ref.forward(
            p, rolled["tokens"], rolled["trace"], state, fresh, config, VOCAB))(params)
    assert np.array_equal(np.asarray(end[-1]), np.asarray(want["state"][-1]))
    depth = np.asarray(end[-1])
    live = np.arange(EPISODE)[None, :, None] < depth[:, None, None]
    rows = lambda st: np.concatenate(
        [np.where(live, np.asarray(s, np.float32), 0.0).ravel() for s in st[:-1]])
    return rolled, state, {
        "logits": _rel(rolled["logits"], want["logits"]),
        "value": _rel(rolled["value"], want["value"]),
        "state": _rel(rows(end), rows(want["state"])),
    }


# -- (a) the update's replay against the reference ----------------------------


def test_the_family_is_read_from_its_keys_and_the_tree_matches(setup):
    config, params, model, _ = setup
    assert model.generation == BlockDiffusion(BLOCK, STEPS, VOCAB - 1)
    assert model.tokens_per_step == BLOCK
    mixer, ffn = model.segments[0].mixer, model.segments[0].ffn
    assert (mixer.block, mixer.gate, mixer.qk_norm, mixer.rotary) == (BLOCK, None, True, 16)
    assert ffn.shared_width == 0 and ffn.scoring == "softmax" and ffn.norm_topk
    have = jax.tree_util.tree_map(lambda x: tuple(x.shape), model.init(jax.random.PRNGKey(0)))
    assert have == jax.tree_util.tree_map(lambda x: tuple(x.shape), params)
    # every other family commits a token a step, under a causal mask
    from tests.test_sequence_lm import small_config as qwen3_next

    other = SequenceLM(VOCAB, qwen3_next()["algo_config"]["model"]["sequence_lm"])
    assert other.tokens_per_step == 1
    assert all(getattr(s.mixer, "block", 1) == 1 for s in other.segments)
    with pytest.raises(ValueError, match="do not divide"):
        BlockDiffusion(4, 3, 0)


def test_loss_and_every_gradient_leaf_match_reference(setup):
    config, params, model, batch = setup
    jb = {k: jnp.asarray(v) for k, v in batch.items()}
    state = _f32(ref.batch_state(batch))
    for k, leaf in enumerate(state):
        jb[f"__chunk__state_in_{k}"] = leaf
    shape = (4, T)

    def system_loss(p):
        logits, value, _ = model.apply(
            p, jb["actions"].reshape(shape), state,
            resets=jb["resets"].reshape(shape), trace=jb["unmask_step"].reshape(shape))
        return ref.ppo_loss(logits, value, jb, config["algo_config"])

    with jax.default_matmul_precision("highest"):
        want, want_g = jax.jit(jax.value_and_grad(
            lambda p: ref.loss(p, jb, config)))(params)
        got, got_g = jax.jit(jax.value_and_grad(system_loss))(params)
    assert abs(float(got) - float(want)) < 1e-5 * max(1.0, abs(float(want)))
    whole = np.sqrt(sum(float(jnp.sum(g * g)) for g in jax.tree_util.tree_leaves(want_g)))
    worst = max(
        (np.linalg.norm(np.asarray(got_g[g][l]) - np.asarray(want_g[g][l]))
         / max(np.linalg.norm(np.asarray(want_g[g][l])), 1e-3 * whole), g, l)
        for g in want_g for l in want_g[g])
    assert worst[0] < GRAD_LEAF_TOL, worst
    # the gradient reaches the clean pass through its keys
    assert float(jnp.abs(got_g["layer_0"]["k_proj"]).sum()) > 0


# -- (b), (c) generation through the cache, and its replay ---------------------


def test_generation_through_the_cache_equals_the_references_forward(setup):
    config, params, model, _ = setup
    rolled, _, far = _rollout_against_reference(config, params, model)
    assert max(far.values()) < LOGIT_TOL, far
    # two of a block's four tokens in each pass
    trace = np.asarray(rolled["trace"]).reshape(3, -1, BLOCK)
    assert np.all((trace == 0).sum(-1) == 2) and np.all((trace == 1).sum(-1) == 2)


def test_the_learn_form_replays_the_rollout(setup):
    config, params, model, _ = setup
    state, fresh = _start(config, [0, 4, 12], seed=11)
    rolled, _ = _generate(model, params, state, T // BLOCK, 5)

    def replay(p):
        stats = {}
        logits, value, _ = model.apply(
            p, rolled["tokens"], state, resets=fresh.astype(np.float32),
            trace=rolled["trace"], stats_out=stats, scope="learn")
        return logits, value, stats

    logits, value, stats = jax.jit(replay)(params)
    assert _rel(logits.reshape(rolled["logits"].shape), rolled["logits"]) < 1e-5
    assert _rel(value.reshape(rolled["value"].shape), rolled["value"]) < 1e-5
    # the statistics of all S + 1 passes: 3 x 48 tokens x top-2 of 8, 4 held
    held = float(stats["moe_tokens_per_held_expert"]) * 4
    assert held + float(stats["moe_slots_on_absent_experts"]) / 2 == 3 * 48 * 2
    assert 0.0 < float(stats["diffusion_commit_confidence_mean"]) < 1.0
    assert float(stats["diffusion_clean_token_passes"]) == 48
    assert float(stats["diffusion_noisy_token_passes"]) == 96


# -- (d) wrong on purpose -----------------------------------------------------


def _causal_inside_the_block(seg, pos0, positions, depth, window, block=1):
    return _FRAGMENT_MASKS(seg, pos0, positions, depth, window, 1)


def _noisy_sees_its_own_clean_rows(seg, block):
    clean, own = _NOISY_MASKS(seg, block)
    return clean | own, own


def _noisy_reads_earlier_noisy_rows(seg, block):
    blocks = jnp.arange(seg.shape[1]) // block
    same = seg[:, :, None] == seg[:, None, :]
    return (jnp.zeros_like(same),
            same & (blocks[:, None] >= blocks[None, :])[None])


_FRAGMENT_MASKS = cached_attention.fragment_masks
_NOISY_MASKS = cached_attention.noisy_masks
WRONG_MASKS = {
    "a causal mask inside the block": ("fragment_masks", _causal_inside_the_block),
    "a noisy block that sees its own clean rows": (
        "noisy_masks", _noisy_sees_its_own_clean_rows),
    "a noisy pass that reads noisy rows of earlier blocks": (
        "noisy_masks", _noisy_reads_earlier_noisy_rows),
}


@pytest.mark.parametrize("wrong", sorted(WRONG_MASKS))
def test_a_wrong_mask_fails_the_comparison(setup, monkeypatch, wrong):
    config, params, model, batch = setup
    name, fn = WRONG_MASKS[wrong]
    monkeypatch.setattr(cached_attention, name, fn)
    state = _f32(ref.batch_state(batch))
    shape = (4, T)
    tokens = jnp.asarray(batch["actions"]).reshape(shape)
    trace = jnp.asarray(batch["unmask_step"]).reshape(shape)
    fresh = jnp.asarray(batch["resets"]).reshape(shape)
    logits, _, _ = jax.jit(lambda p: model.apply(
        p, tokens, state, resets=fresh, trace=trace))(params)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p: ref.forward(
            p, tokens, trace, state, fresh > 0.5, config, VOCAB))(params)
    assert _rel(logits.reshape(want["logits"].shape), want["logits"]) > 30 * LOGIT_TOL


def test_a_cache_left_with_the_last_denoise_passes_rows_fails(setup):
    """No commit pass: the rows that stay are those of the last denoise
    forward's input, ``[MASK]`` where a token was committed in it."""
    config, params, model, _ = setup
    mask = model.generation.mask_token_id

    def commit_from(tokens):  # half of every block still masked
        return tokens.at[:, ::2].set(mask)

    _, _, far = _rollout_against_reference(config, params, model, commit_from)
    assert far["state"] > 0.1 and far["logits"] > 30 * LOGIT_TOL, far


def test_bfloat16_where_the_config_says_float32_fails(setup):
    config, params, _, _ = setup
    _, _, far = _rollout_against_reference(config, params, _model(config, "bfloat16"))
    assert far["logits"] > 5 * LOGIT_TOL and far["state"] > 5 * LOGIT_TOL, far


# -- (e) the commit rule --------------------------------------------------------


def test_the_commit_rule_on_seeded_logits():
    gen = BlockDiffusion(4, 2, mask_token_id=7)
    # one candidate a position that is certain: confidences 0.9, 0.5, 0.5, 0.7
    peaks = np.log(np.array([0.9, 0.5, 0.5, 0.7]))
    rest = np.log((1 - np.exp(peaks)) / 7)
    logits = np.tile(rest[:, None], (1, 8))
    logits[np.arange(4), [3, 7, 1, 2]] = peaks
    logits = jnp.asarray(np.tile(logits[None], (64, 1, 1)), jnp.float32)
    trace = jnp.full((64, 4), -1, jnp.int32)
    cand, chosen, logp, after = gen.commit(logits, trace, jax.random.PRNGKey(0), 0)
    cand, chosen = np.asarray(cand), np.asarray(chosen)
    assert np.all(chosen.sum(1) == 2)  # exactly n a pass
    np.testing.assert_allclose(
        np.asarray(logp), np.take_along_axis(
            np.asarray(jax.nn.log_softmax(logits)), cand[..., None], -1)[..., 0],
        atol=1e-6)
    conf = np.where(chosen, np.exp(np.asarray(logp)), np.inf)
    unchosen = np.where(~chosen, np.exp(np.asarray(logp)), -np.inf)
    assert np.all(conf.min(1) >= unchosen.max(1))  # the highest confidence
    both_peak = (cand == np.array([3, 7, 1, 2])).all(1)
    assert both_peak.any()
    assert np.all(chosen[both_peak] == [True, False, False, True])
    # a tie goes to the lower position: all four equally confident
    flat = jnp.zeros((1, 4, 8), jnp.float32)
    _, chosen, _, _ = gen.commit(flat, trace[:1], jax.random.PRNGKey(1), 0)
    assert np.asarray(chosen).tolist() == [[True, True, False, False]]
    # only masked positions, read from the trace and not the id: a
    # committed [MASK] id stays committed, the others are chosen
    done = jnp.asarray([[0, -1, 0, -1]], jnp.int32)
    _, chosen, _, after = gen.commit(logits[:1], done, jax.random.PRNGKey(2), 1)
    assert np.asarray(chosen).tolist() == [[False, True, False, True]]
    assert np.asarray(after).tolist() == [[0, 1, 0, 1]]
    tokens = jnp.asarray([[7, 7, 5, 5]], jnp.int32)
    noisy = gen.noisy_inputs(tokens, jnp.asarray([[0, 1, 1, 0]], jnp.int32))
    assert np.asarray(noisy).tolist() == [[[7, 7, 7, 7]], [[7, 7, 7, 5]]]


# -- (f) the device lane ----------------------------------------------------------


def _lane(config, env_config, envs=4, length=T, **over):
    from ray_tpu import sharding as sharding_lib
    from ray_tpu.algorithms.ppo.ppo import PPOConfig, PPOJaxPolicy
    from ray_tpu.env.registry import get_env_creator
    from ray_tpu.execution.jax_rollout import JaxRolloutEngine

    cfg = PPOConfig().to_dict()
    cfg.update(config["algo_config"])
    cfg.update(
        seed=5, num_workers=0, num_envs_per_worker=envs,
        rollout_fragment_length=length, train_batch_size=envs * length,
        sgd_minibatch_size=envs * length,
        _mesh=sharding_lib.get_mesh(devices=jax.devices()[:1]))
    cfg["model"] = dict(cfg["model"], **over)
    env = get_env_creator("TokenStreamJax-v0")(env_config)
    policy = PPOJaxPolicy(env.observation_space, env.action_space, cfg)
    return policy, env, JaxRolloutEngine(
        policy, env, envs, length, seed=5, standardize_advantages=False)


def test_the_lane_commits_a_block_a_step_and_keeps_the_token_layout():
    from ray_tpu.env.jax_env import tree_where
    from ray_tpu.telemetry import metrics

    config = small_config()
    env_config = {"vocab_size": VOCAB, "episode_length": 24, "phase_stride": 8}
    policy, env, eng = _lane(config, env_config)
    assert eng.tokens_per_step == BLOCK and eng.T == T
    start = jax.device_get({k: eng._carry[k] for k in ("env", "obs", "ep_len", "state")})
    before = dict(metrics.diffusion_token_passes())
    batch, rows = eng.rollout()
    assert rows == 4 * T
    after = metrics.diffusion_token_passes()
    grown = {k: after[k] - before.get(k, 0.0) for k in after}
    assert grown == {"denoise": 2.0 * rows, "commit": rows, "committed": rows}
    batch = jax.device_get(batch)
    col = lambda name: np.asarray(batch[name]).reshape((4, T) + batch[name].shape[1:])
    actions, trace = col("actions"), col("unmask_step")
    assert np.array_equal(np.asarray(eng.last_actions).T, actions)
    assert np.array_equal(np.asarray(eng.last_trace).T, trace)
    assert sorted(np.unique(trace)) == [0, 1]
    assert np.all((trace.reshape(4, -1, BLOCK) == 0).sum(-1) == 2)
    # the env replayed on the reported actions, a token a step in
    # position order: the rows' rewards, ends and observations
    def replay(c, a):
        s, o, n = c
        s2, o2, rew, term, trunc = jax.vmap(env.step)(s, a)
        s3, o3 = jax.vmap(env.reset)(s2)
        row = {"obs": o, "rewards": rew, "dones": term,
               "resets": (n == 0).astype(jnp.float32)}
        return (tree_where(term, s3, s2), tree_where(term, o3, o2),
                jnp.where(term, 0, n + 1)), row

    (s, o, n), want = jax.lax.scan(
        replay, (start["env"], start["obs"], start["ep_len"]), jnp.asarray(actions.T))
    for name in ("obs", "rewards", "dones", "resets"):
        np.testing.assert_array_equal(
            col(name), np.swapaxes(np.asarray(want[name]), 0, 1), err_msg=name)
    # streams 1 and 2 start 8 and 16 tokens in: stream 2 ends its
    # episode inside the fragment, on a block's last token
    ended = np.argwhere(col("dones"))
    assert ended.tolist() == [[1, 15], [2, 7]]
    assert col("resets")[2, 8] == 1.0
    # the model's position is the env's place in the episode: reset
    # after the block that ended one
    position = np.asarray(eng._carry["state"][-1])
    assert position.tolist() == np.asarray(eng._carry["env"]["t"]).tolist() == [16, 0, 8, 16]
    # what the lane stored is what the learn form replays from the
    # fragment's start state
    starts = tuple(batch[f"__chunk__state_in_{k}"] for k in range(len(start["state"])))
    logits, value, _ = jax.jit(lambda p: policy.model.apply(
        p, jnp.asarray(actions), starts, resets=jnp.asarray(col("resets")),
        trace=jnp.asarray(trace)))(policy.params)
    assert _rel(logits, batch["action_dist_inputs"]) < 1e-5
    assert _rel(value, batch["vf_preds"]) < 1e-5
    logp = np.take_along_axis(
        np.asarray(jax.nn.log_softmax(batch["action_dist_inputs"])),
        batch["actions"][:, None], 1)[:, 0]
    np.testing.assert_allclose(batch["action_logp"], logp, atol=1e-5)
    # GAE in position order, the tail from the next block's first value
    tail = policy.block_first_value(policy.params, eng._carry["state"])
    values = col("vf_preds").T
    next_values = np.concatenate([values[1:], np.asarray(tail)[None]])
    adv, targets = ref.gae(
        col("rewards").T, values, next_values, col("dones").T, col("dones").T, 1.0, 0.95)
    np.testing.assert_allclose(col("advantages"), adv.T, atol=1e-4)
    np.testing.assert_allclose(col("value_targets"), targets.T, atol=1e-4)


@pytest.mark.parametrize("what, env_config, over", [
    ("episode_length", {"episode_length": 30, "phase_stride": 8}, {}),
    ("phase_stride", {"episode_length": 32, "phase_stride": 6}, {}),
    ("max_seq_len", {"episode_length": 32, "phase_stride": 8}, {"max_seq_len": 2}),
])
def test_the_lane_refuses_lengths_a_block_does_not_divide(what, env_config, over):
    with pytest.raises(ValueError, match=what):
        _lane(small_config(), dict(env_config, vocab_size=VOCAB), **over)


def test_a_token_a_step_models_rollout_body_is_the_parents():
    """The lane's body of a ``tokens_per_step`` 1 model lowers to what
    it lowered to before a block a step existed: the ``make_jaxpr`` text
    of the small Qwen3-Next config's body, recorded on the parent commit
    (c6d140e) by this same function."""
    from tests.test_sequence_lm import small_config as qwen3_next

    policy, _, eng = _lane(
        qwen3_next(), {"vocab_size": VOCAB, "episode_length": 32, "phase_stride": 8})
    assert eng.tokens_per_step == 1
    keys = jax.random.split(jax.random.PRNGKey(0), eng.T)
    text = str(jax.make_jaxpr(
        lambda p, c, k, co: eng._rollout_body()(p, c, k, co), axis_env=[("batch", 1)]
    )(policy.params, eng._carry, keys, eng._pre_dispatch()))
    with open(os.path.join(ROOT, "tests", "data", "qwen3_next_rollout_body.sha256")) as f:
        assert hashlib.sha256(text.encode()).hexdigest() == f.read().strip()


# -- (g), (h) shares and arithmetic -----------------------------------------------


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The parts the expert shares of a layer give (1 of 8 experts each;
    no shared expert) add up to the uncut reference's layer output."""
    config = small_config(held=(0, 8))
    z = ref.sizes(config, VOCAB)
    p = ref.init_params(jax.random.PRNGKey(1), config, VOCAB)["layer_0"]
    x = jnp.asarray(np.random.default_rng(2).standard_normal((2, T, 64)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = ref._experts(p, x, *ref._route(p, x, z), z, lambda v: v)
        total = 0.0
        for first in range(8):
            model = _model(small_config(held=(first, 1)))
            share = {**p, **{k: p[k][first:first + 1] for k in
                             ("experts_gate", "experts_up", "experts_down")}}
            part, _, stats = model.segments[0].ffn.apply(
                share, x, (), {"scope": "", "dtype": jnp.float32})
            total = total + part
            assert float(stats["moe_held_load"].sum()
                         + stats["moe_slots_on_absent_experts"]) == 2 * T * 2
    np.testing.assert_allclose(total, whole, atol=2e-5)


def test_byte_model_and_flop_rule_are_the_trees_own_shapes(setup):
    config, params, model, _ = setup
    counts = _load("", "block_diffusion_model")
    leaves = jax.tree_util.tree_leaves(params)
    assert counts.param_count(config, VOCAB) == sum(x.size for x in leaves)
    assert counts.param_count(config, VOCAB) == sum(
        int(np.prod(s)) for g in model.param_shapes().values() for s in g.values())
    layer = params["layer_0"]
    in_products = sum(layer[k].size for k in (
        "q_proj", "k_proj", "v_proj", "o_proj", "experts_gate", "experts_up",
        "experts_down"))
    assert counts.layer_param_counts(config)["in_products"] == in_products
    assert counts.product_weight_count(config, VOCAB) == (
        2 * in_products + params["head"]["kernel"].size)
    # a block forward of 3 streams: product weights at 2 bytes, the
    # others at 4 with 12 rows of the embedding, the caches' rows below
    # the block at the mean depth and the block's own twice, the logits
    others = sum(x.size for x in leaves) - counts.product_weight_count(
        config, VOCAB) - params["embed"]["embedding"].size
    cache_row = sum(s.shape[-1] * 2 for s in model.initial_state(1)[:-1])
    want = (2 * counts.product_weight_count(config, VOCAB) + 4 * (others + 12 * 64)
            + 3 * cache_row * ((EPISODE - BLOCK) / 2 + 2 * BLOCK)
            + 4 * 3 * BLOCK * VOCAB)
    assert counts.block_forward_bytes(config, VOCAB, 3) == want
    # the commit forward: but for the last layer's queries, W_o, router,
    # experts and cache rows, the final norm, the heads and the logits
    spared = (2 * (layer["q_proj"].size + layer["o_proj"].size
                   + 3 * layer["experts_gate"].size) + 4 * layer["router"].size
              + 3 * cache_row / 2 * (EPISODE - BLOCK) / 2
              + 2 * params["head"]["kernel"].size + 4 * (64 + 64 + 1)
              + 4 * 3 * BLOCK * VOCAB)
    assert counts.commit_forward_bytes(config, VOCAB, 3) == want - spared
    assert counts.block_step_bytes(config, VOCAB, 3) == (STEPS + 1) * want - spared
    rule = _load("flop_rules", "sdar_ppo")
    one = rule.forward_flops_per_token_pass(config, VOCAB)
    attention = sum(layer[k].size for k in ("q_proj", "k_proj", "v_proj", "o_proj"))
    # top-2 of 8 with 4 held: one expert a token
    macs = 2 * (attention + layer["router"].size + layer["experts_gate"][0].size * 3
                + 4 * (EPISODE + BLOCK) / 2 * 2 * 16) + 64 * VOCAB + 64
    assert one == 2 * macs
    assert rule.train_flops_per_env_step(config, VOCAB) == (STEPS + 1) * one * 4
