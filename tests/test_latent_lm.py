"""The sequence model's latent-attention, hyper-connection and
sigmoid-router kinds (models/sequence_lm, ops/latent_attention.py,
ops/hyper_connection.py, ops/moe.py) held to the plain reference
(perf/reference/xing4.py) on seeded weights at a small size with
deliberately unequal dimensions: nope 16, rope 8, value 12, latent 24,
query latent 20, 3 lanes.
"""

import functools
import importlib.util
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from ray_tpu.models.sequence_lm import SequenceLM
from ray_tpu.ops import hyper_connection, latent_attention, moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 64
T = 16
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}


def _reference():
    path = os.path.join(ROOT, "perf", "reference", "xing4.py")
    spec = importlib.util.spec_from_file_location("ref_xing4", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _reference()


def small_config(held=(0, 2), **over):
    """Hidden 32, 3 layers of which the first is dense, 8 router outputs
    of which 2 are held, a vocabulary of 64."""
    lm = {
        "hidden_size": 32, "num_hidden_layers": 3, "first_k_dense_replace": 1,
        "num_attention_heads": 4, "q_lora_rank": 20, "kv_lora_rank": 24,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 12,
        "rope_theta": 10000, "rope_scaling": dict(YARN),
        "rms_norm_eps": 1e-6, "max_position_embeddings": 48,
        "intermediate_size": 48, "moe_intermediate_size": 16,
        "n_shared_experts": 1, "n_routed_experts": held[1], "router_outputs": 8,
        "experts_held": list(held), "num_experts_per_tok": 3,
        "norm_topk_prob": True, "routed_scaling_factor": 2.0,
        "scoring_func": "sigmoid", "topk_method": "noaux_tc",
        "hc_mult": 3, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
        "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    }
    lm.update(over)
    config = dict(lm)
    config["algo_config"] = {
        "clip_param": 0.2, "vf_clip_param": 10.0, "kl_coeff": 0.0,
        "entropy_coeff": 0.0, "vf_loss_coeff": 1.0,
        "model": {"use_sequence_lm": True, "sequence_lm": lm, "max_seq_len": T,
                  "dtype": "float32"},
    }
    return config


def _model(lm):
    model = SequenceLM(VOCAB, lm, dtype="float32")
    model.learn_streams = 2
    return model


def _f32_state(state):
    return tuple(jnp.asarray(s, jnp.float32 if s.dtype != np.int32 else jnp.int32)
                 for s in state)


@pytest.fixture(scope="module")
def setup():
    config = small_config()
    params = ref.init_params(jax.random.PRNGKey(7), config, VOCAB)
    model = _model(config["algo_config"]["model"]["sequence_lm"])
    batch = ref.make_batch(np.random.default_rng(3), config, 4 * T, VOCAB)
    return config, params, model, batch


def _model_forward(model, params, batch, stats=None):
    rows = batch["obs"].shape[0]
    return model.apply(
        params, jnp.asarray(batch["obs"]).reshape(rows // T, T, 1),
        _f32_state(ref.batch_state(batch)),
        resets=jnp.asarray(batch["resets"]).reshape(rows // T, T),
        stats_out=stats,
    )


def test_param_shapes_and_state_match_the_reference(setup):
    config, params, model, _ = setup
    want = {g: {k: v.shape for k, v in leaves.items()} for g, leaves in params.items()}
    assert model.param_shapes() == want
    made = model.init(jax.random.PRNGKey(0))
    assert jax.tree_util.tree_map(lambda x: x.shape, made) == want
    z = ref.sizes(config, VOCAB)
    assert [s.shape for s in model.initial_state(5)] == [
        s.shape for s in ref.initial_state(z, 5)]
    # one leaf a layer, a row of latent + rope numbers, whatever the heads
    assert model.initial_state(5)[0].shape == (5, 48, 24 + 8)


def test_one_token_absorbed_steps_equal_the_reference_forward(setup):
    """(a) token by token through the cache (the absorbed product, the
    rollout's form) against the reference's expanded full forward, an
    episode ending inside the second stream's fragment."""
    config, params, model, _ = setup
    rng = np.random.default_rng(11)
    n = 3
    tokens = rng.integers(0, VOCAB, (n, T)).astype(np.int32)
    fresh = np.zeros((n, T), bool)
    fresh[0, 0] = True
    fresh[1, 6] = True
    z = ref.sizes(config, VOCAB)
    start = list(ref.make_state(rng, z, n, T))
    start[-1] = np.asarray([0, 30, 17], np.int32)
    start = _f32_state(start)
    with jax.default_matmul_precision("highest"):
        want = ref.forward(params, tokens, start, fresh, config, VOCAB)
        state, logits, values = start, [], []
        for i in range(T):
            lg, v, state = model.apply(
                params, jnp.asarray(tokens[:, i : i + 1, None]), state,
                resets=jnp.asarray(fresh[:, i : i + 1], jnp.float32))
            logits.append(lg)
            values.append(v)
    np.testing.assert_allclose(
        jnp.stack(logits, 1), want["logits"], atol=3e-4, rtol=3e-4)
    np.testing.assert_allclose(jnp.stack(values, 1), want["value"], atol=3e-4, rtol=3e-4)
    assert np.array_equal(np.asarray(state[-1]), np.asarray(want["state"][-1]))
    for got, ref_rows, depth in zip(state[:-1], want["state"][:-1],
                                    np.asarray(state[-1])[None].repeat(3, 0)):
        for s in range(n):  # the rows below each stream's position
            np.testing.assert_allclose(
                got[s, : depth[s]], ref_rows[s, : depth[s]], atol=2e-4)


def test_fragment_form_equals_the_chain_of_one_token_steps(setup):
    """(b) the expanded fragment form from a stored start state against
    the chain of absorbed steps, a reset inside."""
    config, params, model, batch = setup
    rows = batch["obs"].shape[0]
    n = rows // T
    tokens = jnp.asarray(batch["obs"]).reshape(n, T, 1)
    resets = jnp.asarray(batch["resets"]).reshape(n, T)
    assert float(resets.sum()) >= 1
    with jax.default_matmul_precision("highest"):
        logits, value, after = _model_forward(model, params, batch)
        state, chain = _f32_state(ref.batch_state(batch)), []
        for i in range(T):
            lg, _, state = model.apply(
                params, tokens[:, i : i + 1], state, resets=resets[:, i : i + 1])
            chain.append(lg)
    np.testing.assert_allclose(
        jnp.stack(chain, 1).reshape(rows, VOCAB), logits, atol=3e-4, rtol=3e-4)
    assert np.array_equal(np.asarray(state[-1]), np.asarray(after[-1]))
    depth = np.asarray(after[-1])
    for a, b in zip(state[:-1], after[:-1]):
        for s in range(n):
            np.testing.assert_allclose(a[s, : depth[s]], b[s, : depth[s]], atol=2e-4)


def _fragment_kernel_in_the_interpreter(monkeypatch):
    """What a TPU's rule would say, at this file's sizes: the latent
    layers' fragment form on the tiled kernel in the Pallas interpreter,
    the cache of 48 rows as three key blocks of 16, the four query heads
    of the one key head in two tiles."""
    import functools

    from ray_tpu.ops import flash_attention

    monkeypatch.setattr(flash_attention, "fragment_kernel_applies", lambda *a: True)
    monkeypatch.setattr(flash_attention, "fragment_block_k", lambda depth, _=None: 16)
    monkeypatch.setattr(flash_attention, "fragment_head_tile", lambda *a: 2)
    monkeypatch.setattr(
        flash_attention, "fragment_attention",
        functools.partial(flash_attention.fragment_attention, interpret=True))


def test_fragment_form_on_the_kernel_equals_the_text_and_the_steps(
        setup, monkeypatch):
    """(b') the absorbed product on the tiled kernel against the
    expanded text it replaces on a TPU: the same logits, loss and
    parameter gradient (``kv_b``'s leaves by themselves: they get it
    through the two absorbed halves, the stored rows' share included),
    for streams at three depths, an episode that ends inside a fragment
    and one that opens at its first token; and the kernel's fragment
    form against the chain of absorbed steps, as (b)."""
    from ray_tpu.telemetry import metrics

    config, params, model, batch = setup
    dev = {k: jnp.asarray(v) for k, v in batch.items()}
    rows = batch["obs"].shape[0]
    n = rows // T
    tokens = jnp.asarray(batch["obs"]).reshape(n, T, 1)
    resets = jnp.asarray(batch["resets"]).reshape(n, T)
    pos0 = np.asarray(ref.batch_state(batch)[-1])
    assert len(set(pos0.tolist())) >= 3 and pos0.min() == 0 and pos0.max() > 16
    assert float(resets[0, 0]) == 1.0 and float(resets[1, 1:].sum()) == 1.0

    def loss(p):
        stats = {}
        logits, value, _ = _model_forward(model, p, batch, stats)
        return ref.ppo_loss(logits, value, dev, config["algo_config"]), (logits, stats)

    with jax.default_matmul_precision("highest"):
        (want_loss, (want_logits, want_stats)), want = jax.value_and_grad(
            loss, has_aux=True)(params)
        _fragment_kernel_in_the_interpreter(monkeypatch)
        forms, paths = metrics.mla_decode_lowerings(), dict(
            metrics.attention_fragment_lowerings())
        (got_loss, (got_logits, got_stats)), got = jax.value_and_grad(
            loss, has_aux=True)(params)
        state, chain = _f32_state(ref.batch_state(batch)), []
        for i in range(T):
            lg, _, state = model.apply(
                params, tokens[:, i : i + 1], state, resets=resets[:, i : i + 1])
            chain.append(lg)
    # a traced block once: the dense layer, and the two expert layers together
    now = metrics.mla_decode_lowerings()
    assert now.get("absorbed_fragment", 0) - forms.get("absorbed_fragment", 0) == 2
    assert now.get("expanded", 0) == forms.get("expanded", 0)
    now = metrics.attention_fragment_lowerings()
    assert now.get("kernel", 0) - paths.get("kernel", 0) == 2
    assert now.get("xla", 0) == paths.get("xla", 0)
    np.testing.assert_allclose(got_logits, want_logits, atol=3e-4, rtol=3e-4)
    np.testing.assert_allclose(
        jnp.stack(chain, 1).reshape(rows, VOCAB), got_logits, atol=3e-4, rtol=3e-4)
    assert abs(float(got_loss) - float(want_loss)) < 1e-5 * abs(float(want_loss))
    whole = np.sqrt(sum(float(jnp.sum(g * g)) for g in jax.tree_util.tree_leaves(want)))
    for group in want:
        for leaf in want[group]:
            g, w = np.asarray(got[group][leaf]), np.asarray(want[group][leaf])
            floor = 1e-3 * whole if leaf != "kv_b" else 1e-12
            err = np.linalg.norm(g - w) / max(np.linalg.norm(w), floor)
            assert err < 1e-3, (group, leaf, err)
    # of a stream's three stored blocks of 16 and its own, the stored
    # ones at or past its start position are skipped; the text skips none
    skipped = sum(3 - min(-(-int(p) // 16), 3) for p in pos0)
    assert float(got_stats["attn_key_blocks_skipped_share"]) == pytest.approx(
        skipped / (4.0 * n))
    assert float(want_stats["attn_key_blocks_skipped_share"]) == 0.0
    # the one-token form's rule was not forced: its statistic reads 0 of 0
    assert float(got_stats["attn_decode_key_blocks_skipped_share"]) == 0.0


# the step kernel copies a row's latent as whole lane tiles: the forced
# cases run this file's model with a latent of 128 (a row of 136)
WIDE = {"kv_lora_rank": 128}


def _step_kernel_in_the_interpreter(monkeypatch):
    """What a TPU's rule would say, at this file's sizes: the latent
    layers' one-token form on the step kernel in the Pallas interpreter,
    the cache of 48 rows as three key blocks of 16."""
    from ray_tpu.ops import flash_attention

    monkeypatch.setattr(
        flash_attention, "step_kernel_applies", lambda *a, **value: True)
    monkeypatch.setattr(flash_attention, "fragment_block_k", lambda depth, _=None: 16)
    monkeypatch.setattr(
        flash_attention, "step_attention",
        functools.partial(flash_attention.step_attention, interpret=True))


@pytest.mark.parametrize("forced", [False, True])
def test_one_token_form_takes_the_step_kernel_by_the_rule(monkeypatch, forced):
    """(a') the three latent layers' one-token calls lower to the text
    off a TPU (``absorbed``) and to the step kernel where its rule says
    so (``absorbed_kernel``, here in the interpreter): the same logits,
    values and state, both counters say which ran, and the learn form
    reports the key blocks a step at each of its positions skips (0 of
    0 on the text)."""
    from ray_tpu.telemetry import metrics

    config = small_config(**WIDE)
    params = ref.init_params(jax.random.PRNGKey(7), config, VOCAB)
    model = _model(config["algo_config"]["model"]["sequence_lm"])
    batch = ref.make_batch(np.random.default_rng(3), config, 4 * T, VOCAB)
    rows = batch["obs"].shape[0]
    state = _f32_state(ref.batch_state(batch))
    tokens = jnp.asarray(batch["obs"]).reshape(rows // T, T, 1)
    with jax.default_matmul_precision("highest"):
        want = model.apply(params, tokens[:, :1], state)
        if forced:
            _step_kernel_in_the_interpreter(monkeypatch)
        forms = dict(metrics.mla_decode_lowerings())
        paths = dict(metrics.attention_step_lowerings())
        got = model.apply(params, tokens[:, :1], state)
    now = metrics.mla_decode_lowerings()
    grown = {k: now[k] - forms.get(k, 0) for k in now if now[k] != forms.get(k, 0)}
    assert grown == {"absorbed_kernel" if forced else "absorbed": 3}
    now = metrics.attention_step_lowerings()
    grown = {k: now[k] - paths.get(k, 0) for k in now if now[k] != paths.get(k, 0)}
    assert grown == {"kernel" if forced else "xla": 3}
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=3e-4, rtol=3e-4)
    stats = {}
    _model_forward(model, params, batch, stats)
    # by hand: a step at position p holds the blocks of 16 up to p's own,
    # of a layer's three
    pos0 = np.asarray(ref.batch_state(batch)[-1])
    fresh = batch["resets"].reshape(-1, T) > 0.5
    skipped = []
    for n in range(rows // T):
        p = int(pos0[n])
        for i in range(T):
            p = 0 if fresh[n, i] else p
            skipped.append(3 - min(p // 16 + 1, 3))
            p += 1
    assert 0.1 < np.mean(skipped) / 3 < 0.9
    assert float(stats["attn_decode_key_blocks_skipped_share"]) == pytest.approx(
        np.mean(skipped) / 3 if forced else 0.0)


def test_a_rollout_through_the_lane_is_the_same_on_both_forms(monkeypatch):
    """The device lane's rollout of the small latent model (8 streams 6
    positions apart in episodes of 48, 8 steps: streams in every key
    block of 16, one crossing a block's edge) with the one-token form on
    the text and on the step kernel: the same tokens, and the same
    log-probabilities, values and latent rows within tolerance."""
    from ray_tpu import sharding as sharding_lib
    from ray_tpu.algorithms.ppo.ppo import PPOConfig, PPOJaxPolicy
    from ray_tpu.env.jax_tokens import TokenStreamJax
    from ray_tpu.execution.jax_rollout import JaxRolloutEngine
    from ray_tpu.telemetry import metrics

    def rollout():
        env = TokenStreamJax(
            {"vocab_size": VOCAB, "episode_length": 48, "phase_stride": 6})
        cfg = PPOConfig().to_dict()
        cfg.update(
            seed=5, num_workers=0, num_envs_per_worker=8,
            rollout_fragment_length=8, train_batch_size=64,
            sgd_minibatch_size=64, num_sgd_iter=1,
            model={"use_sequence_lm": True, "max_seq_len": 8, "dtype": "float32",
                   "sequence_lm": small_config(**WIDE)["algo_config"]["model"][
                       "sequence_lm"]},
            _mesh=sharding_lib.get_mesh(devices=jax.devices()[:1]))
        cfg["lambda"] = 0.95
        policy = PPOJaxPolicy(env.observation_space, env.action_space, cfg)
        engine = JaxRolloutEngine(
            policy, env, 8, 8, seed=5, standardize_advantages=False)
        batch = jax.device_get(engine.rollout()[0])
        return batch, jax.device_get(engine._carry["state"])

    before = dict(metrics.mla_decode_lowerings())
    want, want_state = rollout()
    text = dict(metrics.mla_decode_lowerings())
    assert text.get("absorbed", 0) > before.get("absorbed", 0)
    assert text.get("absorbed_kernel", 0) == before.get("absorbed_kernel", 0)
    _step_kernel_in_the_interpreter(monkeypatch)
    # the Pallas interpreter slices a stream's block out of operands that
    # vary over the mesh at a grid index that does not, and jax refuses the
    # pair under ``shard_map``'s typing; on a TPU the grid is Mosaic's own
    monkeypatch.setattr(
        jax, "shard_map", functools.partial(jax.shard_map, check_vma=False))
    got, got_state = rollout()
    now = metrics.mla_decode_lowerings()
    assert now.get("absorbed_kernel", 0) > text.get("absorbed_kernel", 0)
    assert now.get("absorbed", 0) == text.get("absorbed", 0)
    np.testing.assert_array_equal(got["actions"], want["actions"])
    assert len(set(np.asarray(want["actions"]).tolist())) > 8
    for name in ("action_logp", "vf_preds", "action_dist_inputs"):
        np.testing.assert_allclose(
            got[name], want[name], atol=1e-4, rtol=1e-4, err_msg=name)
    depth = np.asarray(want_state[-1])
    np.testing.assert_array_equal(np.asarray(got_state[-1]), depth)
    for a, b in zip(got_state[:-1], want_state[:-1]):
        for s in range(8):
            np.testing.assert_allclose(a[s, : depth[s]], b[s, : depth[s]], atol=1e-4)


def test_hyper_connection_block_equals_the_reference_and_its_gradient(setup):
    """(c) rows and columns of ``H_res`` sum to 1, the block is the
    reference's token by token, and the gradient through the 20 rounds
    is ``jax.grad`` of the reference."""
    config, params, _, _ = setup
    z = ref.sizes(config, VOCAB)
    n, d = z["n"], z["D"]
    p = {k: jnp.asarray(v) for k, v in params["layer_1"].items()}
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((2, 5, n, d)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((d, d)) / np.sqrt(d), jnp.float32)

    def system(p, x):
        flat = x.reshape(2, 5, n * d)
        pre, post, res = hyper_connection.maps(
            flat, p["hc_ffn_norm"], p["hc_ffn_phi"], p["hc_ffn_a"], p["hc_ffn_b"],
            n, z["eps"], z["rounds"], z["hc_eps"], z["lo"], z["hi"])
        y = jnp.tanh(jnp.dot(hyper_connection.mix_in(flat, pre), w))
        return hyper_connection.mix_out(flat, y, post, res).reshape(x.shape), res

    def reference(p, x):
        return ref._hyper(p, "ffn", x, lambda h: (jnp.tanh(jnp.dot(h, w)), None), z)[0]

    with jax.default_matmul_precision("highest"):
        got, res = system(p, x)
        np.testing.assert_allclose(got, reference(p, x), atol=1e-5, rtol=1e-5)
        # the last round divides the rows: they sum to 1 within eps; 20
        # rounds leave the columns within 1e-4 on these seeded maps
        assert float(jnp.max(jnp.abs(res.sum(-1) - 1.0))) < 1e-5
        assert float(jnp.max(jnp.abs(res.sum(-2) - 1.0))) < 1e-4
        assert 0.05 < float(res.min()) and float(res.max()) < 0.95
        g_sys = jax.grad(lambda p, x: jnp.sum(jnp.sin(system(p, x)[0])), (0, 1))(p, x)
        g_ref = jax.grad(lambda p, x: jnp.sum(jnp.sin(reference(p, x))), (0, 1))(p, x)
    np.testing.assert_allclose(g_sys[1], g_ref[1], atol=1e-5, rtol=1e-4)
    for leaf in ("hc_ffn_norm", "hc_ffn_phi", "hc_ffn_a", "hc_ffn_b"):
        np.testing.assert_allclose(
            g_sys[0][leaf], g_ref[0][leaf], atol=1e-5, rtol=1e-3, err_msg=leaf)
        assert float(jnp.abs(g_ref[0][leaf]).max()) > 0


def test_selection_bias_picks_and_never_weighs():
    """(d) the bias changes WHICH experts a token gets and never a
    weight; weights sum to the scaling factor; no gradient reaches it."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((40, 16)), jnp.float32)
    kernel = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    bias = jnp.asarray(0.3 * rng.standard_normal(8), jnp.float32)
    scores = jax.nn.sigmoid(jnp.dot(x, kernel, precision=jax.lax.Precision.HIGHEST))
    i0, w0 = moe.route(x, kernel, 3, True, scoring="sigmoid", scale=2.0)[:2]
    i1, w1 = moe.route(x, kernel, 3, True, scoring="sigmoid", select_bias=bias,
                             scale=2.0)[:2]
    assert np.any(np.sort(i0, -1) != np.sort(i1, -1))  # some token's set moved
    np.testing.assert_allclose(w0.sum(-1), 2.0, rtol=1e-6)
    np.testing.assert_allclose(w1.sum(-1), 2.0, rtol=1e-6)
    picked = jnp.take_along_axis(scores, i1, axis=-1)
    np.testing.assert_allclose(w1, 2.0 * picked / picked.sum(-1, keepdims=True),
                               rtol=1e-6)
    assert np.array_equal(
        np.sort(i1, -1), np.sort(np.asarray(jax.lax.top_k(scores + bias, 3)[1]), -1))
    grad = jax.grad(lambda b: jnp.sum(jnp.square(moe.route(
        x, kernel, 3, True, scoring="sigmoid", select_bias=b, scale=2.0)[1])))(bias)
    assert float(jnp.abs(grad).max()) == 0.0
    # the defaults are the softmax router as it was
    i2, w2 = moe.route(x, kernel, 3, True)[:2]
    top_w, top_i = jax.lax.top_k(jax.nn.softmax(jnp.dot(
        x, kernel, precision=jax.lax.Precision.HIGHEST)), 3)
    assert np.array_equal(i2, top_i)
    np.testing.assert_allclose(w2, top_w / top_w.sum(-1, keepdims=True), rtol=1e-6)


def test_the_bias_gets_no_update(setup):
    """(d) one Adam step on the model's gradient leaves every
    ``select_bias`` to the bit, and its moments at zero."""
    import optax

    config, params, model, batch = setup
    dev = {k: jnp.asarray(v) for k, v in batch.items()}
    p = jax.tree_util.tree_map(jnp.asarray, params)

    def loss(p):
        logits, value, _ = _model_forward(model, p, batch)
        return ref.ppo_loss(logits, value, dev, config["algo_config"])

    grads = jax.grad(loss)(p)
    tx = optax.adam(1e-2)
    updates, opt = tx.update(grads, tx.init(p), p)
    after = optax.apply_updates(p, updates)
    for layer in ("layer_1", "layer_2"):
        assert np.array_equal(after[layer]["select_bias"], p[layer]["select_bias"])
        assert float(jnp.abs(opt[0].mu[layer]["select_bias"]).max()) == 0.0
        assert float(jnp.abs(opt[0].nu[layer]["select_bias"]).max()) == 0.0
        assert not np.array_equal(after[layer]["router"], p[layer]["router"])


def test_expert_shares_add_up_to_the_uncut_layer():
    """(e) the parts the 4 shares of a layer give (2 of 8 experts each),
    the shared expert counted once, add up to the uncut reference's
    layer."""
    config = small_config(held=(0, 8))
    z = ref.sizes(config, VOCAB)
    p = ref.init_params(jax.random.PRNGKey(1), config, VOCAB)["layer_1"]
    x = jnp.asarray(np.random.default_rng(2).standard_normal((2, T, 32)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, _ = ref._experts(p, x, z, lambda v: v)
        shared_only, _ = ref._experts(
            {**p, "experts_down": jnp.zeros_like(p["experts_down"])}, x, z, lambda v: v
        )
        total = shared_only
        for first in range(0, 8, 2):
            lm = small_config(held=(first, 2))["algo_config"]["model"]["sequence_lm"]
            sl = slice(first, first + 2)
            share = {**p, **{k: p[k][sl] for k in
                             ("experts_gate", "experts_up", "experts_down")}}
            part, _, stats = _model(lm).segments[-1].ffn.apply(
                share, x, (), {"scope": "", "dtype": jnp.float32})
            total = total + (part - shared_only)
            assert float(stats["moe_held_load"].sum()
                         + stats["moe_slots_on_absent_experts"]) == 2 * T * 3
    np.testing.assert_allclose(total, whole, atol=2e-5)


def test_loss_and_every_gradient_leaf_match_reference(setup):
    """(f) the model under the reference's loss against the reference's
    own loss and gradient, leaf by leaf."""
    config, params, model, batch = setup
    dev = {k: jnp.asarray(v) for k, v in batch.items()}

    def system_loss(p):
        logits, value, _ = _model_forward(model, p, batch)
        return ref.ppo_loss(logits, value, dev, config["algo_config"])

    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.value_and_grad(lambda p: ref.loss(p, dev, config))(params)
        stats = {"moe_routes": None}
        _model_forward(model, params, batch, stats)
        out = ref.forward(
            params, batch["obs"].reshape(-1, T), _f32_state(ref.batch_state(batch)),
            batch["resets"].reshape(-1, T) > 0.5, config, VOCAB)
        got_loss, got = jax.value_and_grad(system_loss)(params)
    assert np.array_equal(np.sort(np.asarray(stats["moe_routes"]), -1),
                          np.sort(np.asarray(out["routes"]), -1))
    assert abs(float(got_loss) - float(want_loss)) < 1e-4 * abs(float(want_loss))
    whole = np.sqrt(sum(float(jnp.sum(g * g)) for g in jax.tree_util.tree_leaves(want)))
    for group in want:
        for leaf in want[group]:
            g, w = np.asarray(got[group][leaf]), np.asarray(want[group][leaf])
            err = np.linalg.norm(g - w) / max(np.linalg.norm(w), 1e-3 * whole)
            assert err < 2e-3, (group, leaf, err)
    for layer in ("layer_1", "layer_2"):
        assert float(np.abs(got[layer]["select_bias"]).max()) == 0.0
        assert float(np.abs(want[layer]["select_bias"]).max()) == 0.0


def test_the_policys_grouped_learn_body_takes_the_reference_gradient(setup):
    """(f) ``JaxPolicy``'s whole-stack grouping (``loss_groups``: 2
    groups of 2 streams here) around a mean loss gives the reference's
    loss and gradient, and the loads of the groups add up."""
    from ray_tpu.policy import jax_policy

    config, params, model, batch = setup
    dev = {k: jnp.asarray(v) for k, v in batch.items()}
    assert model.loss_groups(4) == 2 and model.loss_groups(2) == 1
    assert _model_plain().loss_groups(64) is None

    def loss_fn(p, aux, part, rng, coeffs):
        stats = {}
        logits, value, _ = _model_forward(model, p, part, stats)
        # the loss's own keys beside the model's, as the policy's hands them
        stats.update(value_mean=jnp.mean(value), value_abs_max=jnp.max(jnp.abs(value)))
        return ref.ppo_loss(logits, value, part, config["algo_config"]), stats

    p = jax.tree_util.tree_map(jnp.asarray, params)
    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.value_and_grad(lambda p: ref.loss(p, dev, config))(p)
        (loss, stats), got = jax.jit(
            lambda p: jax.shard_map(
                lambda p: jax_policy._grouped_loss_grad(
                    loss_fn, 2, model.reduce_group_stats, p, None, dev,
                    jax.random.PRNGKey(0), None, "x"),
                mesh=jax.make_mesh((1,), ("x",)), in_specs=jax.P(), out_specs=jax.P(),
                check_vma=False,
            )(p))(p)
    assert abs(float(loss) - float(want_loss)) < 1e-4 * abs(float(want_loss))
    whole = np.sqrt(sum(float(jnp.sum(g * g)) for g in jax.tree_util.tree_leaves(want)))
    for group in want:
        for leaf in want[group]:
            g, w = np.asarray(got[group][leaf]), np.asarray(want[group][leaf])
            err = np.linalg.norm(g - w) / max(np.linalg.norm(w), 1e-3 * whole)
            assert err < 2e-3, (group, leaf, err)
    # 64 tokens x 3 slots over 2 expert layers, 2 of 8 experts held
    assert float(stats["moe_slots_on_absent_experts"]) + 2 * 2 * float(
        stats["moe_tokens_per_held_expert"]) == 2 * 64 * 3
    assert float(stats["moe_max_tokens_per_held_expert"]) >= float(
        stats["moe_tokens_per_held_expert"])
    assert float(stats["hc_res_row_sum_err_max"]) < 1e-5
    # a key no kind declares: averaged over the groups, a ``_max`` kept
    _, value, _ = _model_forward(model, p, dev)
    value = np.asarray(value).reshape(2, -1)
    np.testing.assert_allclose(stats["value_mean"], value.mean(1).mean(), rtol=1e-4)
    np.testing.assert_allclose(stats["value_abs_max"], np.abs(value).max(), rtol=1e-4)


def test_the_fused_lane_trains_the_lanes_in_groups_around_the_loss():
    """PPO on the token env, ``env_backend: jax``: the learn program
    takes a shard's streams through the whole stack and the loss in
    groups (``loss_groups``) and hands ``reduce_group_stats`` the loss's
    own statistics beside the model's; every one comes back one number
    an update."""
    from ray_tpu.algorithms.registry import get_algorithm_class

    lm = dict(small_config()["algo_config"]["model"]["sequence_lm"],
              max_position_embeddings=24)
    algo = get_algorithm_class("PPO")(config={
        "env": "TokenStreamJax-v0",
        "env_config": {"vocab_size": VOCAB, "episode_length": 24, "phase_stride": 1},
        "env_backend": "jax", "num_workers": 0, "num_envs_per_worker": 32,
        "rollout_fragment_length": 8, "train_batch_size": 256,
        "sgd_minibatch_size": 256, "num_sgd_iter": 1, "superstep": 1,
        "gamma": 1.0, "lambda": 0.95, "lr": 1e-6, "grad_clip": 1.0,
        "kl_coeff": 0.0, "entropy_coeff": 0.0, "seed": 3,
        "model": {"use_sequence_lm": True, "sequence_lm": lm, "max_seq_len": 8,
                  "dtype": "float32"},
    })
    try:
        policy = algo.get_policy()
        streams = 32 // policy.n_shards
        policy.model.learn_streams = max(1, streams // 2)
        assert policy.model.loss_groups(streams) == (2 if streams > 1 else 1)
        for _ in range(2):
            info = algo.train()["info"]["learner"]["default_policy"]
            for key in ("total_loss", "entropy", "moe_tokens_per_held_expert",
                        "moe_max_tokens_per_held_expert", "moe_rows_computed_share",
                        "hc_res_row_sum_err_max", "attn_key_blocks_skipped_share"):
                assert np.isfinite(info[key]) and np.ndim(info[key]) == 0, key
            assert "moe_held_load" not in info
            assert info["hc_res_row_sum_err_max"] < 1e-4
    finally:
        algo.cleanup()


def _model_plain():
    from tests.test_sequence_lm import small_config as qwen_small

    return SequenceLM(VOCAB, qwen_small()["algo_config"]["model"]["sequence_lm"])


def test_yarn_frequencies(setup):
    """(g) ``factor`` 1 is plain RoPE; the published block's
    frequencies and scale are the reference's: the fast dimensions keep
    ``theta^(-2i/d)``, the slow ones are divided by 64."""
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64.0)
    np.testing.assert_allclose(
        latent_attention.yarn_inv_freq(64, 10000.0, None), plain, rtol=1e-6)
    np.testing.assert_allclose(
        latent_attention.yarn_inv_freq(64, 10000.0, dict(YARN, factor=1)), plain,
        rtol=1e-6)
    got = latent_attention.yarn_inv_freq(64, 10000.0, YARN)
    np.testing.assert_allclose(got, ref.yarn_inv_freq(64, 10000.0, YARN), rtol=1e-6)
    np.testing.assert_allclose(got[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(got[-8:], plain[-8:] / 64.0, rtol=1e-6)
    assert np.all(np.diff(got) < 0)
    scale = latent_attention.yarn_softmax_scale(192, YARN)
    assert abs(scale - 192 ** -0.5 * 1.4159 ** 2) < 1e-4
    z = dict(ref.sizes(setup[0], VOCAB), dn=128, R=64)
    assert abs(ref.softmax_scale(z) - scale) < 1e-9
    assert latent_attention.yarn_softmax_scale(192, None) == 192 ** -0.5
    # RoPE at position 0 is the identity, and a rotation keeps the norm
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 3, 4, 8)), jnp.float32)
    inv = latent_attention.yarn_inv_freq(8, 10000.0, YARN)
    pos = jnp.asarray([[0, 5, 900], [0, 1, 2]])
    turned = latent_attention.rope(x, pos, inv)
    np.testing.assert_allclose(turned[:, 0], x[:, 0], atol=1e-6)
    np.testing.assert_allclose(
        jnp.linalg.norm(turned, axis=-1), jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    np.testing.assert_allclose(turned, ref._rope(x, pos, inv), atol=1e-6)


def test_the_forms_are_counted(setup):
    from ray_tpu.telemetry import metrics

    config, params, model, batch = setup
    before = metrics.mla_decode_lowerings()
    paths = dict(metrics.attention_fragment_lowerings())
    _model_forward(model, params, batch)
    state = _f32_state(ref.batch_state(batch))
    model.apply(params, jnp.zeros((4, 1, 1), jnp.int32), state)
    after = metrics.mla_decode_lowerings()
    # the fragment form traces a block once for the layers that share
    # it (the dense layer, and the two expert layers together); the
    # one-token form traces every layer
    assert after.get("expanded", 0) - before.get("expanded", 0) == 2
    assert after.get("absorbed", 0) - before.get("absorbed", 0) == 3
    # off a TPU no fragment takes the kernel, and the one-token form is
    # no fragment
    assert after.get("absorbed_fragment", 0) == before.get("absorbed_fragment", 0)
    now = metrics.attention_fragment_lowerings()
    assert now.get("xla", 0) - paths.get("xla", 0) == 2
    assert now.get("kernel", 0) == paths.get("kernel", 0)
