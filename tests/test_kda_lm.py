"""The sequence model's Kimi-Delta-Attention kind, its latent attention
with no query latent and its group-limited router (models/sequence_lm,
ops/deltanet.py, ops/latent_attention.py, ops/moe.py) held to the plain
reference (perf/reference/ling3.py) on seeded weights at a small size:
hidden 32, 4 heads of 16, latent 24 with head parts 16 / 8 / 12, 16
router outputs in 4 groups of which 2 are kept, 2 layers cut out of 42
by published index.
"""

import importlib.util
import json
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from ray_tpu.models.sequence_lm import KDALayer, LatentLayer, SequenceLM, describe
from ray_tpu.ops import deltanet, moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 64
T = 32
CONFIG_FILE = os.path.join(ROOT, "perf", "configs", "ling_3_0_flash_125b_a5b_ppo.json")


def _reference():
    path = os.path.join(ROOT, "perf", "reference", "ling3.py")
    spec = importlib.util.spec_from_file_location("ref_ling3", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _reference()


def small_config(held=(0, 4), **over):
    """Hidden 32; published layers 5 and 6 of 42 (L K: a latent layer
    where ``(i + 1) % 6 == 0``), the first over a dense feed-forward, the
    second over experts; 16 router outputs in 4 groups, 2 kept, 4 held:
    one whole group."""
    lm = {
        "model_type": "bailing_hybrid", "hidden_size": 32, "num_hidden_layers": 2,
        "layer_indices": [5, 6], "published_num_hidden_layers": 42,
        "layer_group_size": 6, "first_k_dense_replace": 1,
        "num_attention_heads": 4, "head_dim": 16, "short_conv_kernel_size": 4,
        "kda_lower_bound": -5, "kda_safe_gate": True, "no_kda_lora": True,
        "gated_attention_proj_granularity_type": "head_wise",
        "q_lora_rank": None, "kv_lora_rank": 24, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 12, "rope_theta": 6000000,
        "rope_scaling": None, "rope_interleave": True,
        "rms_norm_eps": 1e-6, "max_position_embeddings": 64,
        "intermediate_size": 48, "moe_intermediate_size": 16,
        "moe_shared_expert_intermediate_size": 16, "num_shared_experts": 1,
        "num_experts": held[1], "router_outputs": 16, "experts_held": list(held),
        "n_group": 4, "topk_group": 2, "num_experts_per_tok": 3,
        "norm_topk_prob": True, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "topk_method": "noaux_tc",
        "tie_word_embeddings": False,
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


def _model(lm, chunk=T):
    model = SequenceLM(VOCAB, lm, dtype="float32")
    model.chunk = chunk  # two sub-blocks of 16 a chunk
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


# -- the rule with a decay a key channel (ops/deltanet.py) -----------------------


def _rule_inputs(b=2, t=128, h=2, dk=32, dv=24, seed=0, lower=-5.0):
    rng = np.random.default_rng(seed)
    f32 = lambda *s: rng.standard_normal(s).astype(np.float32)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    q, k, v = unit(f32(b, t, h, dk)) * dk ** -0.5, unit(f32(b, t, h, dk)), f32(b, t, h, dv)
    # the bounded gate with most channels near one of its ends, as a
    # seeded ``A`` in (1, 16) makes them
    g = (lower / (1.0 + np.exp(-8.0 * f32(b, t, h, dk)))).astype(np.float32)
    beta = (1.0 / (1.0 + np.exp(-f32(b, t, h)))).astype(np.float32)
    resets = np.zeros((b, t), np.float32)
    if t > 70:  # inside chunks, and on a chunk's first row
        resets[0, 37] = resets[1, 64] = resets[1, 70] = 1.0
    return 0.1 * f32(b, h, dk, dv), q, k, v, g, beta, resets


def _recurrence(state, q, k, v, g, beta, resets):
    """Token by token: the four lines, the state zeroed where an episode
    opens."""
    def token(s, xs):
        q_t, k_t, v_t, g_t, beta_t, r_t = xs
        s = jnp.where(r_t[:, None, None, None] > 0.5, 0.0, s)
        s, o = deltanet._delta_step_body(s, q_t, k_t, v_t, g_t, beta_t)
        return s, o

    swap = lambda x: jnp.swapaxes(jnp.asarray(x), 0, 1)
    state, outs = jax.lax.scan(
        token, jnp.asarray(state), tuple(swap(x) for x in (q, k, v, g, beta, resets)))
    return jnp.swapaxes(outs, 0, 1), state


@pytest.mark.parametrize("chunk", [64, 32, 16, 8])
def test_channel_chunked_form_equals_the_recurrence(chunk):
    """Four sub-blocks of 16 a chunk (Kimi Linear's), two, one, and a
    chunk shorter than a sub-block, resets inside a chunk, inside a
    sub-block and on a chunk's first row: outputs, end state and the
    gradient of every operand against the token-by-token recurrence."""
    s0, q, k, v, g, beta, resets = _rule_inputs()
    with jax.default_matmul_precision("highest"):
        want_o, want_s = _recurrence(s0, q, k, v, g, beta, resets)
        got_o, got_s = deltanet.gated_delta_chunked(
            s0, q, k, v, g, beta, resets=resets, chunk=chunk)
        np.testing.assert_allclose(got_o, want_o, atol=2e-5)
        np.testing.assert_allclose(got_s, want_s, atol=2e-5)
        if chunk != 64:
            return
        s0, q, k, v, g, beta, resets = (x[:, :64] if x.ndim > 1 and x.shape[1] == 128
                                        else x for x in (s0, q, k, v, g, beta, resets))
        loss = lambda form: lambda *a: jnp.sum(jnp.sin(form(*a)[0])) + jnp.sum(form(*a)[1])
        chunked = lambda s, q, k, v, g, beta: deltanet.gated_delta_chunked(
            s, q, k, v, g, beta, resets=resets, chunk=chunk)
        plain = lambda *a: _recurrence(*a, resets)
        args = tuple(jnp.asarray(x) for x in (s0, q, k, v, g, beta))
        want = jax.grad(loss(plain), argnums=range(6))(*args)
        got = jax.grad(loss(chunked), argnums=range(6))(*args)
    for name, a, b in zip(("state", "q", "k", "v", "g", "beta"), got, want):
        assert float(jnp.linalg.norm(a - b)) < 2e-5 * float(jnp.linalg.norm(b)), name


def test_gates_at_the_bound_stay_finite():
    """``g`` = -5 on EVERY channel for 64 tokens: inside a sub-block the
    keys are scaled by up to ``exp(75)``, which float32 holds; value and
    gradient are finite and the recurrence's."""
    s0, q, k, v, _, beta, _ = _rule_inputs(t=64, seed=1)
    g = np.full(q.shape, -5.0, np.float32)
    with jax.default_matmul_precision("highest"):
        want_o, want_s = _recurrence(s0, q, k, v, g, beta, np.zeros((2, 64), np.float32))
        form = lambda g: deltanet.gated_delta_chunked(s0, q, k, v, g, beta, chunk=64)
        got_o, got_s = form(jnp.asarray(g))
        grad = jax.grad(lambda g: jnp.sum(jnp.square(form(g)[0])))(jnp.asarray(g))
    assert np.all(np.isfinite(got_o)) and np.all(np.isfinite(grad))
    np.testing.assert_allclose(got_o, want_o, atol=2e-5)
    np.testing.assert_allclose(got_s, want_s, atol=2e-5)
    # 16 x 5 = 80 is the edge: a sub-block of 64 such rows would not be
    assert np.isfinite(np.float32(np.exp(np.float32(75.0))))
    assert not np.isfinite(np.exp(np.float32(63 * 5.0)))


@pytest.mark.parametrize("form", ["step", "chunked"])
def test_equal_channels_reproduce_the_scalar_rule(form):
    """A decay a head, repeated over the head's key channels, through the
    per-channel text gives what the scalar text gives, to rounding."""
    s0, q, k, v, g, beta, resets = _rule_inputs(dk=128, dv=128, t=64, seed=2)
    scalar = g[..., 0]
    wide = np.broadcast_to(scalar[..., None], g.shape)
    with jax.default_matmul_precision("highest"):
        if form == "step":
            want = deltanet.gated_delta_step(s0, q[:, 0], k[:, 0], v[:, 0],
                                             scalar[:, 0], beta[:, 0])
            got = deltanet.gated_delta_step(s0, q[:, 0], k[:, 0], v[:, 0],
                                            wide[:, 0], beta[:, 0])
        else:
            want = deltanet.gated_delta_chunked(s0, q, k, v, scalar, beta, resets=resets)
            got = deltanet.gated_delta_chunked(s0, q, k, v, wide, beta, resets=resets)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-6)


# -- the group-limited router (ops/moe.py) -----------------------------------------


def _todays_route_top_k(x, router_kernel, k, renormalise, scoring="softmax",
                        select_bias=None, scale=1.0):
    """``ops/moe.route_top_k`` as it stood before it read the groups
    (PR 60), line for line."""
    hi = jax.lax.Precision.HIGHEST
    logits = jnp.dot(x.astype(jnp.float32), router_kernel.astype(jnp.float32),
                     precision=hi)
    scores = jax.nn.softmax(logits, axis=-1) if scoring == "softmax" else (
        jax.nn.sigmoid(logits))
    if select_bias is None:
        weights, indices = jax.lax.top_k(scores, k)
    else:
        _, indices = jax.lax.top_k(
            scores + jax.lax.stop_gradient(select_bias.astype(jnp.float32)), k)
        weights = jnp.take_along_axis(scores, indices, axis=-1)
    if renormalise:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    if scale != 1.0:
        weights = weights * scale
    return indices.astype(jnp.int32), weights


@pytest.mark.parametrize("options", [
    pytest.param({}, id="softmax-qwen3next"),
    pytest.param({"scoring": "sigmoid", "scale": 2.0, "bias": True}, id="noaux_tc-xing4"),
    pytest.param({"scoring": "sigmoid", "scale": 2.5, "bias": True, "n_group": 1,
                  "topk_group": 1}, id="noaux_tc-nemotron-states-one-group"),
    pytest.param({"scoring": "sigmoid", "scale": 2.5}, id="sigmoid-laguna"),
])
def test_one_group_is_todays_top_k_bit_for_bit(options):
    options = dict(options)
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(keys[0], (96, 32))
    kernel = jax.random.normal(keys[1], (32, 64)) * 32 ** -0.5
    bias = 0.02 * jax.random.normal(keys[2], (64,)) if options.pop("bias", False) else None
    groups = {k: options.pop(k) for k in ("n_group", "topk_group") if k in options}
    want_i, want_w = _todays_route_top_k(x, kernel, 4, True, select_bias=bias, **options)
    got_i, got_w, chosen = moe.route(x, kernel, 4, True, select_bias=bias,
                                     **options, **groups)
    assert chosen is None
    assert np.array_equal(got_i, want_i) and np.array_equal(got_w, want_w)


@pytest.mark.parametrize("experts, n_group, topk_group, k", [
    pytest.param(512, 8, 4, 8, id="ling3-8-groups-of-64-keep-4-top-8"),
    pytest.param(16, 4, 2, 3, id="small-4-groups-of-4-keep-2-top-3"),
])
def test_group_limited_router_equals_the_reference(experts, n_group, topk_group, k):
    """Ids, weights and the groups kept against the reference's written-out
    choice; every chosen expert lies in a kept group; the bias picks and
    never weighs, and takes no gradient."""
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    x = jax.random.normal(keys[0], (200, 32))
    p = {"router": jax.random.normal(keys[1], (32, experts)) * 32 ** -0.5,
         "select_bias": 0.05 * jax.random.normal(keys[2], (experts,))}
    z = {"groups": n_group, "groups_kept": topk_group, "top_k": k, "norm_topk": True,
         "route_scale": 2.5}
    want_i, want_w, want_groups = ref.route(p, x, z)
    got_i, got_w, got_groups = moe.route(
        x, p["router"], k, True, scoring="sigmoid", select_bias=p["select_bias"],
        scale=2.5, n_group=n_group, topk_group=topk_group)
    assert np.array_equal(got_groups, want_groups)
    assert np.all(np.sum(np.asarray(got_groups), axis=-1) == topk_group)
    assert np.array_equal(np.sort(got_i, -1), np.sort(want_i, -1))
    np.testing.assert_allclose(np.sort(got_w, -1), np.sort(want_w, -1), rtol=1e-6)
    size = experts // n_group
    assert np.all(np.take_along_axis(np.asarray(got_groups), np.asarray(got_i) // size, -1))
    # not the plain top-k: some token's best expert lies in a dropped group
    plain_i, _ = moe.route(x, p["router"], k, True, scoring="sigmoid",
                                 select_bias=p["select_bias"], scale=2.5)[:2]
    assert not np.array_equal(np.sort(plain_i, -1), np.sort(got_i, -1))
    np.testing.assert_allclose(jnp.sum(got_w, -1), 2.5, rtol=1e-5)
    grad = jax.grad(lambda b: jnp.sum(jnp.square(moe.route(
        x, p["router"], k, True, scoring="sigmoid", select_bias=b, scale=2.5,
        n_group=n_group, topk_group=topk_group)[1])))(p["select_bias"])
    assert not np.any(np.asarray(grad))


def test_expert_shares_add_up_to_the_uncut_layer():
    """The parts the 4 shares of a layer give (each holds ONE group of 4
    of the 16 experts), the shared expert counted once, add up to the
    uncut reference's layer; a share's tokens are those whose kept groups
    hold its group, and the others send it nothing."""
    config = small_config(held=(0, 16))
    z = ref.sizes(config, VOCAB)
    p = ref.init_params(jax.random.PRNGKey(1), config, VOCAB)["layer_1"]
    x = jnp.asarray(np.random.default_rng(2).standard_normal((2, T, 32)), jnp.float32)
    shares = []
    with jax.default_matmul_precision("highest"):
        whole, _ = ref._experts(p, x, z, lambda v: v)
        shared_only, _ = ref._experts(
            {**p, "experts_down": jnp.zeros_like(p["experts_down"])}, x, z, lambda v: v
        )
        total = shared_only
        for first in range(0, 16, 4):
            lm = small_config(held=(first, 4))["algo_config"]["model"]["sequence_lm"]
            sl = slice(first, first + 4)
            share = {**p, **{k: p[k][sl] for k in
                             ("experts_gate", "experts_up", "experts_down")}}
            part, _, stats = _model(lm).segments[-1].ffn.apply(
                share, x, (), {"scope": "", "dtype": jnp.float32})
            total = total + (part - shared_only)
            assert float(stats["moe_held_load"].sum()
                         + stats["moe_slots_on_absent_experts"]) == 2 * T * 3
            shares.append(float(stats["moe_held_group_chosen_share"]))
            # a token outside the share's group sends it nothing
            routed = np.asarray(part - shared_only).reshape(2 * T, -1)
            _, _, groups = ref.route(p, x.reshape(2 * T, -1), z)
            assert not np.any(routed[~np.asarray(groups)[:, first // 4]])
    np.testing.assert_allclose(total, whole, atol=2e-5)
    # each token keeps 2 of the 4 groups
    assert sum(shares) == pytest.approx(2.0) and all(0.1 < s < 0.9 for s in shares)


def test_grouped_product_meets_the_group_limited_load():
    """4,096-token shapes scaled down: the held eight are an eighth of
    ONE of 8 groups, so about half the tokens send nothing here by the
    group choice alone and the others load the buffers unevenly; the
    grouped form is the dense form's result and no buffer is outgrown."""
    experts, k, held, t, d, f = 512, 8, 8, 1024, 32, 16
    keys = jax.random.split(jax.random.PRNGKey(11), 6)
    x = jax.random.normal(keys[0], (t, d), jnp.float32)
    indices, weights, groups = moe.route(
        x, jax.random.normal(keys[1], (d, experts)) * d ** -0.5, k, True,
        scoring="sigmoid", scale=2.5, n_group=8, topk_group=4,
        select_bias=0.02 * jax.random.normal(keys[5], (experts,)))
    stacks = (jax.random.normal(keys[2], (held, d, f)) * d ** -0.5,
              jax.random.normal(keys[3], (held, d, f)) * d ** -0.5,
              jax.random.normal(keys[4], (held, f, d)) * f ** -0.5)
    per_expert, absent = moe.expert_load(indices, 0, held)
    assert 0.4 < float(jnp.mean(groups[:, 0])) < 0.6
    # nothing from a token that dropped group 0
    assert not np.any(np.asarray(indices)[~np.asarray(groups[:, 0])] < 64)
    assert float(jnp.max(per_expert)) <= moe.expert_buffer_rows(t, k, experts)
    assert float(per_expert.sum() + absent) == t * k
    want = moe.dense_experts_product(
        x, *stacks, moe.held_combine_weights(indices, weights, 0, held),
        dtype=jnp.float32)
    got = moe.grouped_experts_product(
        x, *stacks, indices, weights, per_expert, 0, experts, dtype=jnp.float32)
    np.testing.assert_allclose(got, want, atol=1e-5 * float(jnp.abs(want).max()))


# -- the policy against the reference ------------------------------------------------


def test_param_shapes_and_state_match_the_reference(setup):
    config, params, model, _ = setup
    want = {g: {k: v.shape for k, v in leaves.items()} for g, leaves in params.items()}
    assert model.param_shapes() == want
    made = model.init(jax.random.PRNGKey(0))
    assert jax.tree_util.tree_map(lambda x: x.shape, made) == want
    z = ref.sizes(config, VOCAB)
    assert [(s.shape, s.dtype) for s in _f32_state(model.initial_state(5))] == [
        (s.shape, s.dtype) for s in _f32_state(ref.initial_state(z, 5))]
    # the latent layer: ONE leaf of latent + rope numbers a position and
    # no query latent's leaves; a KDA layer: the matrix and three tails
    assert model.initial_state(5)[0].shape == (5, 64, 24 + 8)
    assert [s.shape for s in model.initial_state(5)[1:5]] == [
        (5, 4, 16, 16)] + [(5, 3, 64)] * 3
    assert "q_proj" in want["layer_0"] and not {"q_a", "q_a_norm", "q_b"} & set(
        want["layer_0"])


def test_one_token_steps_equal_the_reference_forward(setup):
    """Token by token through the carried state (the rollout's form: the
    per-channel step, the absorbed latent product) against the
    reference's forward, an episode ending inside the second stream's
    fragment."""
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
        want = jax.jit(lambda st: ref.forward(
            params, tokens, st, fresh, config, VOCAB))(start)
        state, logits, values = start, [], []
        step = jax.jit(lambda tok, st, r: model.apply(params, tok, st, resets=r))
        for i in range(T):
            lg, v, state = step(
                jnp.asarray(tokens[:, i : i + 1, None]), state,
                jnp.asarray(fresh[:, i : i + 1], jnp.float32))
            logits.append(lg)
            values.append(v)
        # the fragment form from the same start: the chunked rule
        frag_logits, frag_values, frag_state = jax.jit(lambda st: model.apply(
            params, jnp.asarray(tokens[..., None]), st,
            resets=jnp.asarray(fresh, jnp.float32)))(start)
    np.testing.assert_allclose(
        jnp.stack(logits, 1), want["logits"], atol=3e-4, rtol=3e-4)
    np.testing.assert_allclose(jnp.stack(values, 1), want["value"], atol=3e-4, rtol=3e-4)
    np.testing.assert_allclose(
        frag_logits.reshape(n, T, -1), want["logits"], atol=3e-4, rtol=3e-4)
    np.testing.assert_allclose(frag_values.reshape(n, T), want["value"], atol=3e-4)
    depth = np.asarray(want["state"][-1])
    for other in (state, frag_state):
        assert np.array_equal(np.asarray(other[-1]), depth)
        for got, leaf in zip(other[:-1], want["state"][:-1]):
            if leaf.ndim == 3 and leaf.shape[1] == 64:  # the rows below the position
                for s in range(n):
                    np.testing.assert_allclose(
                        got[s, : depth[s]], leaf[s, : depth[s]], atol=2e-4)
            else:
                np.testing.assert_allclose(got, leaf, atol=2e-4)


def test_a_reset_clears_the_matrix_and_the_tails_and_leaves_the_cache(setup):
    _, _, model, _ = setup
    state = tuple(jnp.ones_like(s) for s in model.initial_state(2))
    after = model.reset_state(state, jnp.asarray([True, False]))
    kinds = [type(seg.mixer) for seg, leaves in model._by_segment(state)
             for _ in leaves]
    assert kinds.count(KDALayer) == 4 and kinds.count(LatentLayer) == 1
    for kind, leaf in zip(kinds, after[:-1]):
        assert bool(jnp.all(leaf[1] == 1))
        assert bool(jnp.all(leaf[0] == (1 if kind is LatentLayer else 0)))
    assert after[-1].tolist() == [0, 1]


def test_loss_and_every_gradient_leaf_match_reference(setup):
    """One update's loss and gradient: the model's fragment form under the
    reference's loss against the reference's own, leaf by leaf; the
    routes are the reference's, and the bias gets no gradient."""
    config, params, model, batch = setup
    dev = {k: jnp.asarray(v) for k, v in batch.items()}

    def system_loss(p):
        logits, value, _ = _model_forward(model, p, batch)
        return ref.ppo_loss(logits, value, dev, config["algo_config"])

    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.jit(jax.value_and_grad(
            lambda p: ref.loss(p, dev, config)))(params)

        def routes_and_share(p):
            stats = {"moe_routes": None}
            _model_forward(model, p, batch, stats)
            return stats

        stats = jax.jit(routes_and_share)(params)
        out = jax.jit(lambda p: ref.forward(
            p, batch["obs"].reshape(-1, T), _f32_state(ref.batch_state(batch)),
            batch["resets"].reshape(-1, T) > 0.5, config, VOCAB))(params)
        got_loss, got = jax.jit(jax.value_and_grad(system_loss))(params)
    assert np.array_equal(np.sort(np.asarray(stats["moe_routes"]), -1),
                          np.sort(np.asarray(out["routes"]), -1))
    assert 0.1 < float(stats["moe_held_group_chosen_share"]) < 0.9
    assert abs(float(got_loss) - float(want_loss)) < 1e-4 * abs(float(want_loss))
    whole = np.sqrt(sum(float(jnp.sum(g * g)) for g in jax.tree_util.tree_leaves(want)))
    for group in want:
        for leaf in want[group]:
            g, w = np.asarray(got[group][leaf]), np.asarray(want[group][leaf])
            err = np.linalg.norm(g - w) / max(np.linalg.norm(w), 1e-3 * whole)
            assert err < 2e-3, (group, leaf, err)
    assert float(np.abs(got["layer_1"]["select_bias"]).max()) == 0.0
    assert float(np.abs(want["layer_1"]["select_bias"]).max()) == 0.0


def test_the_state_control_rounds_the_matrix_alone(setup):
    """``precision="bf16_state"`` (the KDA matrix rounded to bfloat16
    after every token) moves the reference's KDA output and leaves the
    operands alone: the control of the one float32 quantity the model
    adds."""
    config, params, _, batch = setup
    z = ref.sizes(config, VOCAB)
    state = _f32_state(ref.batch_state(batch))
    x = jnp.asarray(np.random.default_rng(4).standard_normal((4, T, 32)), jnp.float32)
    fresh = jnp.asarray(batch["resets"].reshape(-1, T) > 0.5)
    with jax.default_matmul_precision("highest"):
        out = {p: ref._kda(params["layer_1"], x, state[1], state[2:5], fresh, z,
                           *ref._QUANT[p])[0] for p in ("float32", "bf16_state")}
    rel = float(jnp.linalg.norm(out["bf16_state"] - out["float32"])
                / jnp.linalg.norm(out["float32"]))
    assert 1e-4 < rel < 3e-2


def test_the_fused_lane_generates_and_trains_the_policy():
    """PPO on the token env, ``env_backend: jax``, through
    ``Algorithm.train()``: a rollout from carried matrices, tails and
    latent rows and one update in a dispatch; the router's statistics
    come back one number an update and feed the group counter."""
    from ray_tpu.algorithms.registry import get_algorithm_class
    from ray_tpu.telemetry import metrics

    lm = dict(small_config()["algo_config"]["model"]["sequence_lm"],
              max_position_embeddings=24)
    before = metrics.held_group_chosen().get("updates", 0)
    chunked_before = dict(metrics.deltanet_chunked_lowerings())
    algo = get_algorithm_class("PPO")(config={
        "env": "TokenStreamJax-v0",
        "env_config": {"vocab_size": VOCAB, "episode_length": 24, "phase_stride": 3},
        "env_backend": "jax", "num_workers": 0, "num_envs_per_worker": 8,
        "rollout_fragment_length": 8, "train_batch_size": 64,
        "sgd_minibatch_size": 64, "num_sgd_iter": 1, "superstep": 1,
        "gamma": 1.0, "lambda": 0.95, "lr": 1e-6, "grad_clip": 1.0,
        "kl_coeff": 0.0, "entropy_coeff": 0.0, "seed": 3,
        "model": {"use_sequence_lm": True, "sequence_lm": lm, "max_seq_len": 8,
                  "dtype": "float32"},
    })
    try:
        info = algo.train()["info"]["learner"]["default_policy"]
        for key in ("total_loss", "entropy", "moe_tokens_per_held_expert",
                    "moe_max_tokens_per_held_expert", "moe_rows_computed_share",
                    "moe_held_group_chosen_share", "attn_key_blocks_skipped_share"):
            assert np.isfinite(info[key]) and np.ndim(info[key]) == 0, key
        assert 0.1 < info["moe_held_group_chosen_share"] < 0.9
        state = algo._jax_rollout_engine._carry["state"]
        assert [s.shape[1:] for s in state[1:5]] == [(4, 16, 16)] + [(3, 64)] * 3
    finally:
        algo.cleanup()
    assert metrics.held_group_chosen()["updates"] - before == 1
    decays = metrics._totals_by_tag(metrics.DELTANET_STEP_LOWERINGS_TOTAL, "decay")
    assert decays.get("channel", 0) > 0
    # the learn form's KDA layers: a decay a channel keeps XLA's text on
    # every platform
    chunked = metrics.deltanet_chunked_lowerings()
    took = {k for k, v in chunked.items() if v != chunked_before.get(k, 0)}
    assert took == {"xla/channel"}


# -- the configuration -----------------------------------------------------------------


def test_describe_of_the_configuration_file():
    """The committed configuration: mixers by PUBLISHED index, five KDA
    to one latent in the period held, the leading dense layer once, no
    query latent, the router's groups."""
    with open(CONFIG_FILE) as f:
        config = json.load(f)
    lm = config["algo_config"]["model"]["sequence_lm"]
    d = describe(lm)
    kda, latent = "kimi_delta_attention", "latent_attention"
    assert d["layer_types"] == (kda,) * 4 + (latent,) + (kda,) * 2
    assert [(i + 1) % 6 == 0 for i in lm["layer_indices"]] == [
        kind == latent for kind in d["layer_types"]]
    assert d["layer_types"][1:].count(kda) == 5 and d["layer_types"][1:].count(latent) == 1
    assert d["ffn_types"] == ("dense",) + ("experts",) * 6
    mixers = [seg.mixer for seg in d["segments"]]
    assert mixers[0] == KDALayer(heads=32, dk=128, dv=128, conv=4, lower=-5.0)
    assert mixers[4].q_latent is None and mixers[4].interleave and mixers[4].gate
    assert (mixers[4].kv_latent, mixers[4].nope, mixers[4].rope_dim) == (512, 128, 64)
    assert mixers[4].softmax_scale == pytest.approx(192 ** -0.5)
    ffn = d["segments"][-1].ffn
    assert (ffn.router_outputs, ffn.held, ffn.top_k, ffn.n_group, ffn.topk_group) == (
        512, 8, 8, 8, 4)
    assert ffn.scoring == "sigmoid" and ffn.select_bias and ffn.scale == 2.5
    assert ffn.gated and ffn.activation == "silu" and ffn.shared_width == 768
    assert not ffn.shared_gated and d["segments"][0].ffn.width == 6144
    # the whole published model: 35 KDA layers to 7 latent ones
    whole = describe({k: v for k, v in lm.items() if k != "layer_indices"}
                     | {"num_hidden_layers": 42})
    assert whole["layer_types"].count(kda) == 35
    assert [i for i, k in enumerate(whole["layer_types"]) if k == latent] == [
        5, 11, 17, 23, 29, 35, 41]


DESCRIBED = {
    "groups_are_read_for_a_family_that_states_them": (
        {"n_group": 4, "topk_group": 2}, lambda ffn, _: (ffn.n_group, ffn.topk_group) == (4, 2)),
    "no_groups_stated_is_the_plain_top_k": (
        {"n_group": None, "topk_group": None},
        lambda ffn, _: (ffn.n_group, ffn.topk_group) == (1, 1)),
    "a_null_query_latent_is_no_query_latent": (
        {"q_lora_rank": None}, lambda _, latent: latent.q_latent is None
        and "q_proj" in latent.param_shapes(32)),
    "a_query_latent_is_kept_where_stated": (
        {"q_lora_rank": 20}, lambda _, latent: latent.q_latent == 20
        and {"q_a", "q_a_norm", "q_b"} <= set(latent.param_shapes(32))),
}


@pytest.mark.parametrize("case", sorted(DESCRIBED))
def test_describe_reads_what_the_config_states(case):
    over, holds = DESCRIBED[case]
    lm = small_config()["algo_config"]["model"]["sequence_lm"]
    lm = {k: v for k, v in {**lm, **over}.items()
          if not (k in ("n_group", "topk_group") and v is None)}
    d = describe(lm)
    assert holds(d["segments"][-1].ffn, d["segments"][0].mixer)


def test_a_group_limited_nemotron_router_is_described_not_refused():
    """``nemotron_h`` states ``n_group`` and ``topk_group``: 1 and 1 is
    the plain top-k it always was, and another pair is read, not
    refused."""
    with open(os.path.join(ROOT, "perf", "configs",
                           "nemotron3_nano_30b_a3b_ppo.json")) as f:
        lm = json.load(f)["algo_config"]["model"]["sequence_lm"]
    ffn = [s.ffn for s in describe(lm)["segments"] if s.ffn.route_on][0]
    assert (ffn.n_group, ffn.topk_group) == (1, 1) and not ffn.gated
    assert ffn.activation == "relu2"
    grouped = [s.ffn for s in describe({**lm, "n_group": 2, "topk_group": 1})[
        "segments"] if s.ffn.route_on][0]
    assert (grouped.n_group, grouped.topk_group) == (2, 1)


def test_what_the_kda_kind_cannot_compute_is_refused_by_name():
    lm = small_config()["algo_config"]["model"]["sequence_lm"]
    for key, value in (("kda_safe_gate", False), ("use_kda_lora", True),
                       ("value_norm", True), ("num_kv_heads_for_linear_attn", 2)):
        with pytest.raises(ValueError, match=key):
            describe({**lm, key: value})
    with pytest.raises(ValueError, match="granularity"):
        describe({**lm, "gated_attention_proj_granularity_type": "elementwise"})


@pytest.mark.parametrize("lower, fits", [
    pytest.param(-5, True, id="published-bound"),
    pytest.param(-5.8, True, id="just-inside-float32"),
    pytest.param(-6, False, id="sub-block-overflows"),
    pytest.param(-88.0 / 15, False, id="at-the-floor"),
    pytest.param(0.0, False, id="no-decay-at-all"),
])
def test_a_bound_the_chunked_rule_cannot_hold_is_refused(lower, fits):
    """``exp(-(_SUB - 1) lower)`` must be finite in float32: a bound at
    or under ``CHANNEL_LOG_DECAY_FLOOR`` is refused where the layer is
    described, and every gate at a bound just inside it stays finite."""
    lm = small_config()["algo_config"]["model"]["sequence_lm"]
    if not fits:
        with pytest.raises(ValueError, match="kda_lower_bound"):
            describe({**lm, "kda_lower_bound": lower})
        return
    kda = describe({**lm, "kda_lower_bound": lower})["segments"][1].mixer
    assert kda.lower == lower > deltanet.CHANNEL_LOG_DECAY_FLOOR
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k, v = (jax.random.normal(key, (1, 32, 2, 16)) for key in keys)
    out, state = deltanet.gated_delta_chunked(
        jnp.zeros((1, 2, 16, 16)), q, k / jnp.linalg.norm(k, axis=-1, keepdims=True),
        v, jnp.full((1, 32, 2, 16), lower, jnp.float32), jnp.full((1, 32, 2), 0.5),
        chunk=32)
    assert bool(jnp.all(jnp.isfinite(out))) and bool(jnp.all(jnp.isfinite(state)))


def test_a_decay_a_head_keeps_a_body_the_channel_body_cannot_replace():
    """Why the delta rule's shared path is split and not adapted (PERF.md,
    PR 61): a decay a head multiplies the chunk's ``(C, C)`` products and
    holds any log-decay, and the families that state one leave it
    unbounded (``-exp(A_log) softplus(..)``); the same decays broadcast
    to 128 equal channels go through sub-blocks scaled by ``exp(-15 g)``,
    which leave float32 under ``CHANNEL_LOG_DECAY_FLOOR``."""
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    q, k, v = (jax.random.normal(key, (1, 64, 2, 16)) for key in keys[:3])
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    beta = jax.nn.sigmoid(jax.random.normal(keys[3], (1, 64, 2)))
    start = jnp.zeros((1, 2, 16, 16))
    g = jnp.full((1, 64, 2), -8.0)
    out, state = deltanet.gated_delta_chunked(start, q, k, v, g, beta)
    want = start
    for t in range(64):
        want, o = deltanet.gated_delta_step(
            want, q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t])
    np.testing.assert_allclose(state, want, atol=1e-6)
    np.testing.assert_allclose(out[:, -1], o, atol=1e-6)
    broadcast = jnp.broadcast_to(g[..., None], k.shape)
    assert float(g.min()) < deltanet.CHANNEL_LOG_DECAY_FLOOR
    out, _ = deltanet.gated_delta_chunked(start, q, k, v, broadcast, beta)
    assert not bool(jnp.all(jnp.isfinite(out)))
