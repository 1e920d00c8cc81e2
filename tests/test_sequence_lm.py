"""The sequence model (models/sequence_lm) held to the plain
reference (perf/reference/qwen3_next.py) on seeded weights at a small
size: logits, values, loss, every gradient leaf; the one-token
recurrence against the chunked form; the shares of an expert layer
against the uncut layer; the token env's rows.
"""

import functools
import importlib.util
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from ray_tpu.models.sequence_lm import SequenceLM
from ray_tpu.ops import deltanet, moe
from ray_tpu.telemetry import metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 64
T = 16


def _reference():
    path = os.path.join(ROOT, "perf", "reference", "qwen3_next.py")
    spec = importlib.util.spec_from_file_location("ref_qwen3_next", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _reference()


def small_config(held=(0, 2), **over):
    """Hidden 64, 8 experts of which 2 are held, 4 layers in the
    published pattern (3 linear : 1 full), a vocabulary of 64."""
    lm = {
        "hidden_size": 64, "num_hidden_layers": 4, "full_attention_interval": 4,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "partial_rotary_factor": 0.25, "rope_theta": 10000000,
        "rms_norm_eps": 1e-6, "max_position_embeddings": 48,
        "linear_num_key_heads": 2, "linear_num_value_heads": 4,
        "linear_key_head_dim": 8, "linear_value_head_dim": 8,
        "linear_conv_kernel_dim": 4,
        "num_experts": held[1], "router_outputs": 8, "experts_held": list(held),
        "num_experts_per_tok": 3, "norm_topk_prob": True,
        "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
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
    """float32 operands, DeltaNet chunks of 8 and learn groups of 2
    streams, so that a 16-token fragment of 4 streams runs several
    chunks and several groups."""
    model = SequenceLM(VOCAB, lm, dtype="float32")
    model.chunk, model.learn_streams = 8, 2
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
    state = _f32_state(ref.batch_state(batch))
    return model.apply(
        params, jnp.asarray(batch["obs"]).reshape(rows // T, T, 1), state,
        resets=jnp.asarray(batch["resets"]).reshape(rows // T, T),
        stats_out=stats,
    )


def test_param_shapes_match_the_reference(setup):
    config, params, model, _ = setup
    want = {g: {k: v.shape for k, v in leaves.items()} for g, leaves in params.items()}
    assert model.param_shapes() == want
    made = model.init(jax.random.PRNGKey(0))
    assert jax.tree_util.tree_map(lambda x: x.shape, made) == want


def test_forward_matches_reference(setup):
    config, params, model, batch = setup
    rows = batch["obs"].shape[0]
    with jax.default_matmul_precision("highest"):
        stats = {"moe_routes": None}
        logits, value, state = _model_forward(model, params, batch, stats)
        out = ref.forward(
            params, batch["obs"].reshape(rows // T, T), _f32_state(ref.batch_state(batch)),
            batch["resets"].reshape(rows // T, T) > 0.5, config, VOCAB,
        )
    np.testing.assert_allclose(
        logits, out["logits"].reshape(rows, VOCAB), atol=2e-4, rtol=2e-4
    )
    np.testing.assert_allclose(value, out["value"].reshape(rows), atol=2e-4, rtol=2e-4)
    # every token's top-k set, every layer
    assert np.array_equal(
        np.sort(np.asarray(stats["moe_routes"]), -1), np.sort(np.asarray(out["routes"]), -1)
    )
    # the end state: DeltaNet matrices, convolution inputs, positions;
    # cache slots below the end position
    kinds = ref.sizes(config, VOCAB)["kinds"]
    end = np.asarray(out["state"][-1])
    assert np.array_equal(np.asarray(state[-1]), end)
    for i, kind in enumerate(kinds):
        for a, b in zip(state[2 * i : 2 * i + 2], out["state"][2 * i : 2 * i + 2]):
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            if kind == ref.FULL:
                live = np.arange(a.shape[1])[None] < end[:, None]
                a, b = a[live], b[live]
            np.testing.assert_allclose(a, b, atol=2e-2 if kind == ref.FULL else 2e-4)


@pytest.mark.parametrize("rule", ["text", "kernel"])
def test_loss_and_every_gradient_leaf_match_reference(setup, rule, monkeypatch):
    """``rule``: the DeltaNet layers' fragment form as the CPU lowers it
    (XLA's text), and on the kernel pair a TPU takes (in the Pallas
    interpreter, at this size's small tiles)."""
    config, params, model, batch = setup
    dev = {k: jnp.asarray(v) for k, v in batch.items()}
    if rule == "kernel":
        monkeypatch.setattr(deltanet, "_chunked_kernel_applies", lambda *a: True)
        monkeypatch.setattr(
            deltanet, "gated_delta_chunked_kernel",
            functools.partial(deltanet.gated_delta_chunked_kernel, interpret=True))
    before = dict(metrics.deltanet_chunked_lowerings())

    def system_loss(p):
        logits, value, _ = _model_forward(model, p, batch)
        return ref.ppo_loss(logits, value, dev, config["algo_config"])

    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.value_and_grad(
            lambda p: ref.loss(p, dev, config)
        )(params)
        got_loss, got = jax.value_and_grad(system_loss)(params)
    took = {k for k, v in metrics.deltanet_chunked_lowerings().items()
            if v != before.get(k, 0)}
    assert took == {("kernel" if rule == "kernel" else "xla") + "/head"}
    assert abs(float(got_loss) - float(want_loss)) < 1e-4 * abs(float(want_loss))
    whole = np.sqrt(sum(float(jnp.sum(g * g)) for g in jax.tree_util.tree_leaves(want)))
    for group in want:
        for leaf in want[group]:
            g, w = np.asarray(got[group][leaf]), np.asarray(want[group][leaf])
            err = np.linalg.norm(g - w) / max(np.linalg.norm(w), 1e-3 * whole)
            assert err < 2e-3, (group, leaf, err)


def test_recurrence_matches_chunked_form_over_two_fragments(setup):
    """Token by token through ``T == 1`` (the rollout's form, state in
    and out, reset by hand) against two fragments of the learn form
    with a reset inside the second."""
    config, params, model, _ = setup
    rng = np.random.default_rng(11)
    b = 3
    tokens = rng.integers(0, VOCAB, (b, 2 * T))
    fresh = np.zeros((b, 2 * T), bool)
    fresh[:, 0] = True
    fresh[1, T + 5] = True  # inside the second fragment
    fresh[2, T] = True  # at its first token
    with jax.default_matmul_precision("highest"):
        state = model.initial_state(b)
        step = jax.jit(lambda s, tok, f: model.apply(
            params, tok[:, None, None], s, resets=f[:, None]))
        want_logits, want_values = [], []
        for t in range(2 * T):
            logits, value, state = step(
                state, jnp.asarray(tokens[:, t]), jnp.asarray(fresh[:, t], jnp.float32)
            )
            want_logits.append(logits)
            want_values.append(value)
        chunked = model.initial_state(b)
        got_logits, got_values = [], []
        for f in range(2):
            sl = slice(f * T, (f + 1) * T)
            logits, value, chunked = model.apply(
                params, jnp.asarray(tokens[:, sl])[..., None], chunked,
                resets=jnp.asarray(fresh[:, sl], jnp.float32),
            )
            got_logits.append(np.asarray(logits).reshape(b, T, VOCAB))
            got_values.append(np.asarray(value).reshape(b, T))
    np.testing.assert_allclose(
        np.concatenate(got_logits, 1), np.stack(want_logits, 1), atol=3e-4, rtol=3e-4
    )
    np.testing.assert_allclose(
        np.concatenate(got_values, 1), np.stack(want_values, 1), atol=3e-4, rtol=3e-4
    )
    for a, w in zip(chunked[:-1], state[:-1]):
        if a.ndim == 4:  # the DeltaNet matrices
            np.testing.assert_allclose(a, w, atol=3e-4)
    assert np.array_equal(np.asarray(chunked[-1]), np.asarray(state[-1]))


@pytest.mark.parametrize("reset_by", ["where", "decay"])
def test_delta_rule_chunked_equals_recurrence_with_resets(reset_by):
    """The recurrence with its state zeroed before a token that opens
    an episode, by a ``where`` (what the model's ``reset_state`` does)
    or by the step's own decay (``g = -inf``), against the chunked
    form."""
    rng = np.random.default_rng(5)
    b, t, h, dk, dv = 2, 24, 3, 8, 4
    q, k = (rng.standard_normal((b, t, h, dk)).astype(np.float32) for _ in range(2))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.standard_normal((b, t, h, dv)).astype(np.float32)
    g = -rng.uniform(0.01, 1.0, (b, t, h)).astype(np.float32)
    beta = rng.uniform(0.1, 0.9, (b, t, h)).astype(np.float32)
    resets = np.zeros((b, t), np.float32)
    resets[0, 5] = resets[0, 6] = resets[1, 16] = 1.0
    s0 = rng.standard_normal((b, h, dk, dv)).astype(np.float32)
    s, want = jnp.asarray(s0), []
    for i in range(t):
        gi = g[:, i]
        if reset_by == "where":
            s = jnp.where(resets[:, i, None, None, None] > 0.5, 0.0, s)
        else:
            gi = np.where(resets[:, i, None] > 0.5, -np.inf, gi)
        s, o = deltanet.gated_delta_step(s, q[:, i], k[:, i], v[:, i], gi, beta[:, i])
        want.append(o)
    got, s_end = deltanet.gated_delta_chunked(
        jnp.asarray(s0), q, k, v, g, beta, resets=jnp.asarray(resets), chunk=8
    )
    np.testing.assert_allclose(got, np.stack(want, 1), atol=2e-5)
    np.testing.assert_allclose(s_end, s, atol=2e-5)


def test_expert_shares_add_up_to_the_uncut_layer():
    """The parts the 4 expert shares of a layer give (2 of 8 experts
    each), with what every chip computes alike (the shared expert)
    counted once, add up to the uncut reference's layer output."""
    config = small_config(held=(0, 8))
    z = ref.sizes(config, VOCAB)
    p = ref.init_params(jax.random.PRNGKey(1), config, VOCAB)["layer_0"]
    x = jnp.asarray(np.random.default_rng(2).standard_normal((2, T, 64)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, _ = ref._experts(p, x, z, lambda v: v)
        shared_only, _ = ref._experts(
            {**p, "experts_down": jnp.zeros_like(p["experts_down"])}, x, z, lambda v: v
        )
        total = shared_only
        for first in range(0, 8, 2):
            lm = small_config(held=(first, 2))["algo_config"]["model"]["sequence_lm"]
            model = _model(lm)
            sl = slice(first, first + 2)
            share = {**p, **{k: p[k][sl] for k in
                             ("experts_gate", "experts_up", "experts_down")}}
            part, _, stats = model.segments[0].ffn.apply(
                share, x, (), {"scope": "", "dtype": jnp.float32})
            total = total + (part - shared_only)
            assert float(stats["moe_held_load"].sum()
                         + stats["moe_slots_on_absent_experts"]) == 2 * T * 3
    np.testing.assert_allclose(total, whole, atol=2e-5)


def test_held_combine_weights_and_load():
    idx = jnp.asarray([[0, 5, 7], [6, 2, 1]], jnp.int32)
    w = jnp.asarray([[0.5, 0.3, 0.2], [0.6, 0.3, 0.1]], jnp.float32)
    combine = moe.held_combine_weights(idx, w, 4, 4)  # experts 4..7
    np.testing.assert_allclose(combine, [[0, 0.3, 0, 0.2], [0, 0, 0.6, 0]])
    per_expert, absent = moe.expert_load(idx, 4, 4)
    np.testing.assert_allclose(per_expert, [0, 1, 1, 1])
    assert float(absent) == 3


# the two routers of the cells, small: (E, k, held, first, route's options)
ROUTERS = {
    "softmax_10_of_512_held_32_at_64": (512, 10, 32, 64, {}),
    "sigmoid_bias_scale_4_of_64_held_8": (
        64, 4, 8, 0, {"scoring": "sigmoid", "scale": 2.0, "select_bias": True}),
}


def _routing(name, indices, first, held):
    """The router's own indices, or a routing that a scheme with
    per-expert buffers gets wrong. A token's experts stay distinct."""
    t, k = indices.shape
    token = jnp.arange(t, dtype=jnp.int32)
    # every slot of every token on an expert past the held ones
    absent = jnp.broadcast_to(first + held + jnp.arange(k, dtype=jnp.int32), (t, k))
    if name in ("routed", "tokens_not_a_tile"):
        return indices
    if name == "all_on_one_expert":
        return absent.at[:, 0].set(first + 3)
    if name == "all_on_two_experts":
        return absent.at[:, 0].set(first + 3).at[:, 1].set(first + 1)
    if name == "all_on_four_experts":  # one more than run alone: dense
        return absent.at[:, :4].set(first + (3 + 2 * jnp.arange(4)) % held)
    if name == "none_held":
        return absent
    if name == "several_held_a_token":
        several = first + (token[:, None] + 3 * jnp.arange(3)) % held
        return absent.at[:, 1:4].set(several)
    if name == "empty_expert_between":  # held experts 0 and 2, never 1
        return absent.at[:, k - 1].set(first + 2 * (token % 2))
    raise ValueError(name)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("routing", [
    "routed", "all_on_one_expert", "all_on_one_expert_alone",
    "all_on_two_experts_alone", "all_on_four_experts_alone", "none_held",
    "several_held_a_token", "tokens_not_a_tile", "empty_expert_between",
])
@pytest.mark.parametrize("router", sorted(ROUTERS))
def test_grouped_product_equals_dense(router, routing, dtype):
    """The grouped form against the dense form: the result and the
    gradient of every operand. float32 operands leave the summation
    order alone between them; bfloat16 adds the rounding of a pair's
    row where the dense form rounds a token's."""
    experts, k, held, first, options = ROUTERS[router]
    t, d, f = (131 if routing == "tokens_not_a_tile" else 256), 32, 16
    keys = jax.random.split(jax.random.PRNGKey(11), 8)
    x = jax.random.normal(keys[0], (t, d), jnp.float32)
    options = dict(options)
    if options.pop("select_bias", False):
        options["select_bias"] = jax.random.normal(keys[6], (experts,)) * 0.02
    indices, weights = moe.route(
        x, jax.random.normal(keys[1], (d, experts)) * d ** -0.5, k, True, **options)[:2]
    # "..._alone": a layer told that three experts may outgrow their buffers
    alone = 3 if routing.endswith("_alone") else 0
    indices = _routing(routing.removesuffix("_alone"), indices, first, held)
    assert bool(jnp.all(jnp.sort(indices, -1)[:, 1:] != jnp.sort(indices, -1)[:, :-1]))
    stacks = (
        jax.random.normal(keys[2], (held, d, f)) * d ** -0.5,
        jax.random.normal(keys[3], (held, d, f)) * d ** -0.5,
        jax.random.normal(keys[4], (held, f, d)) * f ** -0.5,
    )
    ct = jax.random.normal(keys[5], (t, d), jnp.float32)

    def dense(x, wg, wu, wd, w):
        combine = moe.held_combine_weights(indices, w, first, held)
        return moe.dense_experts_product(x, wg, wu, wd, combine, dtype=dtype)

    per_expert, _ = moe.expert_load(indices, first, held)
    # which way the call goes: an expert with every token outgrows its
    # buffer and the call takes the dense form under the cond, or, told
    # so, that expert runs over every token beside the others' buffers
    # (four such experts: dense again); the two experts of
    # "empty_expert_between" fill theirs to the last row
    outgrown = int(jnp.sum(per_expert > moe.expert_buffer_rows(t, k, experts)))
    assert outgrown == {
        "all_on_one_expert": 1, "all_on_one_expert_alone": 1,
        "all_on_two_experts_alone": 2, "all_on_four_experts_alone": 4,
    }.get(routing, 0)
    want_rows = t * held if outgrown > alone else (
        held * moe.expert_buffer_rows(t, k, experts) + t * outgrown)
    assert float(moe.rows_computed(
        per_expert, t, k, experts, "grouped", alone)) == want_rows

    def grouped(x, wg, wu, wd, w):
        return moe.grouped_experts_product(
            x, wg, wu, wd, indices, w, per_expert, first, experts, dtype=dtype,
            alone=alone)

    want, want_vjp = jax.vjp(dense, x, *stacks, weights)
    got, got_vjp = jax.vjp(grouped, x, *stacks, weights)
    tol = 1e-5 if dtype == "float32" else 2e-2
    names = ("out", "x", "w_gate", "w_up", "w_down", "weights")
    for name, a, b in zip(names, (want,) + want_vjp(ct), (got,) + got_vjp(ct)):
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == b.shape and a.dtype == b.dtype, name
        np.testing.assert_allclose(
            b, a, atol=tol * max(1.0, np.abs(a).max()), rtol=0, err_msg=name)
    if routing == "none_held":
        assert not np.any(np.asarray(got))


@pytest.mark.parametrize("tokens,k,experts,want,buffer", [
    (64, 10, 512, "dense", 128),       # the Qwen3-Next cell's decode step
    (2048, 10, 512, "grouped", 128),   # its learn block of 16 fragments
    (1024, 4, 64, "grouped", 256),     # the Xing4 cell's group of 8 fragments
    (32, 4, 64, "dense", 128),         # its decode step
])
def test_product_lowering_rule_at_the_cells_sizes(tokens, k, experts, want, buffer):
    assert moe.product_lowering(tokens, k, experts) == want
    assert moe.expert_buffer_rows(tokens, k, experts) == buffer


def _reduced_qwen3_next():
    """Top-10 of 512 with 32 held at the widths of ``small_config``."""
    config = small_config(
        held=(0, 32), router_outputs=512, num_experts_per_tok=10,
        max_position_embeddings=256)
    return SequenceLM(VOCAB, config["algo_config"]["model"]["sequence_lm"])


def test_learn_form_counts_grouped_and_one_token_form_dense():
    """Tracing alone: a fragment of each of 16 streams, then a token of
    each, at the cell's routing and token counts."""
    from ray_tpu.telemetry import metrics

    model = _reduced_qwen3_next()
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    for t, path in ((128, "grouped"), (1, "dense")):
        tokens = jax.ShapeDtypeStruct((16, t, 1), jnp.int32)
        state = jax.eval_shape(lambda: model.initial_state(16))
        before = metrics.moe_product_lowerings()
        jax.eval_shape(model.apply, params, tokens, state)
        after = metrics.moe_product_lowerings()
        moved = {p: after.get(p, 0.0) - before.get(p, 0.0) for p in after}
        assert moved.pop(path) >= 1.0, (t, moved)
        assert not any(moved.values()), (t, moved)


def test_grouped_form_through_the_model_and_its_rows_computed_share(monkeypatch):
    """The learn form over 256 tokens takes the grouped product; its
    logits, value and every gradient leaf against the same model held
    to the dense form, and the share of the dense rows it computed:
    the buffers' rows where every held expert fits its own."""
    config = small_config(
        held=(2, 4), router_outputs=64, num_experts_per_tok=3,
        max_position_embeddings=96)
    lm = config["algo_config"]["model"]["sequence_lm"]
    model = _model(lm)
    model.learn_streams = 4
    params = ref.init_params(jax.random.PRNGKey(5), config, VOCAB)
    tokens = jax.random.randint(jax.random.PRNGKey(6), (4, 64, 1), 0, VOCAB)
    state = _f32_state(model.initial_state(4))
    assert moe.product_lowering(256, 3, 64) == "grouped"

    def outputs(params):
        stats = {"moe_routes": None}
        logits, value, _ = model.apply(params, tokens, state, stats_out=stats)
        return jnp.sum(jnp.sin(logits)) + jnp.sum(value), (logits, value, stats)

    with jax.default_matmul_precision("highest"):
        (_, (logits, value, stats)), grads = jax.value_and_grad(
            outputs, has_aux=True)(params)
        monkeypatch.setattr(moe, "product_lowering", lambda *a: "dense")
        (_, (logits_d, value_d, stats_d)), grads_d = jax.value_and_grad(
            outputs, has_aux=True)(params)
    np.testing.assert_allclose(logits, logits_d, atol=2e-5)
    np.testing.assert_allclose(value, value_d, atol=2e-5)
    for a, b in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(grads_d)):
        np.testing.assert_allclose(a, b, atol=2e-4 * max(1.0, float(jnp.abs(b).max())))
    assert float(stats_d["moe_rows_computed_share"]) == 1.0
    routes = np.asarray(stats["moe_routes"])  # (layers, tokens, k)
    buffer = moe.expert_buffer_rows(256, 3, 64)
    assert all(np.sum(layer == e) <= buffer for layer in routes for e in range(2, 6))
    np.testing.assert_allclose(float(stats["moe_rows_computed_share"]), buffer / 256)


def test_token_env_rows():
    from ray_tpu.env.jax_env import JaxVectorEnvAdapter
    from ray_tpu.env.registry import get_env_creator

    make = get_env_creator("TokenStreamJax-v0")
    env = make({"vocab_size": 64, "episode_length": 6, "phase_stride": 2})
    assert env.observation_space.shape == (1,) and env.action_space.n == 64
    keys = jnp.stack([jax.random.PRNGKey(i) for i in range(3)])
    state = jax.vmap(env.init_at)(keys, jnp.arange(3))
    state, obs = jax.vmap(env.reset)(state)
    assert list(np.asarray(state["t"])) == [0, 2, 4]  # env i starts 2 i tokens in
    assert obs.shape == (3, 1) and obs.dtype == jnp.int32
    prev = np.asarray(obs)[:, 0]
    seen_rewards = []
    for step in range(6):
        action = jnp.asarray([(7 * step + i) % 64 for i in range(3)], jnp.int32)
        t = np.asarray(state["t"])
        state, obs2, rew, term, trunc = jax.vmap(env.step)(state, action)
        want = ((prev * 31 + np.asarray(action) * 17 + t * 7) % 97) / 96.0 - 0.5
        np.testing.assert_allclose(rew, want, atol=1e-6)
        assert np.array_equal(np.asarray(obs2)[:, 0], np.asarray(action))
        assert list(np.asarray(term)) == list(t + 1 >= 6) and not np.any(trunc)
        seen_rewards.extend(np.asarray(rew))
        reset_state, reset_obs = jax.vmap(env.reset)(state)
        done = np.asarray(term)
        state = jax.tree_util.tree_map(
            lambda a, b: jnp.where(done.reshape((-1,) + (1,) * (a.ndim - 1)), a, b),
            reset_state, state,
        )
        prev = np.where(done, np.asarray(reset_obs)[:, 0], np.asarray(action))
    assert len(set(np.round(seen_rewards, 4))) > 4  # dense, not constant
    assert list(np.asarray(state["t"])) == [0, 2, 4]  # a later episode starts at 0
    # the host lane's adapter speaks the same env
    obs, _ = JaxVectorEnvAdapter(env, 2, seed=0).vector_reset()
    assert obs[0].shape == (1,)
