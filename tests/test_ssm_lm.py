"""The sequence model's state-space and position-free attention kinds,
a model with no expert layer, the tied head and the Granite multipliers
(models/sequence_lm, ops/ssd.py) held to the plain reference
(perf/reference/granite4h.py) on seeded weights at a small size: hidden
32, six layers (mamba x 2, attention, mamba x 3: two stacked runs of
unequal length), 8 state-space heads of 8 with a state of 16, chunks of
8 in fragments of 16, a vocabulary of 64.

Tolerances. Both sides are float32 at precision "highest" here, so
they differ by summation order only (the chunked form against the
recurrence, the stored cache against the full score matrix): 3e-4 on
logits and values of order one, 2e-3 of a gradient leaf's norm. The
int8 and fp8 controls (the reference with rounded operands, one step
below the bfloat16 the configuration states) read 30 to 100 times
that, and the last test holds them to failing.
"""

import importlib.util
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from ray_tpu.models.sequence_lm import SequenceLM

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 64
T = 16
LOGIT_TOL = 3e-4
GRAD_LEAF_TOL = 2e-3


def _reference():
    path = os.path.join(ROOT, "perf", "reference", "granite4h.py")
    spec = importlib.util.spec_from_file_location("ref_granite4h", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _reference()


def small_config(**over):
    lm = {
        "hidden_size": 32, "num_hidden_layers": 6,
        "layer_types": ["mamba", "mamba", "attention", "mamba", "mamba", "mamba"],
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "attention_multiplier": 0.1, "embedding_multiplier": 12,
        "residual_multiplier": 0.22, "logits_scaling": 8,
        "mamba_n_heads": 8, "mamba_d_head": 8, "mamba_d_state": 16,
        "mamba_d_conv": 4, "mamba_expand": 2, "mamba_n_groups": 1,
        "mamba_chunk_size": 8, "mamba_conv_bias": True,
        "num_local_experts": 0, "num_experts_per_tok": 0,
        "shared_intermediate_size": 48, "intermediate_size": 48,
        "rms_norm_eps": 1e-5, "max_position_embeddings": 48,
        "tie_word_embeddings": True, "position_embedding_type": "nope",
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


def _model(config):
    model = SequenceLM(
        VOCAB, config["algo_config"]["model"]["sequence_lm"], dtype="float32")
    model.learn_streams = 2
    return model


def _f32_state(state):
    return tuple(jnp.asarray(s, jnp.float32 if s.dtype != np.int32 else jnp.int32)
                 for s in state)


@pytest.fixture(scope="module")
def setup():
    config = small_config()
    params = ref.init_params(jax.random.PRNGKey(7), config, VOCAB)
    batch = ref.make_batch(np.random.default_rng(3), config, 4 * T, VOCAB)
    return config, params, _model(config), batch


def _model_forward(model, params, batch, stats=None):
    rows = batch["obs"].shape[0]
    return model.apply(
        params, jnp.asarray(batch["obs"]).reshape(rows // T, T, 1),
        _f32_state(ref.batch_state(batch)),
        resets=jnp.asarray(batch["resets"]).reshape(rows // T, T),
        stats_out=stats,
    )


def _step_fn(model):
    """The one-token form, compiled once for a chain of steps."""
    return jax.jit(lambda p, tok, state, fresh: model.apply(
        p, tok, state, resets=fresh))


def _leaf_errors(got, want):
    whole = np.sqrt(sum(float(jnp.sum(g * g)) for g in jax.tree_util.tree_leaves(want)))
    return {
        (group, leaf): np.linalg.norm(
            np.asarray(got[group][leaf]) - np.asarray(want[group][leaf]))
        / max(np.linalg.norm(np.asarray(want[group][leaf])), 1e-3 * whole)
        for group in want for leaf in want[group]
    }


def test_param_tree_and_state_match_the_reference(setup):
    """Runs of state-space layers are one group with a leading layer
    axis (two and three layers here), the attention layer its own; no
    ``head`` group: the table is tied."""
    config, params, model, _ = setup
    want = {g: {k: v.shape for k, v in leaves.items()} for g, leaves in params.items()}
    assert model.param_shapes() == want
    assert sorted(want) == [
        "embed", "final_norm", "layer_2", "layers_0_1", "layers_3_5", "value"]
    assert want["layers_3_5"]["in_proj"] == (3, 32, 2 * 64 + 2 * 16 + 8)
    made = model.init(jax.random.PRNGKey(0))
    assert jax.tree_util.tree_map(lambda x: x.shape, made) == want
    z = ref.sizes(config, VOCAB)
    assert [s.shape for s in model.initial_state(5)] == [
        s.shape for s in ref.initial_state(z, 5)]
    # a run's matrices and convolution tails, the layer axis after the stream's
    assert model.initial_state(5)[0].shape == (5, 2, 8, 8, 16)
    assert model.initial_state(5)[1].shape == (5, 2, 3, 64 + 2 * 16)


def test_the_policys_own_init_is_the_familys(setup):
    _, _, model, _ = setup
    made = model.init(jax.random.PRNGKey(5))["layers_3_5"]
    np.testing.assert_allclose(
        made["A_log"], np.broadcast_to(np.log(np.arange(1, 9)), (3, 8)), rtol=1e-6)
    assert float(jnp.abs(made["D"] - 1.0).max()) == 0.0
    step = jax.nn.softplus(made["dt_bias"])
    assert 1e-3 <= float(step.min()) and float(step.max()) <= 1e-1
    assert float(jnp.abs(made["conv_bias"]).max()) == 0.0
    assert 0.5 < float(jnp.std(made["in_proj"])) * np.sqrt(32) < 2.0


def test_one_token_steps_through_the_carried_state_equal_the_reference(setup):
    """Token by token through the carried state (the rollout's form:
    one scan over a run's layers, each reading and writing its slice of
    the run's state) against the reference's full forward, an episode
    ending inside the second stream's fragment."""
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
        state, logits, values, step = start, [], [], _step_fn(model)
        for i in range(T):
            lg, v, state = step(
                params, jnp.asarray(tokens[:, i : i + 1, None]), state,
                jnp.asarray(fresh[:, i : i + 1], jnp.float32))
            logits.append(lg)
            values.append(v)
    np.testing.assert_allclose(
        jnp.stack(logits, 1), want["logits"], atol=LOGIT_TOL, rtol=LOGIT_TOL)
    np.testing.assert_allclose(
        jnp.stack(values, 1), want["value"], atol=LOGIT_TOL, rtol=LOGIT_TOL)
    depth = np.asarray(state[-1])
    assert np.array_equal(depth, np.asarray(want["state"][-1]))
    for got, ref_leaf in zip(state[:-1], want["state"][:-1]):
        if got.ndim == 3:  # a cache: the rows of the episode so far
            for s in range(n):
                np.testing.assert_allclose(
                    got[s, : depth[s]], ref_leaf[s, : depth[s]], atol=2e-4)
        else:
            np.testing.assert_allclose(got, ref_leaf, atol=2e-4)


def test_fragment_form_equals_the_chain_of_one_token_steps(setup):
    """The chunked fragment form from a stored start state (two chunks
    of 8, a reset inside one) against the chain of steps: the PPO ratio
    divides one by the other."""
    config, params, model, batch = setup
    rows = batch["obs"].shape[0]
    n = rows // T
    tokens = jnp.asarray(batch["obs"]).reshape(n, T, 1)
    resets = jnp.asarray(batch["resets"]).reshape(n, T)
    assert float(resets.sum()) >= 1
    with jax.default_matmul_precision("highest"):
        logits, value, after = _model_forward(model, params, batch)
        state, chain, values = _f32_state(ref.batch_state(batch)), [], []
        step = _step_fn(model)
        for i in range(T):
            lg, v, state = step(params, tokens[:, i : i + 1], state, resets[:, i : i + 1])
            chain.append(lg)
            values.append(v)
    np.testing.assert_allclose(
        jnp.stack(chain, 1).reshape(rows, VOCAB), logits, atol=LOGIT_TOL, rtol=LOGIT_TOL)
    np.testing.assert_allclose(
        jnp.stack(values, 1).reshape(rows), value, atol=LOGIT_TOL, rtol=LOGIT_TOL)
    depth = np.asarray(after[-1])
    assert np.array_equal(np.asarray(state[-1]), depth)
    for a, b in zip(state[:-1], after[:-1]):
        if a.ndim == 3:
            for s in range(n):
                np.testing.assert_allclose(a[s, : depth[s]], b[s, : depth[s]], atol=2e-4)
        else:
            np.testing.assert_allclose(a, b, atol=2e-4)


def test_loss_and_every_gradient_leaf_match_reference(setup):
    """The model under the reference's loss against the reference's own
    loss and gradient, leaf by leaf, the tied table's among them."""
    config, params, model, batch = setup
    dev = {k: jnp.asarray(v) for k, v in batch.items()}

    def system_loss(p):
        logits, value, _ = _model_forward(model, p, batch)
        return ref.ppo_loss(logits, value, dev, config["algo_config"])

    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.jit(jax.value_and_grad(
            lambda p: ref.loss(p, dev, config)))(params)
        got_loss, got = jax.jit(jax.value_and_grad(system_loss))(params)
        out = ref.forward(
            params, batch["obs"].reshape(-1, T), _f32_state(ref.batch_state(batch)),
            batch["resets"].reshape(-1, T) > 0.5, config, VOCAB)
        logits, value, _ = _model_forward(model, params, batch)
    np.testing.assert_allclose(
        logits, out["logits"].reshape(-1, VOCAB), atol=LOGIT_TOL, rtol=LOGIT_TOL)
    np.testing.assert_allclose(
        value, out["value"].reshape(-1), atol=LOGIT_TOL, rtol=LOGIT_TOL)
    assert abs(float(got_loss) - float(want_loss)) < 1e-4 * abs(float(want_loss))
    assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(want)
    errors = _leaf_errors(got, want)
    assert ("embed", "embedding") in errors
    assert max(errors.values()) < GRAD_LEAF_TOL, max(errors, key=errors.get)
    # every parameter is trained: none of the recurrence's has a zero gradient
    for leaf in ("A_log", "D", "dt_bias", "conv", "conv_bias", "ssm_norm"):
        assert float(np.linalg.norm(got["layers_0_1"][leaf])) > 0


def test_the_tied_tables_gradient_is_the_sum_of_both_uses(setup):
    """With the head untied and set to the table's transpose the model
    computes the same function; the tied table's gradient is the
    embedding's plus the head's, transposed."""
    config, params, model, batch = setup
    untied_lm = dict(config["algo_config"]["model"]["sequence_lm"],
                     tie_word_embeddings=False)
    untied = SequenceLM(VOCAB, untied_lm, dtype="float32")
    untied.learn_streams = 2
    assert "head" in untied.param_shapes() and "head" not in model.param_shapes()
    wide = dict(params, head={"kernel": np.asarray(params["embed"]["embedding"]).T})

    def scalar(m):
        def f(p):
            logits, value, _ = _model_forward(m, p, batch)
            return jnp.sum(jnp.sin(logits)) + jnp.sum(value)
        return f

    with jax.default_matmul_precision("highest"):
        tied = jax.jit(jax.grad(scalar(model)))(params)
        both = jax.jit(jax.grad(scalar(untied)))(wide)
    np.testing.assert_allclose(
        tied["embed"]["embedding"],
        both["embed"]["embedding"] + both["head"]["kernel"].T, atol=1e-4, rtol=1e-4)


def test_a_model_with_no_expert_layer_reports_no_expert_statistic(setup):
    config, params, model, batch = setup
    assert all(kind == "dense" for kind in model.ffn_types)
    assert not hasattr(model, "router_outputs")
    stats = {}
    _model_forward(model, params, batch, stats)
    # beside it the attention layers' (no block is skipped by the XLA text)
    assert sorted(stats) == [
        "attn_decode_key_blocks_skipped_share", "attn_key_blocks_skipped_share",
        "ssm_dt_max"]
    assert float(stats["attn_key_blocks_skipped_share"]) == 0.0
    assert float(stats["attn_decode_key_blocks_skipped_share"]) == 0.0
    # the largest step size the update saw: softplus of the in-projection's
    # dt columns plus dt_bias
    assert 0.0 < float(stats["ssm_dt_max"]) < 10.0
    # asked for every token's expert set: the one feed-forward there is
    stats = {"moe_routes": None}
    _model_forward(model, params, batch, stats)
    out = ref.forward(
        params, batch["obs"].reshape(-1, T), _f32_state(ref.batch_state(batch)),
        batch["resets"].reshape(-1, T) > 0.5, config, VOCAB)
    assert stats["moe_routes"].shape == out["routes"].shape == (1, 4 * T, 1)
    assert not np.asarray(stats["moe_routes"]).any()
    # the telemetry that feeds the expert-load counters takes such stats
    from ray_tpu.telemetry import metrics

    metrics.note_expert_load([{"ssm_dt_max": 0.1}])


def test_the_multipliers_and_the_scale_are_applied(setup):
    """Each Granite multiplier changes the output, and leaving all of
    them out is another function: none is dropped."""
    config, params, model, batch = setup
    base, _, _ = _model_forward(model, params, batch)
    for key, other in (("embedding_multiplier", 1.0), ("residual_multiplier", 1.0),
                       ("logits_scaling", 1.0), ("attention_multiplier", 0.25)):
        changed = _model(small_config(**{key: other}))
        logits, _, _ = _model_forward(changed, params, batch)
        assert float(jnp.abs(logits - base).max()) > 1e-3, key
    np.testing.assert_allclose(
        _model_forward(_model(small_config(logits_scaling=1.0)), params, batch)[0] / 8.0,
        base, atol=1e-6, rtol=1e-5)


def test_a_run_of_layers_is_traced_once(setup):
    """The one-token form counts one traced step a RUN (two here), not
    one a layer (five); the fragment form counts none."""
    from ray_tpu.telemetry import metrics

    config, params, model, batch = setup
    before = metrics.ssm_step_lowerings().get("xla", 0)
    _model_forward(model, params, batch)
    assert metrics.ssm_step_lowerings().get("xla", 0) == before
    model.apply(params, jnp.zeros((4, 1, 1), jnp.int32),
                _f32_state(ref.batch_state(batch)))
    assert metrics.ssm_step_lowerings()["xla"] == before + 2


def test_one_token_form_takes_the_kernel_on_a_tpu_and_the_body_here(monkeypatch):
    """At whole-tile sizes the model's one-token form counts one traced
    step a run and call site, ``kernel`` where the backend is a TPU and
    ``xla`` on the CPU, with nothing passed down to say so; on the TPU
    each run's matrices pass through the kernel's call whole and no
    equation of the program makes a layer's slice of them."""
    from ray_tpu.telemetry import metrics

    lm = small_config(mamba_d_state=128)["algo_config"]["model"]["sequence_lm"]
    model = SequenceLM(VOCAB, lm, dtype="float32")
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    state = jax.eval_shape(lambda: model.initial_state(2))
    obs = jax.ShapeDtypeStruct((2, 1, 1), jnp.int32)

    def traced():
        before = dict(metrics.ssm_step_lowerings())
        jaxpr = jax.make_jaxpr(lambda p, o, s: model.apply(p, o, s))(params, obs, state)
        after = metrics.ssm_step_lowerings()
        return jaxpr, {k: after.get(k, 0) - before.get(k, 0) for k in ("kernel", "xla")}

    jaxpr, took = traced()
    assert took == {"kernel": 0, "xla": 2}
    assert "pallas_call" not in str(jaxpr)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jaxpr, took = traced()
    assert took == {"kernel": 2, "xla": 0}

    def equations(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for inner in jax.core.jaxprs_in_params(eqn.params):
                yield from equations(inner)

    made = [
        (eqn.primitive.name, var.aval.shape)
        for eqn in equations(jaxpr.jaxpr) for var in eqn.outvars
        if getattr(var.aval, "shape", ())[-3:] == (8, 8, 128)
    ]
    # the leaves (2, layers, 8, 8, 128) leave the kernel's call, its jit
    # and the scans that carry them, and nothing makes a (2, 8, 8, 128)
    assert {name for name, _ in made} == {"pallas_call", "jit", "scan"}
    assert {shape for _, shape in made} == {(2, 2, 8, 8, 128), (2, 3, 8, 8, 128)}
    assert sum(name == "pallas_call" for name, _ in made) == 2


def test_reset_state_clears_the_recurrence_and_keeps_the_cache(setup):
    config, params, model, batch = setup
    state = _f32_state(ref.batch_state(batch))
    mask = jnp.asarray([True, False, True, False])
    after = model.reset_state(state, mask)
    for a, b in zip(after[:-1], state[:-1]):
        if a.ndim == 3:  # keys and values stay: only slots below the position are read
            assert np.array_equal(np.asarray(a), np.asarray(b))
        else:
            assert float(jnp.abs(a[0]).max()) == 0.0 and float(jnp.abs(a[2]).max()) == 0.0
            assert np.array_equal(np.asarray(a[1]), np.asarray(b[1]))
    assert list(np.asarray(after[-1])[[0, 2]]) == [0, 0]


@pytest.mark.parametrize("precision", ["int8", "fp8"])
def test_the_controls_fail_the_tolerances(setup, precision):
    """The reference computed one precision step below the bfloat16 the
    configuration states, in the system's place, fails the logit
    tolerance and the gradient's."""
    config, params, _, batch = setup
    dev = {k: jnp.asarray(v) for k, v in batch.items()}
    tokens = batch["obs"].reshape(-1, T)
    start = _f32_state(ref.batch_state(batch))
    fresh = batch["resets"].reshape(-1, T) > 0.5
    with jax.default_matmul_precision("highest"):
        want = ref.forward(params, tokens, start, fresh, config, VOCAB)
        low = ref.forward(params, tokens, start, fresh, config, VOCAB, precision)
        want_g = jax.jit(jax.grad(lambda p: ref.loss(p, dev, config)))(params)
        low_g = jax.jit(jax.grad(lambda p: ref.loss(p, dev, config, precision)))(params)
    assert float(jnp.abs(low["logits"] - want["logits"]).max()) > 10 * LOGIT_TOL
    assert max(_leaf_errors(low_g, want_g).values()) > 10 * GRAD_LEAF_TOL
