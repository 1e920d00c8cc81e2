"""The sequence model's attention as ONE body over a per-layer description
(models/sequence_lm.AttentionLayer), at the geometry Laguna-XS.2 forces:
a head count a layer (4 query heads on a full layer, 6 on a window
layer, 2 KV heads of 16 in both), RoPE by the layer's kind (YaRN on HALF
the head of a full layer, plain RoPE on the whole head of a window
layer), an output gate a head on BOTH kinds (so a gated layer on a
ring), a window of 8 in episodes of 32 with fragments of 16, a leading
dense layer by ``mlp_layer_types``, a sigmoid router over 8 outputs
(top-3, renormalised, times 2.5) of which 2 are held and an ungated
shared expert; held to the plain reference (perf/reference/laguna.py)
on seeded weights.

Every start state has EVERY slot of every cache filled with rows of
order one (``make_state``): a row that must not be seen is there to be
seen.

Tolerances. Both sides are float32 at precision "highest" here, so they
differ by summation order only: 3e-4 on logits and values of order one,
2e-3 of a gradient leaf's norm. Each variant that is wrong on purpose
reads 30 times the logit tolerance and more.
"""

import dataclasses
import importlib.util
import json
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from ray_tpu.models.sequence_lm import AttentionLayer, SequenceLM
from ray_tpu.ops import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 64
T = 16
WINDOW = 8
EPISODE = 32
LOGIT_TOL = 3e-4
GRAD_LEAF_TOL = 2e-3
FULL, SLIDING = "full_attention", "sliding_attention"
KINDS = [FULL, SLIDING, SLIDING, SLIDING, FULL]


def _load(kind, name):
    path = os.path.join(ROOT, "perf", *kind, name + ".py")
    spec = importlib.util.spec_from_file_location("laguna_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load(("reference",), "laguna")
byte_model = _load((), "mixed_attention_model")


def small_config(**over):
    lm = {
        "model_type": "laguna",
        "hidden_size": 32, "num_hidden_layers": 5, "intermediate_size": 48,
        # the published lists are longer than the layers run
        "layer_types": KINDS + KINDS[1:],
        "num_attention_heads_per_layer": [4, 6, 6, 6, 4, 6, 6, 6, 4],
        "mlp_layer_types": ["dense"] + ["sparse"] * 8,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "sliding_window": WINDOW, "gating": True, "attention_bias": False,
        "partial_rotary_factor": 0.5,
        "rope_parameters": {
            FULL: {"rope_theta": 100.0, "rope_type": "yarn", "factor": 4.0,
                   "original_max_position_embeddings": 8, "beta_fast": 2.0,
                   "beta_slow": 0.25, "attention_factor": 1.3,
                   "partial_rotary_factor": 0.5},
            SLIDING: {"rope_type": "default", "rope_theta": 10000.0,
                      "partial_rotary_factor": 1},
            "original_max_position_embeddings": 8,
        },
        "num_experts": 2, "router_outputs": 8, "experts_held": [0, 2],
        "num_experts_per_tok": 3, "moe_intermediate_size": 16,
        "shared_expert_intermediate_size": 16, "moe_routed_scaling_factor": 2.5,
        "rms_norm_eps": 1e-6, "max_position_embeddings": EPISODE,
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


def _model(config):
    model = SequenceLM(
        VOCAB, config["algo_config"]["model"]["sequence_lm"], dtype="float32")
    model.learn_streams = 2
    return model


def _f32_state(state):
    return tuple(jnp.asarray(s, jnp.float32 if s.dtype != np.int32 else jnp.int32)
                 for s in state)


def _apply_fn(model):
    return jax.jit(lambda p, tok, state, fresh: model.apply(
        p, tok, state, resets=fresh))


def _step_kernel_in_the_interpreter(monkeypatch):
    """What a TPU's rule would say, at this file's sizes: every layer's
    one-token form on the step kernel in the Pallas interpreter."""
    import functools

    from ray_tpu.ops import flash_attention

    monkeypatch.setattr(flash_attention, "step_kernel_applies", lambda *a: True)
    monkeypatch.setattr(
        flash_attention, "fragment_block_k",
        lambda depth, _=None: 8 if depth > WINDOW else 4)
    monkeypatch.setattr(
        flash_attention, "step_attention",
        functools.partial(flash_attention.step_attention, interpret=True))


@pytest.fixture(scope="module")
def setup():
    config = small_config()
    params = ref.init_params(jax.random.PRNGKey(7), config, VOCAB)
    batch = ref.make_batch(np.random.default_rng(3), config, 4 * T, VOCAB)
    model = _model(config)
    fns = {
        "apply": _apply_fn(model),
        "reference": jax.jit(lambda p, tok, state, fresh: ref.forward(
            p, tok, state, fresh, config, VOCAB)),
    }
    return config, params, model, batch, fns


def _chain(step, params, tokens, state, fresh):
    """Token by token through the carried state: ``(logits (N, T, V),
    values (N, T), state)``."""
    logits, values = [], []
    for i in range(tokens.shape[1]):
        lg, v, state = step(
            params, jnp.asarray(tokens[:, i : i + 1, None]), state,
            jnp.asarray(fresh[:, i : i + 1], jnp.float32))
        logits.append(lg)
        values.append(v)
    return jnp.stack(logits, 1), jnp.stack(values, 1), state


def _held_positions(pos, slots):
    """The position of the row a stream at ``pos`` holds in each slot
    of a cache of ``slots`` (below zero: none), slot by slot."""
    out = np.full((len(pos), slots), -1)
    for n, end in enumerate(pos):
        for p in range(int(end)):
            out[n, p % slots] = p
    return out


def _assert_states_agree(got, want, atol=2e-4):
    depth = np.asarray(want[-1])
    assert np.array_equal(np.asarray(got[-1]), depth)
    for a, b in zip(got[:-1], want[:-1]):
        assert a.shape == b.shape
        live = _held_positions(depth, a.shape[1]) >= 0
        np.testing.assert_allclose(
            np.asarray(a, np.float32)[live], np.asarray(b, np.float32)[live], atol=atol)


def _leaf_errors(got, want):
    whole = np.sqrt(sum(float(jnp.sum(g * g)) for g in jax.tree_util.tree_leaves(want)))
    return {
        (group, leaf): np.linalg.norm(
            np.asarray(got[group][leaf]) - np.asarray(want[group][leaf]))
        / max(np.linalg.norm(np.asarray(want[group][leaf])), 1e-3 * whole)
        for group in want for leaf in want[group]
    }


def _start(config, depths, seed):
    rng = np.random.default_rng(seed)
    n = len(depths)
    tokens = rng.integers(0, VOCAB, (n, T)).astype(np.int32)
    state = list(ref.make_state(rng, ref.sizes(config, VOCAB), n, T))
    state[-1] = np.asarray(depths, np.int32)
    fresh = np.zeros((n, T), bool)
    fresh[:, 0] = np.asarray(depths) == 0
    return tokens, _f32_state(state), fresh


# -- (a) the system against the reference ---------------------------------------


def test_every_layer_has_its_own_geometry_and_the_tree_matches(setup):
    """Five layers of their own: the full layers 4 heads with YaRN on
    half the head and the episode's rows, the window layers 6 heads with
    plain RoPE on the whole head and a ring of 8; a gate a head and q/k
    norms on both; layer 0 dense, the others routed with a shared
    expert and no gate on it."""
    config, params, model, _, _ = setup
    assert model.layer_types == tuple(KINDS)
    assert model.ffn_types == ("dense",) + ("experts",) * 4
    got = {seg.name: seg.mixer for seg in model.segments}
    assert [a.heads for a in got.values()] == [4, 6, 6, 6, 4]
    assert [a.rope for a in got.values()] == [
        "yarn", "default", "default", "default", "yarn"]
    assert [a.rotary for a in got.values()] == [8, 16, 16, 16, 8]
    assert [a.window for a in got.values()] == [None, 8, 8, 8, None]
    assert [a.theta for a in got.values()] == [100.0, 1e4, 1e4, 1e4, 100.0]
    assert all(a.gate == "head" and a.qk_norm and a.kv_heads == 2 for a in got.values())
    assert got["layer_0"].rope_factor == 1.3 and got["layer_1"].rope_factor == 1.0
    assert got["layer_1"] == got["layer_3"] and hash(got["layer_0"]) == hash(
        got["layer_4"])
    want = {g: {k: v.shape for k, v in leaves.items()} for g, leaves in params.items()}
    assert model.param_shapes() == want
    assert want["layer_0"]["g_proj"] == (32, 4) and want["layer_1"]["g_proj"] == (32, 6)
    assert want["layer_0"]["q_proj"] == (32, 64) and want["layer_2"]["q_proj"] == (32, 96)
    assert "mlp_gate" in want["layer_0"] and "router" not in want["layer_0"]
    assert "shared_expert_gate" not in want["layer_1"]
    made = model.init(jax.random.PRNGKey(0))
    assert jax.tree_util.tree_map(lambda x: x.shape, made) == want
    z = ref.sizes(config, VOCAB)
    shapes = [s.shape for s in model.initial_state(5)]
    assert shapes == [s.shape for s in ref.initial_state(z, 5)]
    assert shapes == ([(5, EPISODE, 32)] * 2 + [(5, WINDOW, 32)] * 6
                      + [(5, EPISODE, 32)] * 2 + [(5,)])


@pytest.mark.parametrize("start,forced", [(0, False), (21, False), (21, True)],
                         ids=["0", "21", "21_on_the_step_kernel"])
def test_one_token_steps_from_a_wrapped_ring_equal_the_reference(
        setup, monkeypatch, start, forced):
    """Token by token through the carried caches for an episode's length
    and on into the next (the ring of 8 wraps five times; a reset leaves
    the last episode's rows in it) against the reference's full masked
    forward; once more with every layer's step on the kernel (the rule
    forced, the interpreter), which reads a ring's leading slots by
    their number."""
    config, params, model, _, fns = setup
    if forced:
        _step_kernel_in_the_interpreter(monkeypatch)
        fns = {"apply": _apply_fn(model)}
    rng = np.random.default_rng(11 + start)
    n, steps = 3, EPISODE + 8
    tokens = rng.integers(0, VOCAB, (n, steps)).astype(np.int32)
    state = list(ref.make_state(rng, ref.sizes(config, VOCAB), n, T))
    state[-1] = np.asarray([start, start, 0], np.int32)
    fresh = np.zeros((n, steps), bool)
    fresh[:2, EPISODE - start] = True
    fresh[2, 0] = fresh[2, EPISODE] = True
    state = _f32_state(state)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, tok, st, fr: ref.forward(
            p, tok, st, fr, config, VOCAB))(params, tokens, state, fresh)
        logits, values, after = _chain(fns["apply"], params, tokens, state, fresh)
    np.testing.assert_allclose(logits, want["logits"], atol=LOGIT_TOL, rtol=LOGIT_TOL)
    np.testing.assert_allclose(values, want["value"], atol=LOGIT_TOL, rtol=LOGIT_TOL)
    _assert_states_agree(after, want["state"])


@pytest.mark.parametrize("depths,reset_at", [
    ((0, 3, 5), None),      # below the window
    ((8, 8, 7), None),      # at it
    ((13, 9, 16), None),    # past it: the ring has wrapped
    ((16, 12, 3), 5),       # an episode opens inside the fragment
], ids=["below", "at", "past", "reset"])
def test_fragment_form_from_a_stored_ring_equals_reference_and_steps(
        setup, depths, reset_at):
    """The fragment form (16 tokens from a stored start state, twice the
    window) against the reference's full forward AND against the chain
    of one-token steps: the PPO ratio divides one form by the other."""
    config, params, model, _, fns = setup
    tokens, state, fresh = _start(config, depths, sum(depths))
    n = len(depths)
    if reset_at is not None:
        fresh[0, reset_at] = True
        fresh[2, reset_at + 2] = True
    with jax.default_matmul_precision("highest"):
        want = fns["reference"](params, tokens, state, fresh)
        logits, value, after = fns["apply"](
            params, jnp.asarray(tokens[..., None]), state,
            jnp.asarray(fresh, jnp.float32))
        chain_logits, chain_values, chain_after = _chain(
            fns["apply"], params, tokens, state, fresh)
    for got_l, got_v in ((logits.reshape(n, T, -1), value.reshape(n, T)),
                         (chain_logits, chain_values)):
        np.testing.assert_allclose(
            got_l, want["logits"], atol=LOGIT_TOL, rtol=LOGIT_TOL)
        np.testing.assert_allclose(got_v, want["value"], atol=LOGIT_TOL, rtol=LOGIT_TOL)
    _assert_states_agree(after, want["state"])
    _assert_states_agree(chain_after, want["state"])


def test_loss_and_every_gradient_leaf_match_reference(setup):
    config, params, model, batch, _ = setup
    dev = {k: jnp.asarray(v) for k, v in batch.items()}
    rows = batch["obs"].shape[0]
    assert float(batch["resets"].sum()) >= 1

    def system_loss(p):
        logits, value, _ = model.apply(
            p, dev["obs"].reshape(rows // T, T, 1), _f32_state(ref.batch_state(batch)),
            resets=dev["resets"].reshape(rows // T, T))
        return ref.ppo_loss(logits, value, dev, config["algo_config"])

    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.jit(jax.value_and_grad(
            lambda p: ref.loss(p, dev, config)))(params)
        got_loss, got = jax.jit(jax.value_and_grad(system_loss))(params)
    assert abs(float(got_loss) - float(want_loss)) < 1e-4 * abs(float(want_loss))
    assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(want)
    errors = _leaf_errors(got, want)
    assert max(errors.values()) < GRAD_LEAF_TOL, max(errors, key=errors.get)
    for layer, leaves in (("layer_0", ("g_proj", "q_norm", "mlp_gate", "k_proj")),
                          ("layer_2", ("g_proj", "k_norm", "router", "shared_up"))):
        for leaf in leaves:
            assert float(np.linalg.norm(got[layer][leaf])) > 0, (layer, leaf)


# -- (b) wrong on purpose ---------------------------------------------------------


def _with_layers(model, only=None, **changed):
    """``model`` with the named fields of its attention layers'
    descriptions replaced (``only``: of that kind's)."""
    model.segments = tuple(
        seg._replace(mixer=dataclasses.replace(seg.mixer, **changed))
        if only in (None, seg.mixer.kind) else seg for seg in model.segments)
    return model


def _swapped_thetas(model):
    theta = {FULL: 1e4, SLIDING: 100.0}
    model.segments = tuple(
        seg._replace(mixer=dataclasses.replace(seg.mixer, theta=theta[seg.mixer.kind]))
        for seg in model.segments)
    return model


def _with_experts(model, **changed):
    """``model`` with the named fields of its expert layers' description
    replaced."""
    model.segments = tuple(
        seg._replace(ffn=dataclasses.replace(seg.ffn, **changed))
        if seg.ffn.route_on else seg for seg in model.segments)
    return model


WRONG = {
    "window_plus_one": lambda m: _with_layers(m, only=SLIDING, window=WINDOW + 1),
    "yarn_factor_left_off": lambda m: _with_layers(m, only=FULL, rope_factor=1.0),
    "yarn_on_the_whole_head": lambda m: _with_layers(m, only=FULL, rotary=16),
    "plain_frequencies_on_the_full_layers": lambda m: _with_layers(
        m, only=FULL, yarn=()),
    "thetas_swapped": _swapped_thetas,
    "gate_left_out": lambda m: _with_layers(m, gate=None),
    "q_k_norm_left_out": lambda m: _with_layers(m, qk_norm=False),
    "scale_left_out": lambda m: _with_experts(m, scale=1.0),
    "softmax_router": lambda m: _with_experts(m, scoring="softmax"),
    "shared_expert_gated": lambda m: _with_experts(m, shared_gated=True),
}


@pytest.mark.parametrize("wrong", sorted(WRONG))
@pytest.mark.parametrize("form", ["fragment", "steps"])
def test_a_wrong_reading_fails_the_comparison(setup, wrong, form):
    """Each reads far outside the tolerance against the reference, in
    either form."""
    config, params, _, _, fns = setup
    model = WRONG[wrong](_model(config))
    given = params
    if wrong == "shared_expert_gated":
        # the gate's weights the wrong reading would bring
        rng = np.random.default_rng(1)
        given = {g: dict(l) for g, l in params.items()}
        for i in range(1, 5):
            given[f"layer_{i}"]["shared_expert_gate"] = rng.standard_normal(
                (32, 1)).astype(np.float32) / np.sqrt(32)
    tokens, state, fresh = _start(config, [13, 9, 16], 17)
    if wrong == "window_plus_one" and form == "steps":
        # a ring of 8 cannot show a step a ninth row: the steps open an
        # episode on a model whose ring has the wrong window's 9 slots
        model = _model(small_config(sliding_window=WINDOW + 1))
        tokens, _, fresh = _start(config, [0, 0, 0], 17)
        state = model.initial_state(3)
    apply = _apply_fn(model)
    with jax.default_matmul_precision("highest"):
        want = fns["reference"](
            params, tokens, state if state[2].shape[1] == WINDOW else _start(
                config, [0, 0, 0], 17)[1], fresh)
        if form == "fragment":
            logits = apply(given, jnp.asarray(tokens[..., None]), state,
                           jnp.asarray(fresh, jnp.float32))[0].reshape(3, T, -1)
        else:
            logits, _, _ = _chain(apply, given, tokens, state, fresh)
    assert float(jnp.abs(logits - want["logits"]).max()) > 30 * LOGIT_TOL


@pytest.mark.parametrize("form", ["fragment", "steps"])
def test_one_head_count_for_both_kinds_fails_the_comparison(setup, form):
    """A model that takes ``num_attention_heads`` for every layer asks
    for another tree (refused where the weights are loaded); given the
    window layers' first four heads it reads far outside the tolerance."""
    config, params, model, _, fns = setup
    lm = dict(config["algo_config"]["model"]["sequence_lm"])
    del lm["num_attention_heads_per_layer"]
    one = SequenceLM(VOCAB, lm, dtype="float32")
    one.learn_streams = 2
    assert [seg.mixer.heads for seg in one.segments] == [4] * 5
    assert one.param_shapes() != model.param_shapes()
    cut = {g: dict(l) for g, l in params.items()}
    for i in (1, 2, 3):
        layer = cut[f"layer_{i}"]
        layer["q_proj"], layer["o_proj"] = layer["q_proj"][:, :64], layer["o_proj"][:64]
        layer["g_proj"] = layer["g_proj"][:, :4]
    assert one.param_shapes() == jax.tree_util.tree_map(lambda x: x.shape, cut)
    tokens, state, fresh = _start(config, [13, 9, 16], 17)
    apply = _apply_fn(one)
    with jax.default_matmul_precision("highest"):
        want = fns["reference"](params, tokens, state, fresh)
        if form == "fragment":
            logits = apply(cut, jnp.asarray(tokens[..., None]), state,
                           jnp.asarray(fresh, jnp.float32))[0].reshape(3, T, -1)
        else:
            logits, _, _ = _chain(apply, cut, tokens, state, fresh)
    assert float(jnp.abs(logits - want["logits"]).max()) > 30 * LOGIT_TOL


def test_a_gate_a_dimension_is_another_tree(setup):
    """Read as ``qwen3_next``'s (no ``gating`` key: a gate a dimension
    out of ``q_proj`` on the full layers, none on the window layers) the
    model asks for another tree and is refused where the weights are
    loaded: ``H x D`` gate columns a full layer where the reference has
    ``H``, no ``g_proj`` anywhere."""
    config, _, model, _, _ = setup
    lm = dict(config["algo_config"]["model"]["sequence_lm"])
    del lm["gating"]
    other = SequenceLM(VOCAB, lm, dtype="float32")
    shapes = other.param_shapes()
    assert shapes != model.param_shapes()
    assert shapes["layer_0"]["q_proj"] == (32, 2 * 64)
    assert "g_proj" not in shapes["layer_0"] and "g_proj" not in shapes["layer_1"]
    assert "q_norm" not in shapes["layer_1"]
    assert [seg.mixer.gate for seg in other.segments] == [
        "element", None, None, None, "element"]


# -- (c) the shares add up --------------------------------------------------------


@pytest.mark.parametrize("tokens,top_k,lowering", [
    (24, 3, "dense"), (512, 1, "grouped")])
def test_the_four_shares_add_up_to_the_uncut_layer(tokens, top_k, lowering):
    """Four chips hold two experts each of a layer's eight: what their
    expert layers give for the same tokens (each routes over all eight
    and leaves out what it does not hold), with the shared expert, which
    every chip computes alike, counted once, adds up to the reference's
    uncut layer."""
    uncut = small_config(num_experts=8, experts_held=[0, 8],
                         num_experts_per_tok=top_k)
    z = ref.sizes(uncut, VOCAB)
    params = ref.init_params(jax.random.PRNGKey(3), uncut, VOCAB)["layer_1"]
    assert moe.product_lowering(tokens, top_k, 8) == lowering
    g = jnp.asarray(
        np.random.default_rng(tokens).standard_normal((1, tokens, 32)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, _ = ref._feed_forward(params, g, z, lambda v: v, True)
        shared = moe.gated_mlp(
            g[0], params["shared_gate"], params["shared_up"], params["shared_down"],
            dtype=jnp.float32)
        total = shared[None]
        for first in range(0, 8, 2):
            share = _model(small_config(
                experts_held=[first, 2], num_experts_per_tok=top_k))
            mine = {k: v[first : first + 2] if k.startswith("experts_") else v
                    for k, v in params.items()}
            out, _, load = share.segments[-1].ffn.apply(
                mine, g, (), {"scope": "", "dtype": jnp.float32})
            assert float(load["moe_held_load"].sum()
                         + load["moe_slots_on_absent_experts"]) == tokens * top_k
            total = total + (out - shared[None])
    assert float(jnp.abs(want - shared[None]).max()) > 0.1
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=1e-4)


# -- (d) the byte model's count ----------------------------------------------------


def test_param_count_of_the_byte_model_is_the_trees_and_the_references():
    """At the cell's own size, from shapes alone."""
    with open(os.path.join(
            ROOT, "perf", "configs", "laguna_xs2_33b_a3b_ppo.json")) as f:
        config = json.load(f)
    vocab = int(config["vocab_size"])
    count = lambda shapes: sum(
        int(np.prod(s)) for group in shapes.values() for s in group.values())
    model = SequenceLM(vocab, config["algo_config"]["model"]["sequence_lm"])
    want = count(model.param_shapes())
    assert byte_model.param_count(config, vocab) == want
    assert count(ref.param_shapes(config, vocab)) == want
    assert want == config["parameters_held"]
    in_products = sum(
        int(np.prod(s)) for group, leaves in model.param_shapes().items()
        for leaf, s in leaves.items()
        if len(s) >= 2 and leaf not in ("router", "embedding") and group != "value")
    assert byte_model.product_weight_count(config, vocab) == in_products
    # a stream's caches: two full layers' 4,096 rows, three rings of 512
    per_stream = sum(
        s.dtype.itemsize * s.size for s in model.initial_state(1)[:-1])
    assert per_stream == sum(byte_model.cache_bytes(config)) == 39_845_888


# -- (e) the family is read from its own keys ----------------------------------------


def test_lagunas_keys_are_not_read_as_qwen3_nexts(setup):
    """``num_experts``, ``moe_intermediate_size`` and
    ``shared_expert_intermediate_size`` are ``qwen3_next``'s names; with
    ``moe_routed_scaling_factor`` beside them the router is a sigmoid
    each, renormalised and scaled, and the shared expert has no gate.
    Without it the same keys read as before."""
    config, params, model, _, _ = setup
    ffn = model.segments[2].ffn
    assert (ffn.scoring, ffn.scale, ffn.shared_gated) == ("sigmoid", 2.5, False)
    assert ffn.norm_topk and not ffn.select_bias and ffn.route_on == "stream"
    assert ffn.shared_width == 16 and ffn.activation == "silu"
    lm = dict(config["algo_config"]["model"]["sequence_lm"])
    del lm["moe_routed_scaling_factor"]
    qwen = SequenceLM(VOCAB, lm, dtype="float32").segments[2].ffn
    assert (qwen.scoring, qwen.scale, qwen.shared_gated) == ("softmax", 1.0, True)
    # the route itself against the reference's
    x = jnp.asarray(np.random.default_rng(2).standard_normal((2, 5, 32)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        idx, w = ref._route(params["layer_2"], x, ref.sizes(config, VOCAB))
        got_idx, got_w, _ = ffn.route(params["layer_2"], x.reshape(10, 32))
    assert np.array_equal(np.asarray(idx), np.asarray(got_idx))
    np.testing.assert_allclose(got_w, w, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_w).sum(-1), 2.5, atol=1e-5)


# -- YaRN, the counter, the statistic ------------------------------------------------


def test_yarn_frequencies_are_hugging_faces_and_the_repos_agree():
    """Written out by hand for the small block: dim 8, base 100, factor
    4 over 8 positions, ramp from the correction dimension of 2
    rotations (below 0: 0) to that of a quarter (1.41: 2)."""
    rope = small_config()["rope_parameters"][FULL]
    inv, factor = ref.rope_frequencies(rope, 16)
    plain = 100.0 ** (-np.arange(4) / 4.0)
    ramp = np.array([0.0, 0.5, 1.0, 1.0])
    np.testing.assert_allclose(inv, plain / 4.0 * ramp + plain * (1 - ramp), rtol=1e-6)
    assert factor == 1.3
    from ray_tpu.ops import latent_attention

    np.testing.assert_allclose(
        latent_attention.yarn_inv_freq(8, 100.0, rope), inv, rtol=1e-6)
    # the published block: 64 of 128 dimensions, ramp between 5 and 16
    pub = {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
           "original_max_position_embeddings": 4096, "beta_slow": 1, "beta_fast": 64,
           "attention_factor": 1.4158883083359672, "partial_rotary_factor": 0.5}
    inv, factor = ref.rope_frequencies(pub, 128)
    plain = 500000.0 ** (-np.arange(32) / 32.0)
    assert inv.shape == (32,) and abs(factor - 1.4158883) < 1e-6
    np.testing.assert_allclose(inv[:6], plain[:6], rtol=1e-6)
    np.testing.assert_allclose(inv[16:], plain[16:] / 64.0, rtol=1e-6)
    assert np.all(inv[6:16] < plain[6:16]) and np.all(inv[6:16] > plain[6:16] / 64.0)
    # 0.1 ln(factor) + 1 where no attention_factor is stated
    del pub["attention_factor"]
    assert abs(ref.rope_frequencies(pub, 128)[1] - (0.1 * np.log(64) + 1)) < 1e-12


def test_counters_and_statistics_say_every_layer_got_its_geometry(setup):
    from ray_tpu.telemetry import metrics

    config, params, model, batch, _ = setup
    rows = batch["obs"].shape[0]
    before = dict(metrics.attention_layer_lowerings())
    ring = dict(metrics.window_cache_lowerings())
    stats = {}
    model.apply(
        params, jnp.asarray(batch["obs"]).reshape(rows // T, T, 1),
        _f32_state(ref.batch_state(batch)),
        resets=jnp.asarray(batch["resets"]).reshape(rows // T, T), stats_out=stats)
    after = metrics.attention_layer_lowerings()
    grown = {k: after[k] - before.get(k, 0) for k in after if after[k] != before.get(k, 0)}
    # the fragment form: the three window layers' checkpointed block is
    # one trace; the two full layers' differ in their feed-forward
    assert grown == {"full_attention/4/yarn": 2, "sliding_attention/6/default": 1}
    model.apply(params, jnp.zeros((4, 1, 1), jnp.int32),
                _f32_state(ref.batch_state(batch)))
    steps = metrics.attention_layer_lowerings()
    assert steps["full_attention/4/yarn"] - after["full_attention/4/yarn"] == 2
    assert steps["sliding_attention/6/default"] - after[
        "sliding_attention/6/default"] == 3
    now = metrics.window_cache_lowerings()
    assert now["fragment"] - ring.get("fragment", 0) == 1
    assert now["step"] - ring.get("step", 0) == 3
    # two held experts of eight, top-3, four streams a place: by hand
    # from the learn form's own routes
    routed = {"moe_routes": None}
    model.apply(
        params, jnp.asarray(batch["obs"]).reshape(rows // T, T, 1),
        _f32_state(ref.batch_state(batch)),
        resets=jnp.asarray(batch["resets"]).reshape(rows // T, T), stats_out=routed)
    routes = np.asarray(routed["moe_routes"]).reshape(4, rows // T, T, 3)
    touched = np.mean([
        [[np.any(routes[l, :, t] == e) for e in (0, 1)] for t in range(T)]
        for l in range(4)])
    assert 0.0 < touched < 1.0
    assert abs(float(stats["moe_decode_held_experts_touched_share"]) - touched) < 1e-6
    assert sorted(stats) == [
        "attn_decode_key_blocks_skipped_share",
        "attn_key_blocks_skipped_share", "moe_decode_held_experts_touched_share",
        "moe_max_tokens_per_held_expert", "moe_rows_computed_share",
        "moe_slots_on_absent_experts", "moe_tokens_per_held_expert",
        "window_rows_seen_mean"]
    # fed to the program's counter as the expert load is
    totals = dict(metrics.decode_held_experts_touched())
    metrics.note_expert_load([{k: float(v) for k, v in stats.items()}])
    got = metrics.decode_held_experts_touched()
    assert got["updates"] - totals.get("updates", 0) == 1
    assert abs(got["share"] - totals.get("share", 0) - touched) < 1e-6


@pytest.mark.parametrize("forced", [False, True])
def test_one_token_form_the_rings_on_the_text_the_full_layers_by_the_rule(
        setup, monkeypatch, forced):
    """The three gated rings' one-token calls (six query heads over two
    key heads) and the two gated full layers' (four, YaRN) lower to the
    step kernel where the rule says so, here in the interpreter with the
    cache of 32 rows as four key blocks of 8 and a ring's 8 as two of 4:
    the same logits, values and state; the learn form's statistic counts
    the blocks a step at each position skips."""
    from ray_tpu.telemetry import metrics

    config, params, model, batch, _ = setup
    rows = batch["obs"].shape[0]
    state = _f32_state(ref.batch_state(batch))
    tokens = jnp.asarray(batch["obs"]).reshape(rows // T, T, 1)
    want = model.apply(params, tokens[:, :1], state)
    if forced:
        _step_kernel_in_the_interpreter(monkeypatch)
    before = dict(metrics.attention_step_lowerings())
    got = model.apply(params, tokens[:, :1], state)
    now = metrics.attention_step_lowerings()
    assert now.get("kernel", 0) - before.get("kernel", 0) == (5 if forced else 0)
    assert now.get("xla", 0) - before.get("xla", 0) == (0 if forced else 5)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=3e-4, rtol=3e-4)
    stats = {}
    model.apply(
        params, tokens, state,
        resets=jnp.asarray(batch["resets"]).reshape(rows // T, T), stats_out=stats)
    share = float(stats["attn_decode_key_blocks_skipped_share"])
    assert (0.2 < share < 0.8) if forced else share == 0.0


def test_the_description_reads_the_other_families_as_before():
    """The three kinds the repo had are instances of the one body."""
    qwen = SequenceLM(VOCAB, {
        "hidden_size": 32, "num_hidden_layers": 4, "full_attention_interval": 4,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
        "partial_rotary_factor": 0.25, "rope_theta": 1e6,
        "linear_num_key_heads": 2, "linear_num_value_heads": 4,
        "linear_key_head_dim": 8, "linear_value_head_dim": 8,
        "linear_conv_kernel_dim": 4, "num_experts": 2, "num_experts_per_tok": 2,
        "moe_intermediate_size": 16, "shared_expert_intermediate_size": 16,
        "max_position_embeddings": 32})
    assert qwen.segments[3].mixer == AttentionLayer(
        kind=FULL, heads=4, kv_heads=2, head_dim=8, scale=8 ** -0.5, rotary=2,
        theta=1e6, gate="element", qk_norm=True)
    assert qwen.param_shapes()["layer_3"]["q_proj"] == (32, 64)
    window = SequenceLM(VOCAB, {
        "hidden_size": 32, "num_hidden_layers": 2,
        "sliding_window_layout": [0, 1], "rope_layout": [0, 1],
        "sliding_window_size": 8, "rope_theta": 1.5e6, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 8, "attention_multiplier": 0.5,
        "intermediate_size": 48, "max_position_embeddings": 32})
    mixers = {seg.name: seg.mixer for seg in window.segments}
    assert mixers == {
        "layer_0": AttentionLayer(
            kind="attention", heads=4, kv_heads=2, head_dim=8, scale=0.5,
            theta=1.5e6),
        "layer_1": AttentionLayer(
            kind=SLIDING, heads=4, kv_heads=2, head_dim=8, scale=8 ** -0.5,
            window=8, rotary=8, theta=1.5e6)}
    assert [a.scope for a in mixers.values()] == ["attn", "swa"]
    assert [a.rope for a in mixers.values()] == ["none", "default"]


@pytest.mark.parametrize("precision", ["int8", "fp8"])
def test_the_controls_fail_the_tolerances(setup, precision):
    config, params, _, batch, _ = setup
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
