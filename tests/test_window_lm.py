"""The sequence model's sliding-window kind on its ring cache beside
position-free full attention, the router that reads the layer's input
before the mixer, ReGLU experts and an expert layer with no shared
expert (models/sequence_lm, ops/moe.py) held to the plain reference
(perf/reference/smallthinker.py) on seeded weights at a small size:
hidden 32, four layers (full, window, window, window), 4 heads of 8 over
2 KV heads, a window of 8 in episodes of 32, fragments of 16 (so a
fragment is longer than the window and wraps the ring twice), a router
over 8 experts of which 2 are held, top-3, a vocabulary of 64.

Every start state has EVERY slot of every cache filled with rows of
order one (``make_state``): a row that must not be seen is there to be
seen, so a stale ring row read, a window off by one or a slot taken for
a position moves the logits by far more than the tolerance.

Tolerances. Both sides are float32 at precision "highest" here, so they
differ by summation order only: 3e-4 on logits and values of order one,
2e-3 of a gradient leaf's norm. The wrong masks and the int8 / fp8
controls read 30 times that and more, and tests hold them to failing.
"""

import dataclasses
import importlib.util
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from ray_tpu.models.sequence_lm import SequenceLM
from ray_tpu.ops import cached_attention, moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 64
T = 16
WINDOW = 8
EPISODE = 32
LOGIT_TOL = 3e-4
GRAD_LEAF_TOL = 2e-3


def _reference():
    path = os.path.join(ROOT, "perf", "reference", "smallthinker.py")
    spec = importlib.util.spec_from_file_location("ref_smallthinker", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _reference()


def small_config(**over):
    lm = {
        "hidden_size": 32, "num_hidden_layers": 4,
        "sliding_window_layout": [0, 1, 1, 1, 0, 1, 1, 1],
        "rope_layout": [0, 1, 1, 1, 0, 1, 1, 1],
        "sliding_window_size": WINDOW, "rope_theta": 10000.0,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
        "moe_num_primary_experts": 2, "router_outputs": 8, "experts_held": [0, 2],
        "moe_num_active_primary_experts": 3, "moe_ffn_hidden_size": 16,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
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


@pytest.fixture(scope="module")
def setup():
    config = small_config()
    params = ref.init_params(jax.random.PRNGKey(7), config, VOCAB)
    batch = ref.make_batch(np.random.default_rng(3), config, 4 * T, VOCAB)
    model = _model(config)
    fns = {
        # either form: one token a call, or a fragment
        "apply": jax.jit(lambda p, tok, state, fresh: model.apply(
            p, tok, state, resets=fresh)),
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
    of a cache of ``slots`` (below zero: none), written out slot by
    slot."""
    out = np.full((len(pos), slots), -1)
    for n, end in enumerate(pos):
        for p in range(int(end)):
            out[n, p % slots] = p
    return out


def _assert_states_agree(got, want, atol=2e-4):
    """Position for position: every slot that holds a row of the
    episode so far (a ring's last ``slots`` of them)."""
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


def test_param_tree_and_state_match_the_reference(setup):
    """Four layers of their own, no ``shared_*`` leaf; the full layer
    holds the episode's rows, a window layer a ring of the window's."""
    config, params, model, _, _ = setup
    assert model.layer_types == (
        "attention", "sliding_attention", "sliding_attention", "sliding_attention")
    want = {g: {k: v.shape for k, v in leaves.items()} for g, leaves in params.items()}
    assert model.param_shapes() == want
    assert not any(k.startswith("shared") for k in want["layer_1"])
    assert want["layer_1"]["router"] == (32, 8)
    assert want["layer_1"]["experts_gate"] == (2, 32, 16)
    made = model.init(jax.random.PRNGKey(0))
    assert jax.tree_util.tree_map(lambda x: x.shape, made) == want
    z = ref.sizes(config, VOCAB)
    got = [s.shape for s in model.initial_state(5)]
    assert got == [s.shape for s in ref.initial_state(z, 5)]
    assert got == [(5, EPISODE, 16)] * 2 + [(5, WINDOW, 16)] * 6 + [(5,)]
    # a window wider than the episode never wraps: the ring is the episode's
    wide = _model(small_config(sliding_window_size=64))
    assert wide.initial_state(1)[2].shape == (1, EPISODE, 16)
    with pytest.raises(ValueError, match="window layer without RoPE"):
        _model(small_config(rope_layout=[1, 1, 1, 1]))


@pytest.mark.parametrize("start", [0, 21])
def test_one_token_steps_through_a_whole_episode_equal_the_reference(setup, start):
    """Token by token through the carried rings for an episode's length
    and on into the next episode (the rollout's form: the ring wraps
    three times and a reset leaves the last episode's rows in it)
    against the reference's full masked forward."""
    config, params, model, _, fns = setup
    rng = np.random.default_rng(11 + start)
    n, steps = 3, EPISODE + 8
    tokens = rng.integers(0, VOCAB, (n, steps)).astype(np.int32)
    z = ref.sizes(config, VOCAB)
    state = list(ref.make_state(rng, z, n, T))
    state[-1] = np.asarray([start, start, 0], np.int32)
    fresh = np.zeros((n, steps), bool)
    fresh[:2, EPISODE - start] = True  # the episode ends at its fixed length
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
    ((16, 16, 16), 11),
], ids=["below", "at", "past", "reset_early", "reset_late"])
def test_fragment_form_from_a_stored_ring_equals_reference_and_steps(
        setup, depths, reset_at):
    """The fragment form (16 tokens from a stored start state, twice
    the window) against the reference's full forward AND against the
    chain of one-token steps: the PPO ratio divides one form by the
    other."""
    config, params, model, _, fns = setup
    rng = np.random.default_rng(sum(depths))
    n = len(depths)
    tokens = rng.integers(0, VOCAB, (n, T)).astype(np.int32)
    z = ref.sizes(config, VOCAB)
    state = list(ref.make_state(rng, z, n, T))
    state[-1] = np.asarray(depths, np.int32)
    state = _f32_state(state)
    fresh = np.zeros((n, T), bool)
    fresh[:, 0] = np.asarray(depths) == 0
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


def test_the_ring_equals_a_full_depth_cache_under_the_same_mask(setup):
    """The same steps with each window layer's cache as deep as the
    episode (a ring that never wraps: slot = position, all 32 rows kept)
    and the window as a mask alone: the same logits, and every ring slot
    holds the row the deep cache has at the slot's position."""
    config, params, model, _, fns = setup
    rng = np.random.default_rng(5)
    n, steps = 2, 27
    tokens = rng.integers(0, VOCAB, (n, steps)).astype(np.int32)
    fresh = np.zeros((n, steps), bool)
    ring = model.initial_state(n)
    deep = tuple(jnp.zeros((n, EPISODE, 16), jnp.float32) for _ in ring[:-1]) + ring[-1:]
    with jax.default_matmul_precision("highest"):
        a_logits, _, a = _chain(fns["apply"], params, tokens, ring, fresh)
        b_logits, _, b = _chain(
            jax.jit(lambda p, tok, st, fr: model.apply(p, tok, st, resets=fr)),
            params, tokens, deep, fresh)
    np.testing.assert_allclose(a_logits, b_logits, atol=1e-5, rtol=1e-5)
    held = _held_positions(np.asarray(a[-1]), WINDOW)
    assert held.min() == steps - WINDOW  # wrapped: the last 8 positions
    for leaf, full in zip(a[2:-1], b[2:-1]):
        for s in range(n):
            np.testing.assert_allclose(leaf[s], full[s][held[s]], atol=1e-6)


def _with_layers(model, **changed):
    """``model`` with the named fields of its attention layers'
    descriptions replaced, where ``only`` (a kind) says which."""
    only = changed.pop("only")
    model.segments = tuple(
        seg._replace(mixer=dataclasses.replace(seg.mixer, **changed))
        if getattr(seg.mixer, "kind", None) == only else seg
        for seg in model.segments)
    return model


def _window_plus_one(config):
    # the ring keeps its 8 slots
    return _with_layers(_model(config), only="sliding_attention", window=WINDOW + 1)


def _rope_on_the_full_layer(config):
    return _with_layers(
        _model(config), only="attention", rotary=8, theta=10000.0)


def _router_on_the_normed_stream(config):
    model = _model(config)
    # routes where the other families do
    model.segments = tuple(
        seg._replace(ffn=dataclasses.replace(seg.ffn, route_on="stream"))
        for seg in model.segments)
    return model


@pytest.mark.parametrize("wrong", [
    _window_plus_one, _rope_on_the_full_layer, _router_on_the_normed_stream])
@pytest.mark.parametrize("form", ["fragment", "steps"])
def test_a_wrong_mask_fails_the_comparison(setup, wrong, form):
    """A window off by one, RoPE on the position-free layer, or the
    router fed the normed post-attention stream: each reads far outside
    the tolerance against the reference, in either form."""
    config, params, _, _, fns = setup
    model = wrong(config)
    rng = np.random.default_rng(17)
    n = 3
    tokens = rng.integers(0, VOCAB, (n, T)).astype(np.int32)
    state = list(ref.make_state(rng, ref.sizes(config, VOCAB), n, T))
    state[-1] = np.asarray([13, 9, 16], np.int32)
    fresh = np.zeros((n, T), bool)
    if wrong is _window_plus_one and form == "steps":
        # a ring of 8 cannot show a step a ninth row: the steps start an
        # episode on a model whose ring has the wrong window's 9 slots
        model = _model(small_config(sliding_window_size=WINDOW + 1))
        state[-1] = np.zeros(n, np.int32)
        fresh[:, 0] = True
    state = _f32_state(state)
    # the wrong model's own state where its ring is not the reference's
    own = model.initial_state(n)
    start = own if own[2].shape != state[2].shape else state
    apply = jax.jit(lambda p, tok, st, fr: model.apply(p, tok, st, resets=fr))
    with jax.default_matmul_precision("highest"):
        want = fns["reference"](params, tokens, state, fresh)
        if form == "fragment":
            logits = apply(params, jnp.asarray(tokens[..., None]), state,
                           jnp.asarray(fresh, jnp.float32))[0].reshape(n, T, -1)
        else:
            logits, _, _ = _chain(apply, params, tokens, start, fresh)
    assert float(jnp.abs(logits - want["logits"]).max()) > 30 * LOGIT_TOL


def test_loss_and_every_gradient_leaf_match_reference(setup):
    """The model under the reference's loss against the reference's own
    loss and gradient, leaf by leaf: the router's (through the weights
    alone: it reads the layer's input), the ReLU-gated experts', both
    attention kinds'."""
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
    for layer in ("layer_0", "layer_1"):
        for leaf in ("router", "experts_gate", "experts_down", "k_proj"):
            assert float(np.linalg.norm(got[layer][leaf])) > 0, (layer, leaf)


@pytest.mark.parametrize("tokens,top_k,lowering", [
    (24, 3, "dense"), (512, 1, "grouped")])
def test_the_eight_shares_add_up_to_the_uncut_layer(tokens, top_k, lowering):
    """Eight chips hold one expert each of a layer's eight: what their
    expert layers give for the same tokens and the same route (each
    routes over all eight and leaves out what it does not hold) adds up
    to the reference's uncut layer. There is no shared expert, so
    nothing is counted once."""
    uncut = small_config(
        moe_num_primary_experts=8, experts_held=[0, 8],
        moe_num_active_primary_experts=top_k)
    z = ref.sizes(uncut, VOCAB)
    params = ref.init_params(jax.random.PRNGKey(3), uncut, VOCAB)["layer_1"]
    assert moe.product_lowering(tokens, top_k, 8) == lowering
    rng = np.random.default_rng(tokens)
    x_in = jnp.asarray(rng.standard_normal((1, tokens, 32)), jnp.float32)
    g = jnp.asarray(rng.standard_normal((1, tokens, 32)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        idx, w = ref._route(params, x_in, z)
        want = ref._experts(params, g, idx, w, z, lambda v: v)
        total = jnp.zeros_like(want)
        for first in range(8):
            share = _model(small_config(
                moe_num_primary_experts=1, experts_held=[first, 1],
                moe_num_active_primary_experts=top_k))
            mine = {k: v[first : first + 1] if k.startswith("experts_") else v
                    for k, v in params.items()}
            ffn = share.segments[-1].ffn
            route = ffn.route(mine, x_in.reshape(tokens, 32))
            out, _, load = ffn.apply(
                mine, g, (), {"scope": "", "dtype": jnp.float32, "route": route})
            assert float(load["moe_held_load"].sum()
                         + load["moe_slots_on_absent_experts"]) == tokens * top_k
            total = total + out
    assert float(jnp.abs(want).max()) > 0.1
    np.testing.assert_allclose(total, want, atol=1e-5, rtol=1e-4)


def test_the_route_is_the_renormalised_softmax_top_k_of_the_layers_input(setup):
    """Softmax over the chosen logits (the reference, as the family
    writes it) is the softmax over all, top-k, renormalised
    (``ops/moe.route`` as the Qwen3-Next layer calls it); the
    model takes it from the block's input."""
    config, params, model, _, _ = setup
    z = ref.sizes(config, VOCAB)
    x = jnp.asarray(np.random.default_rng(2).standard_normal((2, 5, 32)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        idx, w = ref._route(params["layer_2"], x, z)
        ffn = model.segments[2].ffn
        got_idx, got_w, _ = ffn.route(params["layer_2"], x.reshape(10, 32))
    assert np.array_equal(np.asarray(idx), np.asarray(got_idx))
    np.testing.assert_allclose(got_w, w, atol=1e-6)
    assert ffn.route_on == "input" and ffn.activation == "relu"
    assert ffn.shared_width == 0


def test_gated_mlp_takes_its_activation_as_an_argument():
    rng = np.random.default_rng(0)
    x, wg, wu = (jnp.asarray(rng.standard_normal(s), jnp.float32)
                 for s in ((6, 8), (8, 12), (8, 12)))
    wd = jnp.asarray(rng.standard_normal((12, 8)), jnp.float32)
    for name, act in (("silu", jax.nn.silu), ("relu", jax.nn.relu)):
        want = (act(x @ wg) * (x @ wu)) @ wd
        got = moe.gated_mlp(x, wg, wu, wd, dtype=jnp.float32, activation=name)
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(
        moe.gated_mlp(x, wg, wu, wd, dtype=jnp.float32),
        moe.gated_mlp(x, wg, wu, wd, dtype=jnp.float32, activation="silu"))
    with pytest.raises(KeyError):
        moe.gated_mlp(x, wg, wu, wd, activation="gelu")


@pytest.mark.parametrize("heads,tokens,rows,block", [
    (32, 256, 2048 + 256, 8),   # the Granite cell's attention layer
    (16, 128, 2048 + 128, 8),   # the Qwen3-Next cell's
    (28, 256, 8192 + 256, 2),   # this family's full layer at episodes of 8,192
    (28, 256, 4096 + 256, 4),   # its window layers
    (4, 16, 48, 8),             # a test's
    (64, 1024, 65536, 1),
])
def test_streams_of_a_score_block_follow_from_the_rows_it_sees(
        heads, tokens, rows, block):
    assert cached_attention.env_block(heads, tokens, rows) == block


def test_window_statistic_and_lowering_counter(setup):
    """The learn form reports the rows inside the window a query saw
    (at most the window's 8; fewer near an episode's start) and counts
    each traced window layer body by its form."""
    from ray_tpu.telemetry import metrics

    config, params, model, batch, _ = setup
    rows = batch["obs"].shape[0]
    before = dict(metrics.window_cache_lowerings())
    paths = dict(metrics.attention_fragment_lowerings())
    stats = {}
    model.apply(
        params, jnp.asarray(batch["obs"]).reshape(rows // T, T, 1),
        _f32_state(ref.batch_state(batch)),
        resets=jnp.asarray(batch["resets"]).reshape(rows // T, T), stats_out=stats)
    model.apply(params, jnp.zeros((4, 1, 1), jnp.int32),
                _f32_state(ref.batch_state(batch)))
    after = metrics.window_cache_lowerings()
    # the three window layers' checkpointed block is one trace
    assert after["fragment"] - before.get("fragment", 0) == 1
    assert after["step"] - before.get("step", 0) == 3
    # off a TPU every fragment form is the XLA text (the full layer's
    # trace and the window layers'), the one-token form is not counted,
    # and the text skips no key block
    now = metrics.attention_fragment_lowerings()
    assert now["xla"] - paths.get("xla", 0) == 2
    assert now.get("kernel", 0) == paths.get("kernel", 0)
    assert float(stats["attn_key_blocks_skipped_share"]) == 0.0
    # by hand from the positions: min(position + 1, window) a query
    pos0 = np.asarray(batch["__chunk__state_in_8"])
    fresh = batch["resets"].reshape(-1, T) > 0.5
    seen = []
    for n in range(rows // T):
        p = int(pos0[n])
        for i in range(T):
            p = 0 if fresh[n, i] else p
            seen.append(min(p + 1, WINDOW))
            p += 1
    assert abs(float(stats["window_rows_seen_mean"]) - np.mean(seen)) < 1e-5
    assert sorted(stats) == [
        "attn_decode_key_blocks_skipped_share",
        "attn_key_blocks_skipped_share", "moe_decode_held_experts_touched_share",
        "moe_max_tokens_per_held_expert", "moe_rows_computed_share",
        "moe_slots_on_absent_experts", "moe_tokens_per_held_expert",
        "window_rows_seen_mean"]


def _step_kernel_in_the_interpreter(monkeypatch):
    """What a TPU's rule would say, at this file's sizes: every layer's
    one-token form on the step kernel in the Pallas interpreter, the
    full layer's cache of 32 rows as four key blocks of 8, a ring's 8
    rows as two of 4."""
    import functools

    from ray_tpu.ops import flash_attention

    monkeypatch.setattr(flash_attention, "step_kernel_applies", lambda *a: True)
    monkeypatch.setattr(
        flash_attention, "fragment_block_k",
        lambda depth, _=None: 8 if depth > WINDOW else 4)
    monkeypatch.setattr(
        flash_attention, "step_attention",
        functools.partial(flash_attention.step_attention, interpret=True))


@pytest.mark.parametrize("forced", [False, True])
def test_one_token_form_a_ring_on_the_text_the_full_layer_by_the_rule(
        setup, monkeypatch, forced):
    """A ring's one-token call lowers by the full layer's rule
    (``ray_tpu_attention_step_lowerings_total{path}`` counts all four
    under ``kernel`` where it says so, under ``xla`` where not): the
    same logits, values and state, and the learn form reports the key
    blocks a step at each of its positions skips, the rings' among them
    (0 on the text)."""
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
    assert now.get("kernel", 0) - before.get("kernel", 0) == (4 if forced else 0)
    assert now.get("xla", 0) - before.get("xla", 0) == (0 if forced else 4)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=LOGIT_TOL, rtol=LOGIT_TOL)
    stats = {}
    model.apply(
        params, tokens, state,
        resets=jnp.asarray(batch["resets"]).reshape(rows // T, T), stats_out=stats)
    # by hand: a step at position p holds the blocks of 8 up to p's own,
    # of the full layer's four, and of each of the three rings' two
    # blocks of 4 the second from position 4 on
    pos0 = np.asarray(batch["__chunk__state_in_8"])
    fresh = batch["resets"].reshape(-1, T) > 0.5
    skipped, in_a_ring = [], []
    for n in range(rows // T):
        p = int(pos0[n])
        for i in range(T):
            p = 0 if fresh[n, i] else p
            skipped.append(4 - min(p // 8 + 1, 4))
            in_a_ring.append(2 - min(p // 4 + 1, 2))
            p += 1
    assert 0.2 < np.mean(skipped) / 4 < 0.8 and 0 < np.mean(in_a_ring) < 1
    assert float(stats["attn_decode_key_blocks_skipped_share"]) == pytest.approx(
        (np.mean(skipped) + 3 * np.mean(in_a_ring)) / (4 + 3 * 2) if forced else 0.0)


def test_reset_state_leaves_the_rings_and_zeroes_the_position(setup):
    config, params, model, batch, _ = setup
    state = _f32_state(ref.batch_state(batch))
    after = model.reset_state(state, jnp.asarray([True, False, True, False]))
    for a, b in zip(after[:-1], state[:-1]):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert list(np.asarray(after[-1])[[0, 2]]) == [0, 0]
    assert np.asarray(after[-1])[1] == np.asarray(state[-1])[1]


@pytest.mark.parametrize("precision", ["int8", "fp8"])
def test_the_controls_fail_the_tolerances(setup, precision):
    """The reference computed one precision step below the bfloat16 the
    configuration states, in the system's place, fails the logit
    tolerance and the gradient's."""
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
