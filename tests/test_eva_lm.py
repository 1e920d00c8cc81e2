"""The sequence model's EVA kind (``model_type: evabyte``: attention
over an exact window store and a summary store of one pooled row a chunk
of every earlier window, under one softmax; models/sequence_lm,
ops/eva_attention.py) held to the plain reference
(perf/reference/evabyte.py) on seeded weights at a small size: hidden
32, two layers, 4 heads of 8, a window of 8 and chunks of 2 in episodes
of 40 (five windows, four boundaries), fragments of 12 (longer than a
window: a fragment may cross two boundaries), a dense feed-forward of
48, a vocabulary of 20.

Every start state has EVERY slot of both stores filled with rows of
order one (``make_state``): a row that must not be seen (the window
before's row in a slot past ``t mod W``, a summary of the query's own
window, a row of an earlier episode) is there to be seen, so a wrong
mask moves the logits by far more than the tolerance.

Tolerances. Both sides are float32 at precision "highest" here, so they
differ by summation order only: 3e-4 on logits and values of order one,
2e-3 of a gradient leaf's norm (a leaf under 0.1% of the whole
gradient's norm against 0.1% of it). The wrong masks, bfloat16 pooling
and the int8 / fp8 controls read 10 times that and more, and tests hold
them to failing.
"""

import importlib.util
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from ray_tpu.models.sequence_lm import EvaLayer, SequenceLM, describe
from ray_tpu.ops import eva_attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 20
T = 12
WINDOW, CHUNK, EPISODE = 8, 2, 40
LOGIT_TOL = 3e-4
GRAD_LEAF_TOL = 2e-3


def _reference():
    path = os.path.join(ROOT, "perf", "reference", "evabyte.py")
    spec = importlib.util.spec_from_file_location("ref_evabyte", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _reference()


def small_config(**over):
    lm = {
        "model_type": "evabyte", "attention_class": "eva",
        "hidden_size": 32, "num_hidden_layers": 2, "intermediate_size": 48,
        "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 8,
        "window_size": WINDOW, "chunk_size": CHUNK, "rope_theta": 100000,
        "rope_scaling": None, "rms_norm_eps": 1e-5,
        "max_position_embeddings": EPISODE, "tie_word_embeddings": False,
    }
    lm.update(over)
    config = dict(lm)
    config["algo_config"] = {
        "clip_param": 0.2, "vf_clip_param": 100.0, "kl_coeff": 0.0,
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


def _state_at(config, depths, seed=5):
    """Seeded start states with every slot filled, at ``depths``."""
    z = ref.sizes(config, VOCAB)
    state = ref.make_state(np.random.default_rng(seed), z, len(depths), T)
    return _f32_state(state[:-1] + (np.asarray(depths, np.int32),))


@pytest.fixture(scope="module")
def setup():
    config = small_config()
    params = ref.init_params(jax.random.PRNGKey(7), config, VOCAB)
    batch = ref.make_batch(np.random.default_rng(3), config, 4 * T, VOCAB)
    # the four fragments of 12 over a window of 8, each across a boundary:
    # from an episode's start, one with a reset inside (``make_batch``),
    # one that starts mid-chunk and crosses two, one from a window's start
    batch["__chunk__state_in_8"] = np.asarray([0, 5, 13, 24], np.int32)
    batch["resets"] = batch["resets"].reshape(4, T)
    batch["resets"][0, 0] = 1.0
    batch["resets"] = batch["resets"].reshape(-1)
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


def _assert_states_agree(got, want, atol=2e-4, every_slot=False):
    """The rows a later query may read: the window store's slots at or
    below ``(position - 1) mod W`` and the summary store's rows of
    completed chunks (``every_slot``: all of both, stale ones too)."""
    depth = np.asarray(want[-1])
    assert np.array_equal(np.asarray(got[-1]), depth)
    for i, (a, b) in enumerate(zip(got[:-1], want[:-1])):
        assert a.shape == b.shape
        slots = np.arange(a.shape[1])[None]
        held = depth[:, None] % WINDOW if i % 4 < 2 else depth[:, None] // CHUNK
        live = (slots < held) | every_slot
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


# -- (a) tree, state, description ----------------------------------------------------


def test_param_tree_and_state_match_the_reference(setup):
    config, params, model, _, _ = setup
    assert model.param_shapes() == ref.param_shapes(config, VOCAB)
    assert model.layer_types == ("eva_attention",) * 2
    assert model.ffn_types == ("dense",) * 2
    z = ref.sizes(config, VOCAB)
    ours, theirs = model.initial_state(3), ref.initial_state(z, 3)
    assert [(s.shape, s.dtype) for s in ours[:-1]] == [
        (s.shape, jnp.float32) for s in theirs[:-1]]
    # two stores on two clocks, keys and values apart: four leaves a layer
    assert [s.shape for s in ours] == (
        [(3, 8, 32)] * 2 + [(3, 20, 32)] * 2) * 2 + [(3,)]
    mixer = model.segments[0].mixer
    assert mixer == EvaLayer(heads=4, head_dim=8, window=8, chunk=2, theta=100000.0)
    assert not mixer.cleared_on_reset
    # an episode shorter than the window: the window store is the episode's
    assert EvaLayer(4, 8, 64, 2, 1e5).state_shapes(3, 40, jnp.bfloat16)[0][0] == (3, 40, 32)
    # the policy's own draw of the two vectors a head
    own = model.init(jax.random.PRNGKey(0))["layer_1"]
    for leaf in ("eva_mu", "eva_phi"):
        assert 0 < float(jnp.max(jnp.abs(own[leaf]))) <= 8 ** -0.5


def test_describe_reads_the_share_of_heads_and_refuses_what_is_no_kind():
    lm = small_config()["algo_config"]["model"]["sequence_lm"]
    held = describe(dict(lm, num_attention_heads=2, num_key_value_heads=2,
                         heads_held=[2, 2]))["segments"][0].mixer
    assert (held.first, held.heads, held.head_dim) == (2, 2, 8)
    # the head's size where none is stated: of the whole layer
    whole = dict(lm)
    del whole["head_dim"]
    assert describe(whole)["segments"][0].mixer.head_dim == 8
    for wrong in ({"num_key_value_heads": 2}, {"heads_held": [0, 2]},
                  {"chunk_size": 3}, {"rope_scaling": {"factor": 2.0}}):
        with pytest.raises(ValueError):
            describe(dict(lm, **wrong))


# -- (b) the two forms against the reference -------------------------------------------


@pytest.mark.parametrize("start", [0, 13])
def test_one_token_steps_through_a_whole_episode_equal_the_reference(setup, start):
    """40 steps from ``start``: through the episode's end (four window
    boundaries on the way from 0) into the next one, against the
    reference's full forward over the same tokens."""
    config, params, model, _, fns = setup
    rng = np.random.default_rng(11)
    tokens = rng.integers(0, VOCAB, (3, EPISODE))
    fresh = np.zeros((3, EPISODE), bool)
    fresh[:, EPISODE - start if start else 0] = True
    state = _state_at(config, [start] * 3)
    with jax.default_matmul_precision("highest"):
        want = fns["reference"](params, jnp.asarray(tokens), state, jnp.asarray(fresh))
        logits, values, after = _chain(fns["apply"], params, tokens, state, fresh)
    np.testing.assert_allclose(logits, want["logits"], atol=LOGIT_TOL)
    np.testing.assert_allclose(values, want["value"], atol=LOGIT_TOL)
    # the reference leaves the stores as a rollout does, stale rows too
    _assert_states_agree(after, want["state"], every_slot=True)


@pytest.mark.parametrize("depths,reset_at", [
    ([5, 13, 30], None),      # crosses a boundary; mid-chunk and crosses two; crosses one
    ([0, 8, 16], None),       # from a window's first position
    ([31, 36, 3], 7),         # an episode opens inside
], ids=["crossing", "window_start", "reset_inside"])
def test_fragment_form_from_a_stored_state_equals_reference_and_steps(
        setup, depths, reset_at):
    config, params, model, _, fns = setup
    rng = np.random.default_rng(13)
    n = len(depths)
    tokens = rng.integers(0, VOCAB, (n, T))
    fresh = np.zeros((n, T), bool)
    fresh[np.asarray(depths) == 0, 0] = True
    if reset_at is not None:
        fresh[:, reset_at] = True
    state = _state_at(config, depths)
    with jax.default_matmul_precision("highest"):
        want = fns["reference"](params, jnp.asarray(tokens), state, jnp.asarray(fresh))
        logits, values, after = fns["apply"](
            params, jnp.asarray(tokens[..., None]), state,
            jnp.asarray(fresh, jnp.float32))
        s_logits, s_values, s_after = _chain(fns["apply"], params, tokens, state, fresh)
    np.testing.assert_allclose(logits.reshape(n, T, -1), want["logits"], atol=LOGIT_TOL)
    np.testing.assert_allclose(values.reshape(n, T), want["value"], atol=LOGIT_TOL)
    np.testing.assert_allclose(s_logits, want["logits"], atol=LOGIT_TOL)
    np.testing.assert_allclose(logits.reshape(n, T, -1), s_logits, atol=LOGIT_TOL)
    _assert_states_agree(after, want["state"])
    _assert_states_agree(s_after, want["state"], every_slot=True)


def _sliding(key_pos, query_pos, window):
    return (key_pos >= 0) & (key_pos <= query_pos) & (query_pos - key_pos < window)


WRONG = {
    # a sliding window in place of the block-aligned one
    "sliding_window": ("window_visible", _sliding),
    # the chunks of the query's own window read as summaries too
    "own_window_chunks_visible": (
        "summary_visible", lambda end, query, window: end < query),
    # summaries visible one chunk early
    "summaries_one_chunk_early": (
        "summary_visible",
        lambda end, query, window: end - CHUNK < window * (query // window)),
}


@pytest.mark.parametrize("wrong", sorted(WRONG))
@pytest.mark.parametrize("form", ["fragment", "steps"])
def test_a_wrong_mask_fails_the_comparison(setup, monkeypatch, wrong, form):
    config, params, model, _, fns = setup
    name, rule = WRONG[wrong]
    monkeypatch.setattr(eva_attention, name, rule)
    rng = np.random.default_rng(17)
    depths = [13, 22, 30]
    tokens = rng.integers(0, VOCAB, (3, T))
    fresh = np.zeros((3, T), bool)
    state = _state_at(config, depths)
    apply = lambda p, tok, st, fr: model.apply(p, tok, st, resets=fr)  # traced anew
    with jax.default_matmul_precision("highest"):
        want = fns["reference"](params, jnp.asarray(tokens), state, jnp.asarray(fresh))
        if form == "fragment":
            logits = apply(params, jnp.asarray(tokens[..., None]), state,
                           jnp.asarray(fresh, jnp.float32))[0].reshape(3, T, -1)
        else:
            logits = _chain(apply, params, tokens, state, fresh)[0]
    assert float(jnp.max(jnp.abs(logits - want["logits"]))) > 10 * LOGIT_TOL


def test_bfloat16_pooling_where_float32_is_stated_fails_the_tolerance(setup, monkeypatch):
    """A summary is written once and read for the rest of the episode:
    its two softmaxes and their logits are float32 at precision highest.
    In bfloat16 they are not within the tolerance."""
    config, params, model, _, fns = setup
    stated = eva_attention.summarise

    def low(k, v, phi, mu):
        bf = lambda x: x.astype(jnp.bfloat16)
        by_phi = jnp.einsum("...chd,hd->...ch", bf(k), bf(phi))
        by_mu = jnp.einsum("...chd,hd->...ch", bf(k), bf(mu))
        kbar = jnp.sum(jax.nn.softmax(by_phi, axis=-2)[..., None] * bf(k), axis=-3)
        vbar = jnp.sum(jax.nn.softmax(by_mu, axis=-2)[..., None] * bf(v), axis=-3)
        return kbar.astype(jnp.float32), vbar.astype(jnp.float32)

    rng = np.random.default_rng(19)
    tokens = rng.integers(0, VOCAB, (3, T))
    fresh = np.zeros((3, T), bool)
    state = _state_at(config, [5, 13, 30])
    apply = lambda p, tok, st, fr: model.apply(p, tok, st, resets=fr)
    errors = {}
    for name, fn in (("float32", stated), ("bfloat16", low)):
        monkeypatch.setattr(eva_attention, "summarise", fn)
        with jax.default_matmul_precision("highest"):
            want = fns["reference"](
                params, jnp.asarray(tokens), state, jnp.asarray(fresh))
            logits = apply(params, jnp.asarray(tokens[..., None]), state,
                           jnp.asarray(fresh, jnp.float32))[0].reshape(3, T, -1)
        errors[name] = float(jnp.max(jnp.abs(logits - want["logits"])))
    assert errors["float32"] < LOGIT_TOL < 3 * LOGIT_TOL < errors["bfloat16"]


# -- (c) the gradient ---------------------------------------------------------------


def test_loss_and_every_gradient_leaf_match_reference(setup):
    """The model under the reference's loss against the reference's own
    loss and gradient, leaf by leaf: ``mu`` and ``phi`` through the
    summaries made inside the fragments, ``k`` and ``v`` through them
    and through the exact rows."""
    config, params, model, batch, _ = setup
    dev = {k: jnp.asarray(v) for k, v in batch.items()}
    rows = batch["obs"].shape[0]

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
        for leaf in ("eva_mu", "eva_phi", "k_proj", "v_proj", "mlp_down"):
            assert float(np.linalg.norm(got[layer][leaf])) > 0, (layer, leaf)


@pytest.mark.parametrize("depths,crosses", [
    ([8, 17, 25], False),   # each fragment of 6 stays inside its window
    ([5, 13, 21], True),    # each crosses a boundary
], ids=["inside_a_window", "crossing"])
def test_mu_and_phi_get_a_gradient_only_where_a_fragment_crosses(setup, depths, crosses):
    """A summary made inside a fragment is read there only by a query of
    a LATER window: with every fragment inside one window the two
    vectors' gradient is exactly zero, in the reference as here."""
    config, params, model, _, _ = setup
    short = 6
    rng = np.random.default_rng(23)
    tokens = jnp.asarray(rng.integers(0, VOCAB, (3, short)))
    state = _state_at(config, depths)
    fresh = jnp.zeros((3, short), bool)

    def ours(p):
        return jnp.sum(jnp.square(model.apply(
            p, tokens[..., None], state, resets=fresh.astype(jnp.float32))[0]))

    def theirs(p):
        return jnp.sum(jnp.square(
            ref.forward(p, tokens, state, fresh, config, VOCAB)["logits"]))

    with jax.default_matmul_precision("highest"):
        got, want = jax.grad(ours)(params), jax.grad(theirs)(params)
    for layer in ("layer_0", "layer_1"):
        for leaf in ("eva_mu", "eva_phi"):
            norm = float(jnp.linalg.norm(got[layer][leaf]))
            assert (norm > 0) == crosses, (layer, leaf, norm)
            assert (float(jnp.linalg.norm(want[layer][leaf])) > 0) == crosses
    assert max(_leaf_errors(got, want).values()) < GRAD_LEAF_TOL


# -- (d) a share of heads -------------------------------------------------------------


@pytest.mark.parametrize("form", ["fragment", "steps"])
def test_the_four_head_shares_add_up_to_the_uncut_layer(form):
    """Four chips share a layer's heads: each computes its heads' part
    of ``W_o o`` from its columns of ``W_q``, ``W_k``, ``W_v``, its rows
    of ``W_o`` and its vectors, over stores that are its heads' lanes of
    the uncut layer's; the four parts add up to the uncut layer's
    output."""
    whole = EvaLayer(heads=4, head_dim=8, window=WINDOW, chunk=CHUNK, theta=1e5)
    rng = np.random.default_rng(29)
    d, b = 32, 3
    p = {leaf: jnp.asarray(rng.standard_normal(shape) / np.sqrt(shape[0]), jnp.float32)
         for leaf, shape in whole.param_shapes(d).items()}
    t = T if form == "fragment" else 1
    x = jnp.asarray(rng.standard_normal((b, t, d)), jnp.float32)
    state = tuple(jnp.asarray(rng.standard_normal(shape), jnp.float32)
                  for shape, _ in whole.state_shapes(b, EPISODE, jnp.float32))
    pos0 = jnp.asarray([5, 13, 30], jnp.int32)
    steps = jnp.arange(t)[None]
    ctx = {"scope": "", "dtype": jnp.float32, "eps": 1e-5, "pos0": pos0,
           "positions": pos0[:, None] + steps, "seg": jnp.zeros((b, t), jnp.int32),
           "fresh": jnp.zeros((b, t), bool)}
    with jax.default_matmul_precision("highest"):
        want, want_state, _ = whole.apply(p, x, state, ctx)
        parts = []
        for first in range(4):
            share = EvaLayer(heads=1, head_dim=8, window=WINDOW, chunk=CHUNK,
                             theta=1e5, first=first)
            lanes = slice(8 * first, 8 * first + 8)
            y, after, _ = share.apply(
                share.share_of(p), x, tuple(s[..., lanes] for s in state), ctx)
            parts.append(y)
            for a, w in zip(after, want_state):
                np.testing.assert_allclose(a, w[..., lanes], atol=1e-5)
    np.testing.assert_allclose(sum(parts), want, atol=1e-4)
    assert float(jnp.max(jnp.abs(parts[0] - want))) > 1e-2  # a share is not the layer


# -- (e) statistics, the lowering counter, the kernel ------------------------------------


def test_statistics_and_lowering_counter(setup):
    """The learn form reports the rows inside each mask a query saw, the
    chunks it summarised, the fragments that crossed a window boundary
    and the key blocks a one-token step at each of its positions would
    skip, and counts each traced EVA layer body by its form."""
    from ray_tpu.telemetry import metrics

    config, params, model, batch, _ = setup
    rows = batch["obs"].shape[0]
    before = dict(metrics.eva_lowerings())
    stats = {}
    model.apply(
        params, jnp.asarray(batch["obs"]).reshape(rows // T, T, 1),
        _f32_state(ref.batch_state(batch)),
        resets=jnp.asarray(batch["resets"]).reshape(rows // T, T), stats_out=stats)
    model.apply(params, jnp.zeros((4, 1, 1), jnp.int32),
                _f32_state(ref.batch_state(batch)))
    after = metrics.eva_lowerings()
    # the fragment form's checkpointed block is one trace for the two
    # layers; off a TPU the one-token form is the text
    assert after["fragment"] - before.get("fragment", 0) == 1
    assert after["step"] - before.get("step", 0) == 2
    assert after.get("kernel", 0) == before.get("kernel", 0)
    # by hand from the positions
    pos0 = np.asarray(batch["__chunk__state_in_8"])
    fresh = batch["resets"].reshape(-1, T) > 0.5
    exact, pooled, chunks, crossing = [], [], 0, 0
    for n in range(rows // T):
        p, crossed = int(pos0[n]), False
        for i in range(T):
            p = 0 if fresh[n, i] else p
            crossed |= i > 0 and p > 0 and p % WINDOW == 0
            exact.append(p % WINDOW + 1)
            pooled.append((WINDOW // CHUNK) * (p // WINDOW))
            chunks += p % CHUNK == CHUNK - 1
            p += 1
        crossing += crossed
    assert abs(float(stats["eva_window_rows_seen_mean"]) - np.mean(exact)) < 1e-5
    assert abs(float(stats["eva_summary_rows_seen_mean"]) - np.mean(pooled)) < 1e-5
    assert float(stats["eva_chunks_summarised"]) == 2 * chunks  # two layers
    assert float(stats["eva_fragments_crossing_a_window"]) == 2 * crossing == 2 * 4
    # one block a store at this size's 128-row blocks: nothing to skip
    assert float(stats["eva_window_key_blocks_skipped_share"]) == 0.0
    # a store of this size is one 128-row block, so one span: a step at
    # each of the update's positions copies a span of the window store,
    # and one of the summary store once a window has closed (two layers)
    spans = [1 + (n > 0) for n in pooled]
    assert float(stats["eva_step_copies"]) == 2 * sum(spans)
    assert abs(float(stats["eva_step_rows_fetched_mean"]) - 128 * np.mean(spans)) < 1e-3
    assert sorted(stats) == [
        "eva_chunks_summarised", "eva_fragments_crossing_a_window",
        "eva_step_copies", "eva_step_rows_fetched_mean",
        "eva_summary_key_blocks_skipped_share", "eva_summary_rows_seen_mean",
        "eva_window_key_blocks_skipped_share", "eva_window_rows_seen_mean"]


def _poisoned_outside(stores, held, block):
    """The four stores with NaN in every row of every key block past the
    ``held`` (window, summary) blocks a stream's masks reach into."""
    return [
        jnp.where(jnp.arange(x.shape[1])[None, :, None] >= block * n[:, None, None],
                  jnp.nan, x)
        for x, n in zip(stores, (held[0], held[0], held[1], held[1]))]


def test_a_one_token_step_fetches_no_block_outside_the_two_masks():
    """The two-store step kernel in the Pallas interpreter (heads of 128,
    a window of 32 in blocks of 16, chunks of 2, episodes of 160) against
    the text: equal where the text is, and UNMOVED when every key block
    the counters call skipped is filled with NaN: it fetched none of
    them. The counters are the masks' own arithmetic: blocks with a slot
    at or below ``t mod W``, blocks below ``(W / c)(t // W)`` rows."""
    rng = np.random.default_rng(31)
    b, h, d, window, chunk, episode, block = 6, 2, 128, 32, 2, 160, 16
    sizes = (window, window, episode // chunk, episode // chunk)
    stores = [jnp.asarray(rng.standard_normal((b, n, h * d)), jnp.bfloat16)
              for n in sizes]
    q = jnp.asarray(rng.standard_normal((b, h, d)) * d ** -0.5, jnp.bfloat16)
    positions = jnp.asarray([0, 15, 31, 32, 77, 159], jnp.int32)
    want = eva_attention.step_text(q, stores, positions, window, chunk)
    run = lambda s: eva_attention.step_attention(
        q, s, positions, window=window, chunk=chunk, block=block, interpret=True)
    got = run(stores)
    # bfloat16 weights into the value product, normalised after it in
    # the kernel and before it in the text
    np.testing.assert_allclose(got, want, atol=2e-2)
    seen = eva_attention.rows_seen(positions, window, chunk)
    assert [int(x) for x in seen[0]] == [1, 16, 32, 1, 14, 32]
    assert [int(x) for x in seen[1]] == [0, 0, 0, 16, 32, 64]
    held = eva_attention.step_blocks(*seen, block)
    assert [int(x) for x in held[0]] == [1, 1, 2, 1, 1, 2]
    assert [int(x) for x in held[1]] == [0, 0, 0, 1, 2, 4]
    poisoned = _poisoned_outside(stores, held, block)
    assert bool(jnp.all(jnp.isnan(poisoned[2][0])))  # a stream with no summary yet
    np.testing.assert_array_equal(run(poisoned), got)
    assert bool(jnp.any(jnp.isnan(  # the text multiplies every slot
        eva_attention.step_text(q, poisoned, positions, window, chunk))))
    counted = eva_attention.step_key_blocks(
        positions, window, chunk, window, episode // chunk, block)
    assert (int(counted["window"][0]), counted["window"][1]) == (12 - 8, 12)
    assert (int(counted["summary"][0]), counted["summary"][1]) == (30 - 7, 30)
    # the cell's sizes: 4 of 16 fragments of 640 cross a window of 2,048
    starts = 640 * np.arange(16)
    assert sum(s // 2048 != (s + 639) // 2048 for s in starts) == 4
    at = jnp.asarray((starts[:, None] + np.arange(640)[None]).ravel())
    exact, pooled = eva_attention.rows_seen(at, 2048, 16)
    assert float(jnp.mean(exact)) == 1024.5 and float(jnp.mean(pooled)) == 256.0


# the cell's geometry at a sixteenth: a window of 16 key blocks (of 8 rows
# for 128), a block of summary rows a window, an episode of ten windows
SPAN_WINDOW, SPAN_CHUNK, SPAN_BLOCK, SPAN_EPISODE = 128, 16, 8, 10 * 128
SPAN = eva_attention._SPAN_BLOCKS  # 8: a window is two whole spans


@pytest.mark.parametrize("in_window", [0, 7, 8, 23, 31, 32, 55, 63, 64, 127])
def test_the_span_walk_is_the_text_at_every_span_length(in_window):
    """Steps at ``t mod W = in_window`` (the cell's 0, 127, 128, 383, 511,
    512, 895, 1,023, 1,024 and 2,047 at a sixteenth: both edges of a span
    of one block, of three, four, five, seven and eight, a whole span
    and a span of one, two whole spans) in the first, second, ninth and
    tenth window of an episode (summary prefixes of 0, 1, 8 and 9 blocks:
    no span, a span of one, a whole span, a whole span and a span of
    one), in the interpreter: the text's, and UNMOVED by NaN in every
    block outside the two masks, the block right after a mask's last
    among them. The interpreter's scratch slots hold NaN before the first
    copy, so a row of a slot that no copy wrote would show in a product
    as well."""
    from jax._src.pallas import primitives

    assert bool(jnp.all(jnp.isnan(primitives.uninitialized_value((8, 128), jnp.bfloat16))))
    rng = np.random.default_rng(41)
    h, d, b = 2, 128, 4
    sizes = (SPAN_WINDOW,) * 2 + (SPAN_EPISODE // SPAN_CHUNK,) * 2
    stores = [jnp.asarray(rng.standard_normal((b, n, h * d)), jnp.bfloat16)
              for n in sizes]
    q = jnp.asarray(rng.standard_normal((b, h, d)) * d ** -0.5, jnp.bfloat16)
    positions = jnp.asarray(
        [SPAN_WINDOW * n + in_window for n in (0, 1, SPAN, SPAN + 1)], jnp.int32)
    seen = eva_attention.rows_seen(positions, SPAN_WINDOW, SPAN_CHUNK)
    held = eva_attention.step_blocks(*seen, SPAN_BLOCK)
    assert [int(n) for n in held[0]] == [in_window // SPAN_BLOCK + 1] * 4
    assert [int(n) for n in held[1]] == [0, 1, SPAN, SPAN + 1]
    spans = eva_attention.step_spans(*seen, SPAN_BLOCK)
    assert [int(n) for n in spans[0]] == [in_window // (SPAN * SPAN_BLOCK) + 1] * 4
    assert [int(n) for n in spans[1]] == [0, 1, 1, 2]
    want = eva_attention.step_text(q, stores, positions, SPAN_WINDOW, SPAN_CHUNK)
    run = lambda s: eva_attention.step_attention(
        q, s, positions, window=SPAN_WINDOW, chunk=SPAN_CHUNK, block=SPAN_BLOCK,
        interpret=True)
    got = run(stores)
    np.testing.assert_allclose(got, want, atol=2e-2)
    poisoned = _poisoned_outside(stores, held, SPAN_BLOCK)
    # the first row after the summary mask's last block, a stream
    assert all(bool(jnp.all(jnp.isnan(poisoned[2][n, SPAN_BLOCK * int(held[1][n])])))
               for n in range(b))
    np.testing.assert_array_equal(run(poisoned), got)
    assert bool(jnp.any(jnp.isnan(
        eva_attention.step_text(q, poisoned, positions, SPAN_WINDOW, SPAN_CHUNK))))


@pytest.mark.parametrize("offset", [0, 127, 128, 383, 511, 512, 639])
def test_the_flat_list_holds_spans_of_one_store_and_one_stream(offset):
    """The list the kernel walks at the cell's sizes (16 streams 640
    apart, ``offset`` into their fragments, blocks of 128 in stores of
    2,048 and 640 rows): a span is one to ``_SPAN_BLOCKS`` blocks of ONE
    store and ONE stream; a stream's spans tile its two prefixes in
    order, the window's first, no block twice and none left out; none
    reaches past its store; the rows it says are inside the mask are the
    mask's."""
    window, chunk, block, depths = 2048, 16, 128, (2048, 640)
    positions = jnp.asarray(640 * np.arange(16) + offset, jnp.int32)
    seen = eva_attention.rows_seen(positions, window, chunk)
    held = [np.asarray(n) for n in eva_attention.step_blocks(*seen, block)]
    (first, count), entries = eva_attention._span_list(*seen, depths, block)
    first, count = np.asarray(first), np.asarray(count)
    stream, code, row, rows_in = (np.asarray(x) for x in entries)
    assert len(stream) == 16 * sum(
        -(-rows // (SPAN * block)) for rows in depths) + eva_attention._AHEAD
    assert list(first) == list(np.cumsum(count) - count)
    for n in range(16):
        mine = slice(first[n], first[n] + count[n])
        assert set(stream[mine]) == {n}
        store, blocks = code[mine] // SPAN, code[mine] % SPAN + 1
        assert list(store) == sorted(store)  # the window store's spans first
        for which in (0, 1):
            of = store == which
            # whole spans, then one that ends at the mask's last block
            assert list(row[mine][of]) == [SPAN * block * i for i in range(of.sum())]
            assert list(blocks[of]) == [SPAN] * (held[which][n] // SPAN) + (
                [held[which][n] % SPAN] if held[which][n] % SPAN else [])
            assert np.all(row[mine][of] + block * blocks[of] <= depths[which])
            assert list(rows_in[mine][of]) == list(
                int(seen[which][n]) - row[mine][of])
    # past the list's end: the last entry again
    total = first[-1] + count[-1]
    assert set(stream[total:]) == {15} and set(code[total:]) == {code[total - 1]}


def test_the_two_fetch_statistics_are_the_masks_arithmetic_at_the_cells_sizes():
    """``eva_step_copies`` and ``eva_step_rows_fetched_mean`` over every
    position of the cell's 16 fragments of 640 in an episode of 10,240:
    2.3 spans a stream and step (1.5 of the window store, 0.8 of the
    summary store) where a block a trip was 10.5, and 1,344 rows (1,088 +
    256) of whole key blocks either way."""
    at = jnp.asarray((640 * np.arange(16)[:, None] + np.arange(640)[None]).ravel())
    copies, fetched = eva_attention.step_fetches(at, 2048, 16)
    assert abs(float(copies) / at.size - 2.3) < 1e-6
    assert float(fetched) == 1344.0
    seen = eva_attention.rows_seen(at, 2048, 16)
    spans = eva_attention.step_spans(*seen)
    assert int(jnp.sum(spans[0])) == 1.5 * at.size
    assert int(jnp.sum(spans[1])) == 0.8 * at.size
    blocks = eva_attention.step_blocks(*seen)
    assert float(jnp.mean(blocks[0] + blocks[1])) == 10.5
    counted = eva_attention.step_key_blocks(at, 2048, 16, 2048, 640)
    walked = sum(every - int(skipped) for skipped, every in counted.values())
    assert walked == 10.5 * at.size == float(fetched) / 128 * at.size


def test_the_kernel_is_the_steps_form_where_the_rule_says_so(setup, monkeypatch):
    """What a TPU's rule would say, at heads of 128: the layer's
    one-token form on the kernel (in the interpreter), counted as
    ``kernel``, equal to the text's."""
    import functools

    from ray_tpu.telemetry import metrics

    layer = EvaLayer(heads=2, head_dim=128, window=128, chunk=16, theta=1e5)
    rng = np.random.default_rng(37)
    b, d = 3, 64
    p = {leaf: jnp.asarray(rng.standard_normal(shape) / np.sqrt(shape[0]), jnp.float32)
         for leaf, shape in layer.param_shapes(d).items()}
    x = jnp.asarray(rng.standard_normal((b, 1, d)), jnp.float32)
    state = tuple(jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
                  for shape, _ in layer.state_shapes(b, 2048, jnp.bfloat16))
    pos0 = jnp.asarray([15, 130, 2047], jnp.int32)
    ctx = {"scope": "", "dtype": jnp.bfloat16, "eps": 1e-5, "pos0": pos0,
           "positions": pos0[:, None], "seg": jnp.zeros((b, 1), jnp.int32),
           "fresh": jnp.zeros((b, 1), bool)}
    want, want_state, _ = layer.apply(p, x, state, ctx)
    monkeypatch.setattr(eva_attention, "step_kernel_applies", lambda *a: True)
    monkeypatch.setattr(
        eva_attention, "step_attention",
        functools.partial(eva_attention.step_attention, interpret=True))
    before = dict(metrics.eva_lowerings())
    got, got_state, _ = layer.apply(p, x, state, ctx)
    assert metrics.eva_lowerings()["kernel"] - before.get("kernel", 0) == 1
    np.testing.assert_allclose(got, want, atol=2e-2)
    for a, w in zip(got_state, want_state):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(w, np.float32))
    # the stream at 15 ended its chunk: row 0 of its summary store is new
    assert not np.array_equal(np.asarray(got_state[2][0, 0], np.float32),
                              np.asarray(state[2][0, 0], np.float32))
    np.testing.assert_array_equal(np.asarray(got_state[2][1], np.float32),
                                  np.asarray(state[2][1], np.float32))


def test_reset_state_leaves_the_stores_and_zeroes_the_position(setup):
    config, _, model, _, _ = setup
    state = _state_at(config, [5, 13, 30])
    after = model.reset_state(state, jnp.asarray([True, False, True]))
    for a, b in zip(after[:-1], state[:-1]):
        np.testing.assert_array_equal(a, b)
    assert list(np.asarray(after[-1])) == [0, 13, 0]


# -- (f) the controls, the fused lane, the reference ------------------------------------


@pytest.mark.parametrize("precision", ["int8", "fp8"])
def test_the_controls_fail_the_tolerances(setup, precision):
    config, params, _, _, fns = setup
    rng = np.random.default_rng(41)
    tokens = jnp.asarray(rng.integers(0, VOCAB, (3, T)))
    state = _state_at(config, [5, 13, 30])
    fresh = jnp.zeros((3, T), bool)
    with jax.default_matmul_precision("highest"):
        want = fns["reference"](params, tokens, state, fresh)
        low = ref.forward(params, tokens, state, fresh, config, VOCAB, precision)
    assert float(jnp.max(jnp.abs(low["logits"] - want["logits"]))) > 10 * LOGIT_TOL


def test_two_updates_on_the_fused_lane():
    """PPO on the token env, ``env_backend: jax``: rollout and update in
    one dispatch through ``JaxPolicy``, twice, built from ``model_type:
    evabyte`` as ``python -m ray_tpu.train`` builds it. Fragments of 12
    in episodes of 24 over a window of 8: every iteration some stream
    crosses a boundary, so ``mu`` and ``phi`` move."""
    from ray_tpu.algorithms.registry import get_algorithm_class

    lm = dict(small_config()["algo_config"]["model"]["sequence_lm"],
              max_position_embeddings=24)
    algo = get_algorithm_class("PPO")(config={
        "env": "TokenStreamJax-v0",
        "env_config": {"vocab_size": VOCAB, "episode_length": 24, "phase_stride": 3},
        "env_backend": "jax", "num_workers": 0, "num_envs_per_worker": 8,
        "rollout_fragment_length": T, "train_batch_size": 8 * T,
        "sgd_minibatch_size": 8 * T, "num_sgd_iter": 1, "superstep": 1,
        "gamma": 1.0, "lambda": 0.95, "lr": 1e-4, "grad_clip": 1.0,
        "kl_coeff": 0.0, "entropy_coeff": 0.0, "seed": 3,
        "model": {"use_sequence_lm": True, "sequence_lm": lm, "max_seq_len": T,
                  "dtype": "float32"},
    })
    try:
        policy = algo.get_policy()
        assert policy.model.loss_groups(8) is None
        before = jax.device_get(policy.params)
        for _ in range(2):
            info = algo.train()["info"]["learner"]["default_policy"]
            for key in ("total_loss", "entropy", "eva_window_rows_seen_mean",
                        "eva_summary_rows_seen_mean", "eva_chunks_summarised",
                        "eva_fragments_crossing_a_window",
                        "eva_window_key_blocks_skipped_share",
                        "eva_summary_key_blocks_skipped_share"):
                assert np.isfinite(info[key]) and np.ndim(info[key]) == 0, key
            assert info["eva_chunks_summarised"] > 0  # a shard's, averaged
            assert info["eva_fragments_crossing_a_window"] > 0
        after = jax.device_get(policy.params)
        moved = lambda g, k: float(np.abs(after[g][k] - before[g][k]).max())
        for leaf in ("eva_mu", "eva_phi", "q_proj", "mlp_up"):
            assert moved("layer_0", leaf) > 0 and moved("layer_1", leaf) > 0, leaf
        assert moved("head", "kernel") > 0
    finally:
        algo.cleanup()


def test_the_reference_imports_nothing_of_the_system():
    with open(os.path.join(ROOT, "perf", "reference", "evabyte.py")) as f:
        text = f.read()
    assert "import ray_tpu" not in text and "from ray_tpu" not in text
    assert "pallas" not in text and "import perf" not in text
