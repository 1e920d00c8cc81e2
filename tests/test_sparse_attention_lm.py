"""The attention layer under a learned index (``sa_config``: the
lightning indexer of DeepSeek Sparse Attention, ``model_type: KeyeVL2``)
on the ``qwen3_moe`` stack (models/sequence_lm, ops/sparse_index.py,
ops/cached_attention.py) held to the plain reference
(perf/reference/keye2.py) on seeded weights at a small size: hidden 64,
two layers, 4 heads of 16 over 2 KV heads, an index of 8 heads of 16
that keeps 8 rows, episodes of 64, fragments of 16 (so a fragment is
twice ``topk``), a router over 8 experts of which 2 are held, top-3, a
vocabulary of 64.

Every start state has EVERY slot of all three caches filled with rows of
order one (``make_state``): a row that must not be seen, or must not be
chosen, is there to be, so a stale row read, a choice off by one or a tie
broken the other way moves the logits by far more than the tolerance.

Tolerances. Both sides are float32 at precision "highest" here, so they
differ by summation order only: 3e-4 on logits and values of order one,
2e-3 of a gradient leaf's norm, and the CHOICES are equal slot for slot.
"""

import dataclasses
import importlib.util
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from ray_tpu.models.sequence_lm import SequenceLM
from ray_tpu.models.sequence_lm.generation import Autoregressive, BlockDiffusion
from ray_tpu.ops import cached_attention, flash_attention, moe, sparse_index

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 64
T = 16
TOPK = 8
EPISODE = 64
LOGIT_TOL = 3e-4
GRAD_LEAF_TOL = 2e-3


def _reference():
    path = os.path.join(ROOT, "perf", "reference", "keye2.py")
    spec = importlib.util.spec_from_file_location("ref_keye2", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _reference()


def small_config(**over):
    lm = {
        "model_type": "KeyeVL2", "hidden_size": 64, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "rope_theta": 10000.0,
        "rope_scaling": {"mrope_section": [2, 3, 3], "rope_type": "default",
                         "type": "default"},
        "num_experts": 2, "router_outputs": 8, "experts_held": [0, 2],
        "num_experts_per_tok": 3, "moe_intermediate_size": 16,
        "norm_topk_prob": True,
        "sa_config": {"indexer_num_heads": 8, "indexer_head_dim": 16,
                      "indexer_num_kv_heads": 1, "topk": TOPK,
                      "q_chunk_size": 4, "kv_chunk_size": 4},
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


def _apply(model):
    """Either form, with every query's chosen rows: ``(logits, values,
    state, choices (layers, B, T, slots))``."""
    def apply(p, tok, state, fresh):
        stats = {"index_choices": None}
        logits, value, state = model.apply(
            p, tok, state, resets=fresh, stats_out=stats)
        return logits, value, state, stats["index_choices"]

    return jax.jit(apply)


@pytest.fixture(scope="module")
def setup():
    config = small_config()
    params = ref.init_params(jax.random.PRNGKey(7), config, VOCAB)
    batch = ref.make_batch(np.random.default_rng(3), config, 4 * T, VOCAB)
    model = _model(config)
    fns = {
        "apply": _apply(model),
        "reference": jax.jit(lambda p, tok, state, fresh: ref.forward(
            p, tok, state, fresh, config, VOCAB)),
    }
    return config, params, model, batch, fns


def _chain(step, params, tokens, state, fresh):
    """Token by token through the carried state: ``(logits (N, T, V),
    values (N, T), state, choices (layers, N, T, slots))``."""
    logits, values, choices = [], [], []
    for i in range(tokens.shape[1]):
        lg, v, state, chosen = step(
            params, jnp.asarray(tokens[:, i : i + 1, None]), state,
            jnp.asarray(fresh[:, i : i + 1], jnp.float32))
        logits.append(lg)
        values.append(v)
        choices.append(chosen[:, :, 0])
    return jnp.stack(logits, 1), jnp.stack(values, 1), state, jnp.stack(choices, 2)


def _positions(depths, fresh):
    """Each token's position in its episode, and the slot of each of the
    fragment's own rows that the one-token form finds it in."""
    out = np.zeros(fresh.shape, np.int64)
    for n, p in enumerate(depths):
        for i in range(fresh.shape[1]):
            p = 0 if fresh[n, i] else p
            out[n, i] = p
            p += 1
    return out


def _by_slot(choices, positions, seg):
    """A fragment form's choices ``(layers, N, T, S + T)`` (the slots,
    then the fragment's own rows) as the one-token form states them, by
    slot alone ``(layers, N, T, S)``: an own row of the query's episode
    lies in the slot of its position."""
    choices = np.asarray(choices)
    s = choices.shape[-1] - choices.shape[-2]
    out = choices[..., :s].copy()
    for n in range(choices.shape[1]):
        for t in range(choices.shape[2]):
            for own in range(t + 1):
                if seg[n, own] == seg[n, t]:
                    assert not out[:, n, t, positions[n, own]].any()
                    out[:, n, t, positions[n, own]] = choices[:, n, t, s + own]
    return out


def _assert_states_agree(got, want, atol=2e-4):
    """Slot for slot below each stream's position: all THREE leaves a
    layer."""
    depth = np.asarray(want[-1])
    assert np.array_equal(np.asarray(got[-1]), depth)
    assert len(got) == len(want) == 3 * 2 + 1
    for a, b in zip(got[:-1], want[:-1]):
        assert a.shape == b.shape
        live = np.arange(a.shape[1])[None] < depth[:, None]
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


# -- the op ---------------------------------------------------------------------


def _choice_by_hand(index, seen, k):
    """Row by row: the seen slots by falling score, a tie to the lower
    slot, the first ``k``."""
    out = np.zeros(index.shape, bool)
    for row, (scores, ok) in enumerate(zip(index, seen)):
        order = sorted(np.flatnonzero(ok), key=lambda s: (-scores[s], s))
        out[row, order[:k]] = True
    return out


@pytest.mark.parametrize("k", [1, 5, 16, 40])
def test_the_choice_is_exact_with_ties_to_the_lower_slot(k):
    """The choice (a mask by the ``k``-th largest score) against a sort
    by hand and against ``lax.top_k``'s slot numbers, on scores with
    many ties (whole numbers 0-3, zeros of both signs) and rows that see
    fewer than ``k``, exactly ``k`` and more."""
    rng = np.random.default_rng(k)
    index = rng.integers(0, 4, (12, 32)).astype(np.float32)
    index[:, ::5] *= -0.0
    seen = rng.random((12, 32)) < rng.random((12, 1))
    seen[0], seen[1] = False, True
    seen[2] = np.arange(32) < k
    want = _choice_by_hand(np.where(index == 0, 0.0, index), seen, k)
    tidy = jnp.where(jnp.asarray(index) == 0.0, 0.0, jnp.asarray(index))
    mask = sparse_index.select(tidy, jnp.asarray(seen), k)
    assert np.array_equal(np.asarray(mask), want)
    best, slots = jax.lax.top_k(jnp.where(jnp.asarray(seen), tidy, -jnp.inf), min(k, 32))
    by_number = np.zeros_like(want)
    for row in range(12):
        by_number[row, np.asarray(slots)[row][np.asarray(best)[row] > -np.inf]] = True
    assert np.array_equal(by_number, want)
    assert np.array_equal(want.sum(1), np.minimum(seen.sum(1), k))


@pytest.mark.parametrize("k", [1, 7, 64])
def test_kth_largest_is_the_sorted_rows_kth_number(k):
    """The radix select over ordered bit patterns against a sort:
    negative numbers, infinities of the mask, duplicates at the
    threshold, subnormals."""
    rng = np.random.default_rng(k)
    x = rng.standard_normal((9, 64)).astype(np.float32)
    x[0] = np.round(x[0])            # many duplicates
    x[1, ::2] = -np.inf              # half masked
    x[2] = -np.abs(x[2])             # all negative
    x[3, :60] = -np.inf              # fewer than k left for k = 7, 64
    x[4] *= 1e-42                    # subnormals of both signs
    x[4] = np.where(x[4] == 0, 0.0, x[4])
    x[5] = 3.5
    got = np.asarray(sparse_index.kth_largest(jnp.asarray(x), k))
    want = np.sort(x, axis=-1)[:, ::-1][:, k - 1 : k]
    assert np.array_equal(got, want)


def test_index_scores_are_the_weighted_relu_sum_and_zero_has_one_sign():
    rng = np.random.default_rng(0)
    q = rng.standard_normal((2, 3, 4, 8)).astype(np.float32)
    keys = rng.standard_normal((2, 5, 8)).astype(np.float32)
    w = rng.standard_normal((2, 3, 4)).astype(np.float32)
    q[0, 0] = -np.abs(q[0, 0])
    keys[0] = np.abs(keys[0])  # no head of query (0, 0) fires
    want = np.einsum("bths,bth->bts", np.maximum(
        np.einsum("bthd,bsd->bths", q, keys), 0.0), w)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(sparse_index.scores(*(jnp.asarray(a) for a in (q, w, keys))))
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert not np.signbit(got[0, 0]).any() and (got[0, 0] == 0).all()


@pytest.mark.parametrize("heads,tokens,rows,tile", [
    (48, 256, 16384 + 256, 128),  # the Keye cell's layer and its index
    (48, 256, 8192 + 256, 256),
    (12, 16, 80, 16),             # a test's
])
def test_queries_of_a_score_tile_follow_from_the_rows_they_see(heads, tokens, rows, tile):
    assert cached_attention.query_tile(heads, tokens, rows) == tile


# -- the family -------------------------------------------------------------------


def test_the_stack_and_how_it_generates_are_apart(setup):
    """``KeyeVL2`` takes SDAR's stack of layers and generates one token a
    step; SDAR still generates by blocks and has no index."""
    config, params, model, _, _ = setup
    assert isinstance(model.generation, Autoregressive)
    assert model.layer_types == ("full_attention",) * 2
    assert model.ffn_types == ("experts",) * 2
    layer = model.segments[0].mixer
    assert (layer.block, layer.qk_norm, layer.gate, layer.rotary) == (1, True, None, 16)
    assert layer.indexer == type(layer.indexer)(heads=8, head_dim=16, top_k=TOPK)
    assert model.segments[0].ffn.shared_width == 0
    lm = dict(config["algo_config"]["model"]["sequence_lm"], model_type="sdar_moe",
              block_length=4, denoising_steps=2, mask_token_id=VOCAB - 1)
    del lm["sa_config"]
    sdar = SequenceLM(VOCAB, lm, dtype="float32")
    assert isinstance(sdar.generation, BlockDiffusion)
    assert sdar.segments[0].mixer.indexer is None
    assert len(sdar.initial_state(1)) == 2 * 2 + 1


def test_param_tree_and_state_match_the_reference(setup):
    """The index's five leaves a layer beside the attention's, and a
    THIRD cache leaf of the index head's width."""
    config, params, model, _, _ = setup
    want = {g: {k: v.shape for k, v in leaves.items()} for g, leaves in params.items()}
    assert model.param_shapes() == want
    assert want["layer_0"]["index_q_proj"] == (64, 8 * 16)
    assert want["layer_0"]["index_k_proj"] == (64, 16)
    assert want["layer_0"]["index_w_proj"] == (64, 8)
    assert want["layer_0"]["index_k_norm"] == want["layer_0"]["index_k_norm_bias"] == (16,)
    made = model.init(jax.random.PRNGKey(0))
    assert jax.tree_util.tree_map(lambda x: x.shape, made) == want
    z = ref.sizes(config, VOCAB)
    got = [s.shape for s in model.initial_state(5)]
    assert got == [s.shape for s in ref.initial_state(z, 5)]
    assert got == [(5, EPISODE, 32), (5, EPISODE, 32), (5, EPISODE, 16)] * 2 + [(5,)]


@pytest.mark.parametrize("bad,match", [
    ({"sa_config": {"indexer_num_heads": 8, "indexer_head_dim": 16,
                    "indexer_num_kv_heads": 2, "topk": 8}}, "ONE key a row"),
    ({"rope_scaling": {"mrope_section": [2, 3, 4], "rope_type": "default"}},
     "M-RoPE sections"),
    ({"rope_scaling": {"rope_type": "yarn", "factor": 4.0}}, "M-RoPE sections"),
])
def test_what_the_index_cannot_read_is_refused_by_name(bad, match):
    with pytest.raises(ValueError, match=match):
        _model(small_config(**bad))


@pytest.mark.parametrize("start", [0, 37])
def test_one_token_steps_through_a_whole_episode_equal_the_reference(setup, start):
    """Token by token through the three carried caches for an episode's
    length and on into the next episode against the reference's full
    masked forward: logits, values, the state, and every query's chosen
    rows slot for slot."""
    config, params, model, _, fns = setup
    rng = np.random.default_rng(11 + start)
    n, steps = 3, EPISODE + 8
    tokens = rng.integers(0, VOCAB, (n, steps)).astype(np.int32)
    z = ref.sizes(config, VOCAB)
    state = list(ref.make_state(rng, z, n, T))
    depths = [start, start, 0]
    state[-1] = np.asarray(depths, np.int32)
    fresh = np.zeros((n, steps), bool)
    fresh[:2, EPISODE - start] = True  # the episode ends at its fixed length
    fresh[2, 0] = fresh[2, EPISODE] = True
    state = _f32_state(state)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, tok, st, fr: ref.forward(
            p, tok, st, fr, config, VOCAB))(params, tokens, state, fresh)
        logits, values, after, choices = _chain(
            fns["apply"], params, tokens, state, fresh)
    np.testing.assert_allclose(logits, want["logits"], atol=LOGIT_TOL, rtol=LOGIT_TOL)
    np.testing.assert_allclose(values, want["value"], atol=LOGIT_TOL, rtol=LOGIT_TOL)
    _assert_states_agree(after, want["state"])
    positions = _positions(depths, fresh)
    assert np.array_equal(
        np.asarray(choices),
        _by_slot(want["selected"], positions, np.cumsum(fresh, axis=1)))
    kept = np.asarray(choices).sum(-1)
    assert np.array_equal(kept, np.broadcast_to(
        np.minimum(positions + 1, TOPK), kept.shape))


@pytest.mark.parametrize("depths,reset_at", [
    ((0, 3, 5), None),       # below topk: every row seen is chosen
    ((8, 7, 6), None),       # at it
    ((48, 20, 33), None),    # beyond it: the index chooses
    ((48, 12, 30), 5),       # an episode opens inside the fragment
    ((40, 40, 40), 11),
], ids=["below", "at", "beyond", "reset_early", "reset_late"])
def test_fragment_form_equals_reference_and_steps_and_chooses_the_same_rows(
        setup, depths, reset_at):
    """The fragment form (16 tokens from a stored start state, twice
    ``topk``) against the reference's full forward AND against the chain
    of one-token steps, the PPO ratio's two sides: logits, values, state
    and the chosen rows of all three."""
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
        logits, value, after, choices = fns["apply"](
            params, jnp.asarray(tokens[..., None]), state,
            jnp.asarray(fresh, jnp.float32))
        chain_logits, chain_values, chain_after, chain_choices = _chain(
            fns["apply"], params, tokens, state, fresh)
    for got_l, got_v in ((logits.reshape(n, T, -1), value.reshape(n, T)),
                         (chain_logits, chain_values)):
        np.testing.assert_allclose(
            got_l, want["logits"], atol=LOGIT_TOL, rtol=LOGIT_TOL)
        np.testing.assert_allclose(got_v, want["value"], atol=LOGIT_TOL, rtol=LOGIT_TOL)
    _assert_states_agree(after, want["state"])
    _assert_states_agree(chain_after, want["state"])
    assert np.array_equal(np.asarray(choices), np.asarray(want["selected"]))
    positions, seg = _positions(depths, fresh), np.cumsum(fresh, axis=1)
    assert np.array_equal(
        np.asarray(chain_choices), _by_slot(want["selected"], positions, seg))
    kept = np.asarray(choices).sum(-1)
    assert np.array_equal(kept, np.broadcast_to(
        np.minimum(positions + 1, TOPK), kept.shape))
    if reset_at is not None:
        # no query of the new episode chooses a stored row (all of the
        # episode before) or an own row from before the reset
        for stream, at in ((0, reset_at), (2, reset_at + 2)):
            new = np.asarray(choices)[:, stream, at:]
            assert not new[..., :EPISODE].any()
            assert not new[..., EPISODE:EPISODE + at].any()


def test_an_index_that_keeps_every_row_is_the_stack_without_one(setup):
    """``topk`` out of every depth's reach: logits, values, keys and
    values equal, TO THE BIT, those of the same weights without
    ``sa_config`` in both forms (the guard on the code SDAR's and the
    other families' layers share); the third leaf is still written."""
    config, params, _, batch, _ = setup
    wide = small_config()
    wide["algo_config"]["model"]["sequence_lm"]["sa_config"] = dict(
        config["sa_config"], topk=EPISODE)
    lm = dict(config["algo_config"]["model"]["sequence_lm"])
    del lm["sa_config"], lm["rope_scaling"]
    plain = SequenceLM(VOCAB, lm, dtype="float32")
    plain.learn_streams = 2
    bare = {g: {k: v for k, v in leaves.items() if not k.startswith("index_")}
            for g, leaves in params.items()}
    state = _f32_state(ref.batch_state(batch))
    two = tuple(s for i, s in enumerate(state[:-1]) if i % 3 != 2) + state[-1:]
    tokens = jnp.asarray(batch["obs"]).reshape(-1, T, 1)
    resets = jnp.asarray(batch["resets"]).reshape(-1, T)
    for tok, fr in ((tokens, resets), (tokens[:, :1], resets[:, :1])):
        got = _apply(_model(wide))(params, tok, state, fr)
        want = jax.jit(lambda p, t, s, f: plain.apply(p, t, s, resets=f))(
            bare, tok, two, fr)
        assert np.array_equal(np.asarray(got[0]), np.asarray(want[0]))
        assert np.array_equal(np.asarray(got[1]), np.asarray(want[1]))
        kept = tuple(s for i, s in enumerate(got[2][:-1]) if i % 3 != 2)
        for a, b in zip(kept + got[2][-1:], want[2]):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        # every row seen is chosen, and the index keys are written
        assert np.array_equal(
            np.asarray(got[3]).sum(-1)[0],
            np.asarray(_positions(np.asarray(state[-1]), np.asarray(fr) > 0.5)) + 1)
        assert not np.array_equal(np.asarray(got[2][2]), np.asarray(state[2]))


def test_loss_and_every_gradient_leaf_match_reference_and_the_index_takes_none(setup):
    """The model under the reference's loss against the reference's own
    loss and gradient, leaf by leaf, through the chosen rows only; the
    index's five leaves read EXACTLY zero on both sides."""
    config, params, model, batch, _ = setup
    dev = {k: jnp.asarray(v) for k, v in batch.items()}
    rows = batch["obs"].shape[0]
    assert float(batch["resets"].sum()) >= 1
    assert int(np.max(batch["__chunk__state_in_6"])) > TOPK

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
        for leaf in ("router", "experts_gate", "experts_down", "q_proj", "k_proj",
                     "v_proj", "o_proj", "q_norm", "k_norm"):
            assert float(np.linalg.norm(got[layer][leaf])) > 0, (layer, leaf)
        for leaf in ("index_q_proj", "index_k_proj", "index_w_proj", "index_k_norm",
                     "index_k_norm_bias"):
            for side in (got, want):
                assert not np.asarray(side[layer][leaf]).any(), (layer, leaf)


@pytest.mark.parametrize("tokens,top_k,lowering", [
    (24, 3, "dense"), (512, 1, "grouped")])
def test_the_eight_shares_add_up_to_the_uncut_layer(tokens, top_k, lowering):
    """Eight chips hold one expert each of a layer's eight: what their
    expert layers give for the same tokens (each routes over all eight
    and leaves out what it does not hold) adds up to the reference's
    uncut layer. There is no shared expert, so nothing is counted once."""
    uncut = small_config(num_experts=8, experts_held=[0, 8], num_experts_per_tok=top_k)
    z = ref.sizes(uncut, VOCAB)
    params = ref.init_params(jax.random.PRNGKey(3), uncut, VOCAB)["layer_1"]
    assert moe.product_lowering(tokens, top_k, 8) == lowering
    rng = np.random.default_rng(tokens)
    g = jnp.asarray(rng.standard_normal((1, tokens, 64)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        idx, w = ref._route(params, g, z)
        want = ref._experts(params, g, idx, w, z, lambda v: v)
        total = jnp.zeros_like(want)
        for first in range(8):
            share = _model(small_config(
                num_experts=1, experts_held=[first, 1], num_experts_per_tok=top_k))
            mine = {k: v[first : first + 1] if k.startswith("experts_") else v
                    for k, v in params.items()}
            out, _, load = share.segments[-1].ffn.apply(
                mine, g, (), {"scope": "", "dtype": jnp.float32})
            assert float(load["moe_held_load"].sum()
                         + load["moe_slots_on_absent_experts"]) == tokens * top_k
            total = total + out
    assert float(jnp.abs(want).max()) > 0.1
    np.testing.assert_allclose(total, want, atol=1e-5, rtol=1e-4)


def test_reset_state_leaves_all_three_caches_and_zeroes_the_position(setup):
    """The third leaf is a cache like the other two: a reset moves the
    position and leaves the rows, which no query of the new episode sees
    (``test_fragment_form...[reset_*]`` holds that)."""
    config, params, model, batch, _ = setup
    state = _f32_state(ref.batch_state(batch))
    after = model.reset_state(state, jnp.asarray([True, False, True, False]))
    for a, b in zip(after[:-1], state[:-1]):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert list(np.asarray(after[-1])[[0, 2]]) == [0, 0]
    assert np.asarray(after[-1])[1] == np.asarray(state[-1])[1]


def test_index_statistics_and_the_counters_label_of_the_path_taken(setup):
    """The learn form's eight statistics by hand from the positions, and
    ``ray_tpu_attention_{fragment,step}_lowerings_total`` under
    ``selected_xla`` for a call with a selection, under the old labels
    for the same stack whose index keeps every row. Off a TPU both
    kernels' rules answer no, so the path and every label here stay
    ``selected_xla`` (what a TPU changes:
    ``test_the_fragment_rule_admits_a_selection_on_a_tpu``)."""
    from ray_tpu.telemetry import metrics

    config, params, model, batch, _ = setup
    rows = batch["obs"].shape[0]
    state = _f32_state(ref.batch_state(batch))
    tokens = jnp.asarray(batch["obs"]).reshape(rows // T, T, 1)
    resets = jnp.asarray(batch["resets"]).reshape(rows // T, T)
    frag, step = (dict(f()) for f in (
        metrics.attention_fragment_lowerings, metrics.attention_step_lowerings))
    stats = {}
    model.apply(params, tokens, state, resets=resets, stats_out=stats)
    model.apply(params, tokens[:, :1], state)
    now_frag, now_step = (
        metrics.attention_fragment_lowerings(), metrics.attention_step_lowerings())
    # the two layers' checkpointed block is one trace of the fragment form
    assert now_frag["selected_xla"] - frag.get("selected_xla", 0) == 1
    assert now_step["selected_xla"] - step.get("selected_xla", 0) == 2
    for label in ("xla", "kernel"):
        assert now_frag.get(label, 0) == frag.get(label, 0)
        assert now_step.get(label, 0) == step.get(label, 0)
    wide = small_config()
    wide["algo_config"]["model"]["sequence_lm"]["sa_config"] = dict(
        config["sa_config"], topk=EPISODE)
    _model(wide).apply(params, tokens, state, resets=resets)
    assert metrics.attention_fragment_lowerings()["xla"] - frag.get("xla", 0) == 1
    assert metrics.attention_fragment_lowerings()["selected_xla"] == (
        now_frag["selected_xla"])
    assert not flash_attention.step_kernel_applies(
        32, 4, 128, 16384, jnp.bfloat16, selected=True)
    assert not flash_attention.fragment_kernel_applies(
        256, 32, 4, 128, 16384, jnp.bfloat16, selected=True)
    positions = _positions(
        np.asarray(state[-1]), np.asarray(resets) > 0.5).astype(np.float64) + 1
    kept = np.minimum(positions, TOPK)
    for form in ("", "decode_"):
        assert float(stats[f"index_{form}rows_scored_mean"]) == pytest.approx(
            positions.mean())
        assert float(stats[f"index_{form}rows_selected_mean"]) == pytest.approx(
            kept.mean())
        assert float(stats[f"index_{form}selected_share_mean"]) == pytest.approx(
            (kept / positions).mean())
        assert float(stats[f"index_{form}dense_query_share"]) == pytest.approx(
            (positions <= TOPK).mean())
    assert 0.2 < float(stats["index_selected_share_mean"]) < 0.8
    metrics.note_index_selection([{k: float(v) for k, v in stats.items()}])
    totals = metrics.index_selection()
    assert totals["updates"] >= 1 and totals["selected_share_mean"] > 0


def _top_k_plus_one(config):
    model = _model(config)
    model.segments = tuple(
        seg._replace(mixer=dataclasses.replace(
            seg.mixer, indexer=dataclasses.replace(seg.mixer.indexer, top_k=TOPK + 1)))
        for seg in model.segments)
    return model


def _no_rope_on_the_index(config):
    model = _model(config)
    kind = type(model.segments[0].mixer)

    class Unturned(kind):
        def selection(self, p, x, index_cache, ctx, scope):
            flat = dict(ctx, positions=jnp.zeros_like(ctx["positions"]))
            return kind.selection(self, p, x, index_cache, flat, scope)

    model.segments = tuple(
        seg._replace(mixer=Unturned(**dataclasses.asdict(seg.mixer) | {
            "indexer": seg.mixer.indexer}))
        for seg in model.segments)
    return model


@pytest.mark.parametrize("tokens,heads,kv,head,depth,tile", [
    (256, 32, 4, 128, 8192, 8),    # the cell's layer: its group of 8 in one tile
    (256, 32, 4, 128, 16384, 8),   # at the episodes ISSUE 65 named
    (128, 64, 8, 64, 2048, 16),    # a packed head: two key heads a block
    (256, 6, 2, 128, 2048, 3),
])
def test_the_fragment_rule_admits_a_selection_on_a_tpu(
        monkeypatch, tokens, heads, kv, head, depth, tile):
    """Where the backend is a TPU the fragment rule answers for a call
    with a selection as for one without, the choice's blocks counted in
    the tile's room (VMEM alone decides a tile: here the one without a
    selection); the step rule keeps answering no (the one-token form under a
    selection is the text: ROADMAP B32 (a)); off a TPU both say no."""
    bf = jnp.bfloat16
    assert not flash_attention.fragment_kernel_applies(
        tokens, heads, kv, head, depth, bf, selected=True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert flash_attention.fragment_kernel_applies(
        tokens, heads, kv, head, depth, bf, selected=True)
    assert flash_attention.fragment_kernel_applies(tokens, heads, kv, head, depth, bf)
    assert flash_attention.fragment_head_tile(
        tokens, heads, kv, head, selected=True) == tile
    assert flash_attention.fragment_head_tile(tokens, heads, kv, head) == tile
    assert not flash_attention.fragment_kernel_applies(
        tokens, heads, kv, head, depth, jnp.float32, selected=True)
    assert not flash_attention.fragment_kernel_applies(
        tokens, heads, kv, head, depth + 24, bf, selected=True)
    assert not flash_attention.step_kernel_applies(
        heads, kv, head, depth, bf, selected=True)
    assert flash_attention.step_kernel_applies(heads, kv, head, depth, bf)
    # the choice's blocks take room: a fragment whose tile just fits
    # without one does not fit with one
    assert flash_attention.fragment_head_tile(1408, 8, 8, 128) == 1
    assert flash_attention.fragment_head_tile(1408, 8, 8, 128, selected=True) == 0


def test_a_selection_takes_the_kernel_where_the_rule_says_so(monkeypatch):
    """``cached_attention(select=)`` where the fragment rule admits the
    call (patched: the kernel pair runs in the interpreter here): the
    choice is made as on the text's path (the same rows, query for
    query), handed to ``fragment_attention`` as ``chosen``, counted
    under ``selected_kernel`` and not ``selected_xla``; ``o`` and the
    gradients are the text's, and the statistics report the kernel's
    walk of the key blocks as they do without an index."""
    import functools

    from ray_tpu.telemetry import metrics

    b, t, kv, group, d, depth, index_heads, width, top_k = 3, 16, 2, 2, 128, 256, 4, 16, 24
    h = kv * group
    rng = np.random.default_rng(5)
    normal = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    q, k, v = normal(b, t, h, d), normal(b, t, kv, d), normal(b, t, kv, d)
    caches = (normal(b, depth, kv * d), normal(b, depth, kv * d))
    pos0 = jnp.asarray([0, 100, 256], jnp.int32)
    fresh = np.zeros((b, t), bool)
    fresh[1, 5] = True
    seg = jnp.asarray(np.cumsum(fresh, 1), jnp.int32)
    steps = np.arange(t)[None]
    opened = np.maximum.accumulate(np.where(fresh, steps, -1), axis=1)
    positions = jnp.where(seg == 0, pos0[:, None] + steps, steps - opened).astype(jnp.int32)
    rows = {"seg": seg, "positions": positions, "pos0": pos0, "choices": True}
    select = cached_attention.Selection(
        normal(b, t, index_heads, width), jnp.abs(normal(b, t, index_heads)),
        normal(b, t, width), normal(b, depth, width), top_k)
    w = normal(b, t, h, d)

    def call(q, k, v):
        o, _, stats = cached_attention.cached_attention(
            q, k, v, caches, rows, scale=d ** -0.5, window=None, dtype=jnp.float32,
            scope="attn", select=select)
        return jnp.sum(o * w), (o, stats)

    count = lambda: dict(metrics.attention_fragment_lowerings())
    before = count()
    (_, (want, want_stats)), want_grads = jax.value_and_grad(
        call, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    text = count()
    assert text.get("selected_xla", 0) - before.get("selected_xla", 0) == 1
    assert text.get("selected_kernel", 0) == before.get("selected_kernel", 0)
    assert int(want_stats["attn_key_blocks_walked"]) == 0

    asked = []
    monkeypatch.setattr(
        flash_attention, "fragment_kernel_applies",
        lambda *a, selected=False: asked.append(selected) or True)
    monkeypatch.setattr(
        flash_attention, "fragment_attention",
        functools.partial(flash_attention.fragment_attention, interpret=True))
    (_, (got, stats)), grads = jax.value_and_grad(
        call, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    now = count()
    assert asked == [True]
    assert now.get("selected_kernel", 0) - text.get("selected_kernel", 0) == 1
    assert now.get("selected_xla", 0) == text.get("selected_xla", 0)
    assert now.get("kernel", 0) == before.get("kernel", 0)
    assert metrics.attention_fragment_lowerings()["selected_kernel"] >= 1
    # the same choice, the rows a query attended to, and the text's numbers
    assert np.array_equal(
        np.asarray(stats["index_choices"]), np.asarray(want_stats["index_choices"]))
    assert np.array_equal(np.asarray(stats["index_rows_selected"]),
                          np.asarray(want_stats["index_rows_selected"]))
    assert float(jnp.min(stats["index_rows_selected"])) >= 1
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-4)
    for a, c in zip(grads, want_grads):
        assert float(jnp.max(jnp.abs(c))) > 0.1
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=1e-4, rtol=1e-4)
    # one stored block of 256 and the own, a key head: the stream at 0
    # skips the stored one
    assert (int(stats["attn_key_blocks_skipped"]),
            int(stats["attn_key_blocks_walked"])) == (1, 6)


@pytest.mark.parametrize("wrong", [_top_k_plus_one, _no_rope_on_the_index])
@pytest.mark.parametrize("form", ["fragment", "steps"])
def test_a_wrong_choice_fails_the_comparison(setup, wrong, form):
    """One row too many, or index keys without their positions: each
    reads far outside the tolerance against the reference, in either
    form."""
    config, params, _, _, fns = setup
    apply = _apply(wrong(config))
    rng = np.random.default_rng(17)
    n = 3
    tokens = rng.integers(0, VOCAB, (n, T)).astype(np.int32)
    state = list(ref.make_state(rng, ref.sizes(config, VOCAB), n, T))
    state[-1] = np.asarray([48, 20, 33], np.int32)
    state = _f32_state(state)
    fresh = np.zeros((n, T), bool)
    with jax.default_matmul_precision("highest"):
        want = fns["reference"](params, tokens, state, fresh)
        if form == "fragment":
            logits = apply(params, jnp.asarray(tokens[..., None]), state,
                           jnp.asarray(fresh, jnp.float32))[0].reshape(n, T, -1)
        else:
            logits = _chain(apply, params, tokens, state, fresh)[0]
    assert float(jnp.abs(logits - want["logits"]).max()) > 30 * LOGIT_TOL


@pytest.mark.parametrize("precision", ["int8", "fp8"])
def test_the_controls_fail_the_tolerances(setup, precision):
    """The reference computed one precision step below the bfloat16 the
    configuration states, in the system's place, fails the logit
    tolerance and the gradient's, and chooses other rows."""
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
    assert not np.array_equal(np.asarray(low["selected"]), np.asarray(want["selected"]))
