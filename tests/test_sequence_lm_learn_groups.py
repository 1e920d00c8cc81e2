"""Where the learn form groups the streams inside a block
(``SequenceLM._stack``: a plain residual, more streams than
``learn_streams``), only the MIXER'S half of the block runs under the
loop over groups; the feed-forward's half runs once over all streams.
Held here, at the families' small test configs: the grouped model
against the same model with every stream in one group (logits, value,
every gradient leaf, every statistic), and the traced program's
structure (no feed-forward scope inside the loop over groups; one
checkpointed call a block where there is no such loop, as before the
split).
"""

import importlib

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from ray_tpu.models.sequence_lm import SequenceLM
from ray_tpu.ops import moe

VOCAB = 64
T = 16

# family -> (the test file with its ``small_config``, overrides, streams x tokens)
FAMILIES = {
    # mixer + experts with a shared expert (Qwen3-Next)
    "qwen3next": ("sequence_lm", {}, (4, T)),
    # the same at 256 tokens of top-3 of 64: the GROUPED product, one call
    "qwen3next_grouped_product": (
        "sequence_lm",
        dict(held=(2, 4), router_outputs=64, max_position_embeddings=96), (4, 64)),
    # the router reads the block's input, before the mixer (SmallThinker)
    "smallthinker": ("window_lm", {}, (4, T)),
    # blocks of ONE sublayer: mixer-only, expert-only (Nemotron)
    "nemotron": ("nemotron_h_lm", {}, (4, T)),
    # dense MLPs, the state-space runs stacked and scanned (Granite)
    "granite_dense_mlp": ("ssm_lm", {}, (4, T)),
    # a dense first layer, then experts with an ungated shared one (Laguna)
    "laguna": ("mixed_attention_lm", {}, (4, T)),
}


def _model(family, learn_streams):
    file, over, _ = FAMILIES[family]
    config = importlib.import_module("tests.test_" + file).small_config(**over)
    model = SequenceLM(
        VOCAB, config["algo_config"]["model"]["sequence_lm"], dtype="float32")
    model.chunk, model.learn_streams = 8, learn_streams
    return model


def _fresh(b, t):
    """An episode opens inside two of the fragments."""
    return jnp.zeros((b, t)).at[1, 5].set(1.0).at[b - 1, t - 3].set(1.0)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_grouped_mixers_with_one_feed_forward_call_equal_one_group(family):
    """4 streams at ``learn_streams`` 2 (the mixers in two groups, the
    feed-forwards once over all four) against ``learn_streams`` 4 (one
    checkpointed block, no loop)."""
    b, t = FAMILIES[family][2]
    model = _model(family, 2)

    @jax.jit
    def inputs(key):
        keys = jax.random.split(key, 3)
        # every leaf random: the shared ladder leaves the vectors at zero
        leaves, tree = jax.tree_util.tree_flatten(model.init(keys[0]))
        params = jax.tree_util.tree_unflatten(tree, [
            x + 0.1 * jax.random.normal(k, x.shape)
            for x, k in zip(leaves, jax.random.split(keys[1], len(leaves)))])
        return params, jax.random.randint(keys[2], (b, t), 0, VOCAB)

    params, tokens = inputs(jax.random.PRNGKey(11))
    state, fresh = model.initial_state(b), _fresh(b, t)
    results = {}
    for learn_streams in (2, 4):
        model.learn_streams = learn_streams

        def outputs(params):
            stats = {"moe_routes": None}
            logits, value, new = model.apply(
                params, tokens, state, resets=fresh, stats_out=stats)
            return jnp.sum(jnp.sin(logits)) + jnp.sum(value), (logits, value, new, stats)

        with jax.default_matmul_precision("highest"):
            results[learn_streams] = jax.jit(
                jax.value_and_grad(outputs, has_aux=True))(params)
    ((_, (logits, value, new, stats)), grads), ((_, want), grads_want) = (
        results[2], results[4])
    np.testing.assert_allclose(logits, want[0], rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(value, want[1], rtol=2e-5, atol=2e-5)
    for a, w in zip(new, want[2]):
        np.testing.assert_allclose(a, w, rtol=2e-5, atol=2e-5)
    flat, flat_want = (jax.tree_util.tree_leaves_with_path(g) for g in (grads, grads_want))
    assert len(flat) == len(flat_want) > 0
    for (path, a), (_, w) in zip(flat, flat_want):
        np.testing.assert_allclose(
            a, w, atol=2e-4 * max(1.0, float(jnp.abs(w).max())), err_msg=str(path))
    # every statistic: the loads' ratios, the computed rows' share, the
    # share of held experts a place's tokens touched, every token's set
    assert sorted(stats) == sorted(want[3])
    for key in stats:
        np.testing.assert_allclose(stats[key], want[3][key], rtol=1e-6, err_msg=key)
    routed = any(s.ffn.route_on for s in model.segments)
    if routed:
        assert stats["moe_routes"].shape[1] == b * t
        assert "moe_decode_held_experts_touched_share" in stats
    if family == "qwen3next_grouped_product":
        assert moe.product_lowering(b * t, 3, 64) == "grouped"
        np.testing.assert_allclose(
            float(stats["moe_rows_computed_share"]),
            moe.expert_buffer_rows(b * t, 3, 64) / (b * t))


# -- the traced program's structure ---------------------------------------

# 10 streams in 5 groups: no other loop of these programs has 5 steps
# (runs of 1-3 layers, 2 chunks a fragment)
GROUPS, STREAMS = 5, 10
MIXER_SCOPES = ("p/attn", "p/swa", "p/linear_attn", "p/ssm")
FEED_FORWARD_SCOPES = ("p/moe/route", "p/moe/experts", "p/moe/shared", "p/mlp")
STRUCTURES = sorted(set(FAMILIES) - {"qwen3next_grouped_product"})


def _walk(jaxpr, prefix="", loops=(), checkpoints=0):
    """``(equation, its whole name stack, lengths of the scans around
    it, checkpointed calls around it)`` of every equation, nested jaxprs
    under their equation's."""
    for eqn in jaxpr.eqns:
        stack = prefix + str(eqn.source_info.name_stack)
        yield eqn, stack, loops, checkpoints
        name = eqn.primitive.name
        inside = loops + ((eqn.params["length"],) if name == "scan" else ())
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (tuple, list)) else (value,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _walk(inner, stack + "/" if stack else "", inside,
                                     checkpoints + (name == "remat2"))


def _traced(family, learn_streams):
    """The learn form of ``STREAMS`` streams, forward: the model, every
    equation, and the BLOCKS' checkpointed calls (those inside no other)
    as ``(loops around it, a mixer's scope inside it, a feed-forward's)``."""
    model = _model(family, learn_streams)
    jaxpr = jax.make_jaxpr(lambda p: model.apply(
        p, jnp.zeros((STREAMS, T), jnp.int32), model.initial_state(STREAMS),
        resets=_fresh(STREAMS, T), scope="p", stats_out={})
    )(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    eqns = list(_walk(jaxpr.jaxpr))
    has = lambda stacks, scopes: any(scope in s for s in stacks for scope in scopes)
    calls = []
    for eqn, _, loops, checkpoints in eqns:
        if eqn.primitive.name == "remat2" and not checkpoints:
            inner = [s for _, s, _, _ in _walk(eqn.params["jaxpr"])]
            calls.append((loops, has(inner, MIXER_SCOPES), has(inner, FEED_FORWARD_SCOPES)))
    return model, eqns, calls


@pytest.mark.parametrize("family", STRUCTURES)
def test_no_feed_forward_scope_inside_the_loop_over_groups(family):
    model, eqns, calls = _traced(family, STREAMS // GROUPS)
    in_loop = [stack for _, stack, loops, _ in eqns if GROUPS in loops]
    assert any(scope in stack for stack in in_loop for scope in MIXER_SCOPES)
    assert not [stack for stack in in_loop
                if any(scope in stack for scope in FEED_FORWARD_SCOPES)]
    # each half a segment has under a checkpoint of its own: a mixer's
    # inside the loop, a feed-forward's outside (a stacked run's, which
    # lie in the scan over its layers, once)
    mixers = sum(not s.mixer.absent for s in model.segments)
    feed_forwards = sum(not s.ffn.absent for s in model.segments)
    assert sorted((GROUPS in loops, mixer, ffn) for loops, mixer, ffn in calls) == (
        [(False, False, True)] * feed_forwards + [(True, True, False)] * mixers)


@pytest.mark.parametrize("family", STRUCTURES)
def test_one_checkpointed_call_a_block_where_the_streams_are_one_group(family):
    """``b == learn_streams``: there is no loop over groups and a block
    is ONE checkpointed function of the halves it has, so the programs
    of the cells that do not group inside a block are what they were."""
    model, eqns, calls = _traced(family, STREAMS)
    assert not any(GROUPS in loops for _, _, loops, _ in eqns)
    assert sorted((mixer, ffn) for _, mixer, ffn in calls) == sorted(
        (not s.mixer.absent, not s.ffn.absent) for s in model.segments)
