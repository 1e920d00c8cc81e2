"""The SambaY kinds of the sequence model (``model_type: phi4flash``: a
Mamba-1 selective scan, differential attention on a ring and at full
depth, a gated memory unit, a cross-attention that reads a cache it does
not own, LayerNorm; ``models/sequence_lm``, ``ops/selective_scan.py``)
and the layer loop's export / import channel, held to the plain
reference (``perf/reference/phi4_flash.py``) on seeded weights at a small
size: hidden 64, 8 heads over 4 key heads of 8, inner 128, state 4,
``dt_rank`` 4, a window of 8, episodes of 40, the six layers of the
published indices 0, 1, 16, 17, 18, 19, a vocabulary of 50.

Tolerances. Both sides are float32 at precision "highest" here, caches
included, so they differ by summation order only (the stored cache
against the full score matrix, the paired 2 x head score product with
its zero halves against the head's own): 3e-4 on logits of order one
(read: 2e-6 in the fragment form, 3e-5 over two episodes of steps), 2e-3
of a gradient leaf's norm (read: 6e-6; a lambda vector's gradient is a
difference of two large sums and reads up to 3e-4). A wrong variant
reads 30 to 1,000 times the logits' tolerance, the int8 and fp8 controls
and a bfloat16 scan or memory 10 to 100 times.
"""

import functools
import importlib.util
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from ray_tpu.models.sequence_lm import SequenceLM, config as config_lib, kinds
from ray_tpu.ops import flash_attention, selective_scan
from ray_tpu.telemetry import metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 50
T = 6
WINDOW, EPISODE = 8, 40
INDICES = (0, 1, 16, 17, 18, 19)
LOGIT_TOL = 3e-4
GRAD_LEAF_TOL = 2e-3


def _reference():
    path = os.path.join(ROOT, "perf", "reference", "phi4_flash.py")
    spec = importlib.util.spec_from_file_location("ref_phi4_flash", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _reference()


def small_config(indices=INDICES, **over):
    lm = {
        "model_type": "phi4flash", "hidden_size": 64, "intermediate_size": 96,
        "num_attention_heads": 8, "num_key_value_heads": 4,
        "num_hidden_layers": len(indices), "layer_indices": list(indices),
        "published_num_hidden_layers": 32, "mb_per_layer": 2,
        "sliding_window": WINDOW, "layer_norm_eps": 1e-5,
        "max_position_embeddings": EPISODE, "tie_word_embeddings": True,
        "mamba_d_state": 4, "mamba_d_conv": 4, "mamba_expand": 2, "mamba_dt_rank": 4,
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


def _f32(state):
    return tuple(jnp.asarray(s, jnp.int32 if s.dtype == np.int32 else jnp.float32)
                 for s in state)


def _state_at(config, depths, seed=5):
    """Start states as ``depths`` tokens of an episode leave them, every
    slot of every cache filled (float32 here: the comparison is of the
    arithmetic, not of the cache's rounding)."""
    z = ref.sizes(config, VOCAB)
    state = list(ref.make_state(np.random.default_rng(seed), z, len(depths), T))
    state[-1] = np.asarray(depths, np.int32)
    return _f32(state)


@pytest.fixture(scope="module")
def setup():
    config = small_config()
    params = ref.init_params(jax.random.PRNGKey(7), config, VOCAB)
    model = _model(config)
    fns = {
        "reference": jax.jit(lambda p, tok, st, fr: ref.forward(
            p, tok, st, fr, config, VOCAB)),
        "fragment": jax.jit(lambda p, tok, st, fr: model.apply(
            p, tok[..., None], st, resets=fr)),
        "step": jax.jit(lambda p, tok, st, fr: model.apply(p, tok, st, resets=fr)),
    }
    return config, params, model, fns


def _chain(step, params, tokens, state, fresh):
    """``(logits (B, T, V), state after)`` of one-token steps."""
    out = []
    for i in range(tokens.shape[1]):
        logits, _, state = step(
            params, jnp.asarray(tokens[:, i:i + 1, None]), state,
            jnp.asarray(fresh[:, i:i + 1], jnp.float32))
        out.append(logits)
    return jnp.stack(out, axis=1), state


def _leaf_errors(got, want):
    whole = np.sqrt(sum(float(jnp.sum(g * g)) for g in jax.tree_util.tree_leaves(want)))
    return {
        (group, leaf): np.linalg.norm(
            np.asarray(got[group][leaf]) - np.asarray(want[group][leaf]))
        / max(np.linalg.norm(np.asarray(want[group][leaf])), 1e-3 * whole)
        for group in want for leaf in want[group]
    }


# -- (a) the tree, the state, describe ---------------------------------------------


def test_param_tree_and_state_match_the_reference(setup):
    config, params, model, _ = setup
    shapes = model.param_shapes()
    want = ref.param_shapes(config, VOCAB)
    assert {g: {k: tuple(s) for k, s in v.items()} for g, v in shapes.items()} == want
    assert {g: {k: v.shape for k, v in leaves.items()}
            for g, leaves in ref.to_policy_tree(params, config).items()} == want
    # a gated-memory layer and a cross layer own no state; the scan's
    # matrix lies (state, inner), the channels on the lanes
    state = model.initial_state(3)
    assert [s.shape for s in state] == [
        s.shape for s in ref.initial_state(ref.sizes(config, VOCAB), 3)] == [
        (3, 4, 128), (3, 3, 128), (3, WINDOW, 32), (3, WINDOW, 32),
        (3, 4, 128), (3, 3, 128), (3, EPISODE, 32), (3, EPISODE, 32), (3,)]
    assert model.layer_types == (
        "selective_scan", "sliding_attention", "selective_scan", "attention",
        "gated_memory", "cross_attention")
    mixers = [s.mixer for s in model.segments]
    assert [m.exports for m in mixers] == [(), (), ("memory",), ("kv",), (), ()]
    assert [m.imports for m in mixers] == [(), (), (), (), ("memory",), ("kv",)]
    # lambda0 by the PUBLISHED index
    assert [round(m.lambda0, 6) for m in mixers if hasattr(m, "lambda0")] == [
        round(0.8 - 0.6 * np.exp(-0.3 * i), 6) for i in (1, 17, 19)]


def test_the_policys_own_init_is_the_familys(setup):
    _, _, model, _ = setup
    p = model.init(jax.random.PRNGKey(0))
    scan = p["layer_0"]
    np.testing.assert_allclose(
        np.exp(scan["A_log"]), np.broadcast_to(np.arange(1, 5)[:, None], (4, 128)),
        rtol=1e-6)
    assert np.all(scan["D"] == 1.0) and np.all(p["layer_1"]["diff_norm"] == 1.0)
    step = np.log1p(np.exp(scan["dt_bias"]))
    assert 1e-3 * 0.99 < step.min() and step.max() < 1e-1 * 1.01
    assert np.abs(scan["dt_proj"]).max() <= 4 ** -0.5
    assert 0.05 < np.std(p["layer_1"]["lambda_q1"]) < 0.2
    assert np.all(p["layer_1"]["input_norm_bias"] == 0.0)


@pytest.mark.parametrize("indices", [(0, 1, 16, 18, 19), (18, 19), (0, 1, 17, 18)])
def test_an_importer_without_its_exporter_is_refused_by_name(indices):
    lm = small_config(indices)["algo_config"]["model"]["sequence_lm"]
    with pytest.raises(ValueError, match="importer without its exporter"):
        config_lib.describe(lm)


def test_the_uncut_layer_list_counts_the_published_parameters():
    """Written as the kinds write them, the 32 published layers at the
    published widths and vocabulary are the published 3.8 B, and the
    kinds stand 9 : 8 : 1 : 7 : 7."""
    lm = {
        "model_type": "phi4flash", "hidden_size": 2560, "intermediate_size": 10240,
        "num_attention_heads": 40, "num_key_value_heads": 20, "num_hidden_layers": 32,
        "mb_per_layer": 2, "sliding_window": 512, "layer_norm_eps": 1e-5,
        "max_position_embeddings": 262144, "tie_word_embeddings": True,
    }
    model = SequenceLM(200064, lm)
    shapes = model.param_shapes()
    count = sum(int(np.prod(s)) for g in shapes.values() for s in g.values())
    assert count - (2560 + 1) == 3_852_562_944  # less the value head PPO adds
    by_kind = {k: model.layer_types.count(k) for k in set(model.layer_types)}
    assert by_kind == {"selective_scan": 9, "sliding_attention": 8, "attention": 1,
                       "gated_memory": 7, "cross_attention": 7}
    per_layer = lambda i: sum(int(np.prod(s)) for s in shapes[f"layer_{i}"].values())
    assert [per_layer(i) for i in (0, 1, 18, 19)] == [
        119_895_040, 98_322_304, 104_867_840, 91_766_144]
    # the cut the benchmark runs: six layers and an eighth of the table
    cut = SequenceLM(25008, dict(
        lm, num_hidden_layers=6, layer_indices=[0, 1, 16, 17, 18, 19],
        published_num_hidden_layers=32, max_position_embeddings=8192))
    assert sum(int(np.prod(s)) for g in cut.param_shapes().values()
               for s in g.values()) == 697_096_833
    state = cut.initial_state(1)
    assert sum(s.size * s.dtype.itemsize for s in state[:-1]) == 45_342_720


# -- (b) both forms against the reference ---------------------------------------------


@pytest.mark.parametrize("forced", [False, True], ids=["text", "step_kernel"])
def test_one_token_steps_through_two_episodes_equal_the_reference(
        setup, monkeypatch, forced):
    """80 steps from an empty state, an episode's end after 40: the scan
    state, the convolution's inputs, the ring (five turns) and the shared
    cache through the carried state against the reference's full forward
    of both episodes; once more with the ring's, the full layer's and the
    cross layer's steps on the kernel (the rule forced, the interpreter;
    the second episode's first steps read a ring that still holds the
    first's rows)."""
    config, params, model, fns = setup
    if forced:
        monkeypatch.setattr(flash_attention, "step_kernel_applies", lambda *a, **k: True)
        monkeypatch.setattr(
            flash_attention, "fragment_block_k",
            lambda depth, _=None: 8 if depth > WINDOW else 4)
        monkeypatch.setattr(flash_attention, "step_attention", functools.partial(
            flash_attention.step_attention, interpret=True))
        before = dict(metrics.attention_step_lowerings())
        fns = {"step": jax.jit(lambda p, tok, st, fr: model.apply(p, tok, st, resets=fr))}
    rng = np.random.default_rng(11)
    tokens = rng.integers(0, VOCAB, (2, 2 * EPISODE))
    fresh = np.zeros((2, 2 * EPISODE), bool)
    fresh[:, 0] = fresh[:, EPISODE] = True
    start = _f32(model.initial_state(2))
    with jax.default_matmul_precision("highest"):
        logits, state = _chain(fns["step"], params, tokens, start, fresh)
        want = [ref.forward(params, jnp.asarray(tokens[:, lo:lo + EPISODE]), start,
                            jnp.asarray(fresh[:, lo:lo + EPISODE]), config, VOCAB)
                for lo in (0, EPISODE)]
    got = logits.reshape(2, 2 * EPISODE, VOCAB)
    for n, lo in enumerate((0, EPISODE)):
        assert float(jnp.max(jnp.abs(
            got[:, lo:lo + EPISODE] - want[n]["logits"]))) < LOGIT_TOL
    for a, b in zip(state[:-1], want[1]["state"][:-1]):
        np.testing.assert_allclose(a, np.asarray(b, np.float32), atol=LOGIT_TOL)
    assert list(np.asarray(state[-1])) == [EPISODE, EPISODE]
    if forced:  # one trace: the ring, the full layer and its reader
        now = metrics.attention_step_lowerings()
        assert now["kernel"] - before.get("kernel", 0) == 3
        assert now.get("xla", 0) == before.get("xla", 0)


@pytest.mark.parametrize("depths,reset_at", [
    ((1, 2, 0), None),      # starts mid-window, stays inside the ring's first turn
    ((5, 13, 29), None),    # wraps the ring
    ((3, 17, 33), 2),       # a reset inside: everything restarts
], ids=["mid_window", "wraps_the_ring", "reset_inside"])
def test_fragment_form_from_a_stored_state_equals_reference_and_steps(
        setup, depths, reset_at):
    config, params, model, fns = setup
    rng = np.random.default_rng(13)
    tokens = rng.integers(0, VOCAB, (3, T))
    fresh = np.zeros((3, T), bool)
    if reset_at is not None:
        fresh[1, reset_at] = True
    state = _state_at(config, depths)
    with jax.default_matmul_precision("highest"):
        want = fns["reference"](params, jnp.asarray(tokens), state, jnp.asarray(fresh))
        logits, _, after = fns["fragment"](
            params, jnp.asarray(tokens), state, jnp.asarray(fresh, jnp.float32))
        stepped, stepped_state = _chain(fns["step"], params, tokens, state, fresh)
    logits = logits.reshape(3, T, VOCAB)
    assert float(jnp.max(jnp.abs(logits - want["logits"]))) < LOGIT_TOL
    assert float(jnp.max(jnp.abs(stepped.reshape(3, T, VOCAB) - want["logits"]))) < LOGIT_TOL
    end = np.asarray(want["state"][-1])
    for i, (a, b, c) in enumerate(zip(after[:-1], stepped_state[:-1], want["state"][:-1])):
        a, b, c = (np.asarray(x, np.float32) for x in (a, b, c))
        if a.shape[1] == EPISODE:  # a full cache counts below the position
            keep = np.arange(EPISODE)[None, :, None] < end[:, None, None]
            a, b, c = (np.where(keep, x, 0.0) for x in (a, b, c))
        np.testing.assert_allclose(a, c, atol=LOGIT_TOL, err_msg=f"leaf {i}")
        np.testing.assert_allclose(b, c, atol=LOGIT_TOL, err_msg=f"leaf {i}")
    np.testing.assert_array_equal(after[-1], end)
    if reset_at is not None:
        # the memory of a token after the reset holds nothing of the
        # episode before: the same tokens from an EMPTY state give it
        tail = slice(reset_at, T)
        alone = ref.forward(
            params, jnp.asarray(tokens[1:2, tail]), _f32(model.initial_state(1)),
            jnp.asarray(fresh[1:2, tail]), config, VOCAB)
        np.testing.assert_allclose(
            want["memory"][1, tail], alone["memory"][0], atol=1e-5)


_STATED_LAMBDA0 = ref.lambda0


def _cut_index(index):
    return _STATED_LAMBDA0(INDICES.index(index))


def _one_softmax(q, keys, values, mask, lam, scale):
    """Both maps of a pair normalised together."""
    scores = jnp.stack([jnp.einsum(
        "btjd,bsjd->bjts", q[:, :, :, w], keys[:, :, :, w],
        precision=ref.HI) * scale for w in (0, 1)], axis=-1)
    w = jax.nn.softmax(
        jnp.where(mask[:, None, :, :, None], scores, -jnp.inf), axis=(-2, -1))
    out = [jnp.einsum("bjts,bsjd->btjd", w[..., i], values, precision=ref.HI)
           for i in (0, 1)]
    return out[0] - lam * out[1]


# a WRONG reference, by what it gets wrong: (attribute, its value)
WRONG = {
    "memory_after_the_gate": ("_memory", lambda y, gate: y * jax.nn.silu(gate)),
    "lambda0_by_the_cuts_index": ("lambda0", _cut_index),
    "one_softmax_for_both_maps": ("_two_maps", _one_softmax),
    "cross_reads_the_ring": ("EXPORTS_CACHE", ref.WINDOW),
}


@pytest.mark.parametrize("wrong", sorted(WRONG) + ["a_window_of_nine"])
@pytest.mark.parametrize("form", ["fragment", "steps"])
def test_a_wrong_variant_fails_the_comparison(setup, monkeypatch, wrong, form):
    """The comparison sees each: the system against a reference that
    takes the memory after the gate, lambda0 by the cut's index, one
    softmax for a pair's two maps, the ring for the cross layer's keys,
    or a window one row wider."""
    config, params, model, fns = setup
    if wrong == "a_window_of_nine":
        config = dict(config, sliding_window=WINDOW + 1)
    else:
        monkeypatch.setattr(ref, *WRONG[wrong])
    rng = np.random.default_rng(17)
    tokens = rng.integers(0, VOCAB, (3, T))
    fresh = np.zeros((3, T), bool)
    state = _state_at(config, [5, 13, 29])
    with jax.default_matmul_precision("highest"):
        want = ref.forward(  # traced anew, with the wrong part
            params, jnp.asarray(tokens), state, jnp.asarray(fresh), config, VOCAB)
        if form == "fragment":
            logits = fns["fragment"](params, jnp.asarray(tokens), state,
                                     jnp.asarray(fresh, jnp.float32))[0]
        else:
            logits = _chain(fns["step"], params, tokens, state, fresh)[0]
    error = float(jnp.max(jnp.abs(logits.reshape(3, T, VOCAB) - want["logits"])))
    assert error > 10 * LOGIT_TOL, error


def _in_bfloat16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


@pytest.mark.parametrize("what", ["scan", "memory"])
def test_bfloat16_where_float32_is_stated_fails_the_tolerance(setup, monkeypatch, what):
    """The scan's state is an accumulator over the episode and the
    memory is read by every gated-memory layer: float32. With the
    recurrence's state, or the exported memory, rounded to bfloat16 the
    logits are not within the tolerance."""
    config, params, model, fns = setup
    if what == "scan":
        stated = selective_scan._token

        def low(state, u, dt, a, b, c):
            new, y = stated(_in_bfloat16(state), u, dt, a, b, c)
            return _in_bfloat16(new), _in_bfloat16(y)

        monkeypatch.setattr(selective_scan, "_token", low)
    else:
        stated = kinds.GatedMemoryLayer.apply

        def low(self, p, x, state, ctx):
            memory = {self.source: _in_bfloat16(ctx["imports"][self.source])}
            return stated(self, p, x, state, dict(ctx, imports=memory))

        monkeypatch.setattr(kinds.GatedMemoryLayer, "apply", low)
    rng = np.random.default_rng(19)
    tokens = rng.integers(0, VOCAB, (3, T))
    fresh = np.zeros((3, T), bool)
    state = _state_at(config, [5, 13, 29])
    with jax.default_matmul_precision("highest"):
        want = fns["reference"](params, jnp.asarray(tokens), state, jnp.asarray(fresh))
        logits = model.apply(  # traced anew, with the rounding
            params, jnp.asarray(tokens[..., None]), state,
            resets=jnp.asarray(fresh, jnp.float32))[0].reshape(3, T, VOCAB)
    assert float(jnp.max(jnp.abs(logits - want["logits"]))) > 3 * LOGIT_TOL


# -- (c) the gradient -------------------------------------------------------------------


EIGHT = (0, 1, 16, 17, 18, 19, 20, 21)  # a second gated-memory / cross pair


@pytest.fixture(scope="module")
def eight():
    """Eight layers: layer 17's cache is read by THREE layers (itself,
    19 and 21) and layer 16's memory by two (18 and 20). With the
    reference's own loss and gradient, computed once."""
    config = small_config(EIGHT)
    params = ref.init_params(jax.random.PRNGKey(23), config, VOCAB)
    batch = ref.make_batch(np.random.default_rng(3), config, 4 * T, VOCAB)
    for k in range(8):  # float32 caches, as above
        batch[f"__chunk__state_in_{k}"] = np.asarray(
            batch[f"__chunk__state_in_{k}"], np.float32)
    jb = {k: jnp.asarray(v) for k, v in batch.items()}
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.value_and_grad(lambda p: ref.loss(p, jb, config)))(params)
    return config, params, _model(config), batch, want


def _model_grad(model, config, batch, params):
    """``(loss, gradient)`` of the model under the reference's loss."""
    rows = batch["obs"].shape[0]
    jb = {k: jnp.asarray(v) for k, v in batch.items()}

    def loss(p):
        logits, value, _ = model.apply(
            p, jb["obs"].reshape(rows // T, T, 1), ref.batch_state(jb),
            resets=jb["resets"].reshape(rows // T, T))
        return ref.ppo_loss(logits, value, jb, config["algo_config"])

    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(loss))(params)


def test_loss_and_every_gradient_leaf_match_reference(eight):
    """The model under the reference's loss against the reference's own
    loss and gradient, leaf by leaf: layer 17's key and value columns
    among them, whose gradient is the sum over THREE layers' use of the
    fragment's rows, and layer 16's through the memory two layers read;
    four fragments in two groups of two (``learn_streams`` 2), so the
    imports are split with the streams."""
    config, params, model, batch, (want_loss, want) = eight
    got_loss, got = _model_grad(model, config, batch, params)
    assert abs(float(got_loss) - float(want_loss)) < 1e-5 * abs(float(want_loss))
    errors = _leaf_errors(got, want)
    assert set(errors) == {(g, k) for g in params for k in params[g]}
    worst = max(errors, key=errors.get)
    assert errors[worst] < GRAD_LEAF_TOL, (worst, errors[worst])
    for leaf in ("k_proj", "v_proj", "v_bias"):  # layer 17 is the tree's layer_3
        assert float(jnp.linalg.norm(want["layer_3"][leaf])) > 0


@pytest.mark.parametrize("name,leaves", [
    ("kv", [("layer_3", "k_proj"), ("layer_3", "v_proj")]),
    ("memory", [("layer_2", "x_proj"), ("layer_2", "in_proj")]),
])
def test_an_exporters_gradient_is_summed_over_its_importers(eight, monkeypatch, name, leaves):
    """Cut the importers' path (their imports under ``stop_gradient``)
    and the exporter's leaves lose the importers' share (as does every
    layer before it): the comparison above then fails on them."""
    config, params, model, batch, (_, want) = eight
    stated = {kind: kind.apply for kind in (kinds.AttentionLayer, kinds.GatedMemoryLayer)}

    def cut(kind):
        def apply(self, p, x, state, ctx):
            imports = {k: jax.lax.stop_gradient(v) if k == name else v
                       for k, v in ctx["imports"].items()}
            return stated[kind](self, p, x, state, dict(ctx, imports=imports))
        return apply

    for kind in stated:
        monkeypatch.setattr(kind, "apply", cut(kind))
    _, got = _model_grad(model, config, batch, params)
    errors = _leaf_errors(got, want)
    for leaf in leaves:
        assert errors[leaf] > 10 * GRAD_LEAF_TOL, (leaf, errors[leaf])
    # a layer after every exporter takes no gradient through an import
    assert errors[("layer_7", "q_proj")] < GRAD_LEAF_TOL


# -- (d) statistics, counters, what a step fetches ------------------------------------


def test_statistics_and_lowering_counters(setup):
    config, params, model, fns = setup
    depths = [5, 13, 29]
    state = _state_at(config, depths)
    tokens = jnp.zeros((3, T, 1), jnp.int32)
    before = (dict(metrics.selective_scan_lowerings()),
              dict(metrics.shared_state_lowerings()))
    def fragment(p, tok, st):
        stats = {}
        model.apply(p, tok, st, resets=jnp.zeros((3, T)), stats_out=stats)
        return stats

    stats = jax.jit(fragment)(params, tokens, state)
    jax.jit(lambda p, tok, st: model.apply(p, tok, st))(params, tokens[:, :1], state)
    scans, shared = metrics.selective_scan_lowerings(), metrics.shared_state_lowerings()
    # two scan layers a traced stack, in each form
    assert scans["fragment"] - before[0].get("fragment", 0) == 2
    assert scans["step"] - before[0].get("step", 0) == 2
    assert scans.get("kernel", 0) == before[0].get("kernel", 0)
    # each export counted once a traced stack, with its one reader
    for name in ("kv/1", "memory/1"):
        assert shared[name] - before[1].get(name, 0) == 2
    positions = np.asarray(depths)[:, None] + np.arange(T)[None]
    assert abs(float(stats["xattn_rows_seen_mean"]) - (positions.mean() + 1)) < 1e-5
    assert abs(float(stats["window_rows_seen_mean"])
               - np.minimum(positions + 1, WINDOW).mean()) < 1e-5
    assert float(stats["shared_cache_reads"]) == 1.0  # one cross layer
    lam = [0.8 - 0.6 * np.exp(-0.3 * i) for i in (1, 17, 19)]
    p = ref.to_policy_tree(params, config)
    for n, layer in enumerate(("layer_1", "layer_3", "layer_5")):
        lam[n] += float(np.exp(np.sum(p[layer]["lambda_q1"] * p[layer]["lambda_k1"]))
                        - np.exp(np.sum(p[layer]["lambda_q2"] * p[layer]["lambda_k2"])))
    assert abs(float(stats["diff_lambda_mean"]) - np.mean(lam)) < 1e-5
    assert 0.0 < float(stats["scan_dt_max"]) < 1.0
    assert sorted(stats) == [
        "attn_decode_key_blocks_skipped_share", "attn_key_blocks_skipped_share",
        "diff_lambda_mean", "scan_dt_max", "shared_cache_reads",
        "window_rows_seen_mean", "xattn_decode_key_blocks_skipped_share",
        "xattn_key_blocks_skipped_share", "xattn_rows_seen_mean"]


def test_a_cross_layers_step_fetches_the_rows_below_the_position_once_and_writes_nothing(
        monkeypatch):
    """A cross layer at the benchmark's geometry in small (4 heads of 64
    over 2 key heads: ONE key pair of 128 lanes, four paired queries), its
    one-token form on the step kernel in the Pallas interpreter over a
    cache it is handed and does not own: equal to the text; UNMOVED when
    every key block the counters call skipped is NaN (no row above the
    position's block is fetched, and a block is fetched once for both
    maps and both value halves: the kernel walks a stream's held blocks
    once); and it hands back no state."""

    layer = kinds.AttentionLayer(
        kind="cross_attention", heads=4, kv_heads=2, head_dim=64, scale=0.125,
        diff=True, bias=True, index=19, source="kv")
    rng = np.random.default_rng(29)
    b, d, depth, block = 4, 32, 64, 16
    p = {leaf: jnp.asarray(rng.standard_normal(shape) / np.sqrt(shape[0]), jnp.float32)
         for leaf, shape in layer.param_shapes(d).items()}
    assert "k_proj" not in p and "v_bias" not in p
    assert layer.state_shapes(b, depth, jnp.bfloat16) == []
    x = jnp.asarray(rng.standard_normal((b, 1, d)), jnp.float32)
    caches = tuple(jnp.asarray(rng.standard_normal((b, depth, 128)), jnp.bfloat16)
                   for _ in range(2))
    pos0 = jnp.asarray([0, 15, 16, 63], jnp.int32)
    own = tuple(jnp.zeros((b, 1, 2, 64), jnp.float32) for _ in range(2))

    def run(caches):
        ctx = {"scope": "", "dtype": jnp.bfloat16, "eps": 1e-5, "pos0": pos0,
               "positions": pos0[:, None], "seg": jnp.zeros((b, 1), jnp.int32),
               "fresh": jnp.zeros((b, 1), bool), "step": True,
               "imports": {"kv": (caches, own)}}
        return layer.apply(p, x, (), ctx)

    want, new, _ = run(caches)
    assert new == ()
    monkeypatch.setattr(flash_attention, "step_kernel_applies", lambda *a, **k: True)
    monkeypatch.setattr(flash_attention, "step_attention", functools.partial(
        flash_attention.step_attention, block_k=block, interpret=True))
    before = dict(metrics.attention_step_lowerings())
    got, new, _ = run(caches)
    assert metrics.attention_step_lowerings()["kernel"] - before.get("kernel", 0) == 1
    assert new == ()
    np.testing.assert_allclose(got, want, atol=3e-2)
    # rows held: the position's own among them; blocks of 16
    held = (np.asarray(pos0) + 1 + block - 1) // block
    assert list(held) == [1, 1, 2, 4]
    poisoned = tuple(jnp.where(
        jnp.arange(depth)[None, :, None] >= block * held[:, None, None], jnp.nan, c)
        for c in caches)
    np.testing.assert_array_equal(run(poisoned)[0], got)
    skipped, walked = flash_attention.step_key_blocks(pos0 + 1, depth, block)
    assert (int(skipped), walked) == (16 - 8, 16)


def test_the_fragment_forms_counters_are_the_masks_arithmetic(monkeypatch):
    """What the learn form reports for the cross layer where the kernels'
    rules hold (forced here; the text runs): of the shared cache's key
    blocks, those at or past a stream's start are skipped, and a step at
    each of the fragment's positions would skip those above it."""
    config = small_config(max_position_embeddings=64)
    model = _model(config)
    params = ref.init_params(jax.random.PRNGKey(31), config, VOCAB)
    depths = jnp.asarray([0, 17, 40])
    state = list(_f32(model.initial_state(3)))
    state[-1] = depths.astype(jnp.int32)
    monkeypatch.setattr(flash_attention, "fragment_block_k",
                        lambda depth, block_k=None: 16 if depth % 16 == 0 else 0)
    monkeypatch.setattr(flash_attention, "fragment_kernel_applies",
                        lambda t, h, hkv, d, depth, *a: depth == 64)
    monkeypatch.setattr(flash_attention, "step_kernel_applies",
                        lambda h, hkv, d, depth, *a, **k: depth == 64)
    monkeypatch.setattr(
        flash_attention, "fragment_attention",
        lambda qh, k, v, kc, vc, pos0, seg, positions, **kw: jnp.zeros(
            qh.shape[:2] + (qh.shape[2] * qh.shape[3], qh.shape[4]), jnp.float32))
    stats = {}
    model.apply(params, jnp.zeros((3, T, 1), jnp.int32), tuple(state),
                resets=jnp.zeros((3, T)), stats_out=stats)
    # 4 stored blocks of 16 and the own block a stream: held 0, 2, 3
    assert abs(float(stats["xattn_key_blocks_skipped_share"]) - (12 - 5) / 15) < 1e-6
    positions = np.asarray(depths)[:, None] + np.arange(T)[None]
    held = np.minimum((positions + 1 + 15) // 16, 4)
    assert abs(float(stats["xattn_decode_key_blocks_skipped_share"])
               - (4 - held).sum() / (4 * held.size)) < 1e-6
    # the layer that OWNS the cache reports the same under its own name
    assert stats["attn_key_blocks_skipped_share"] == stats["xattn_key_blocks_skipped_share"]


def test_reset_state_clears_the_scans_and_keeps_the_caches(setup):
    config, _, model, _ = setup
    state = _state_at(config, [5, 13, 29])
    after = model.reset_state(state, jnp.asarray([True, False, True]))
    for i, (a, b) in enumerate(zip(after[:-1], state[:-1])):
        if i in (0, 1, 4, 5):  # a scan's matrix and convolution inputs
            assert not np.any(np.asarray(a[0])) and not np.any(np.asarray(a[2]))
            np.testing.assert_array_equal(a[1], b[1])
        else:
            np.testing.assert_array_equal(a, b)
    assert list(np.asarray(after[-1])) == [0, 13, 0]


# -- (e) the controls, the fused lane, the reference ------------------------------------


@pytest.mark.parametrize("precision", ["int8", "fp8"])
def test_the_controls_fail_the_tolerances(setup, precision):
    config, params, _, fns = setup
    rng = np.random.default_rng(41)
    tokens = jnp.asarray(rng.integers(0, VOCAB, (3, T)))
    state = _state_at(config, [5, 13, 29])
    fresh = jnp.zeros((3, T), bool)
    with jax.default_matmul_precision("highest"):
        want = fns["reference"](params, tokens, state, fresh)
        low = ref.forward(params, tokens, state, fresh, config, VOCAB, precision)
    assert float(jnp.max(jnp.abs(low["logits"] - want["logits"]))) > 10 * LOGIT_TOL


def test_two_updates_on_the_fused_lane():
    """PPO on the token env, ``env_backend: jax``: rollout and update in
    one dispatch through ``JaxPolicy``, twice, built from ``model_type:
    phi4flash`` as ``python -m ray_tpu.train`` builds it. Fragments of 6
    in episodes of 24 over a window of 8: every exporter and importer
    moves."""
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
            for key in ("total_loss", "entropy", "scan_dt_max", "diff_lambda_mean",
                        "shared_cache_reads", "xattn_rows_seen_mean",
                        "window_rows_seen_mean",
                        "xattn_key_blocks_skipped_share",
                        "xattn_decode_key_blocks_skipped_share"):
                assert np.isfinite(info[key]) and np.ndim(info[key]) == 0, key
            assert info["shared_cache_reads"] == 1.0
        after = jax.device_get(policy.params)
        moved = lambda g, k: float(np.abs(after[g][k] - before[g][k]).max())
        for group, leaf in (("layer_0", "A_log"), ("layer_0", "dt_proj"),
                            ("layer_1", "lambda_q1"), ("layer_2", "x_proj"),
                            ("layer_3", "k_proj"), ("layer_3", "v_bias"),
                            ("layer_4", "gmu_in"), ("layer_5", "q_proj"),
                            ("layer_5", "diff_norm"), ("final_norm", "bias"),
                            ("embed", "embedding")):
            assert moved(group, leaf) > 0, (group, leaf)
    finally:
        algo.cleanup()


def test_the_reference_imports_nothing_of_the_system():
    with open(os.path.join(ROOT, "perf", "reference", "phi4_flash.py")) as f:
        text = f.read()
    assert "import ray_tpu" not in text and "from ray_tpu" not in text
    assert "pallas" not in text and "import perf" not in text
