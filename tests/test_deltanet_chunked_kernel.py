"""The fragment form of the gated delta rule with a decay a head on its
kernel pair (``ops/deltanet.gated_delta_chunked_kernel``: forward and
backward under one ``custom_vjp``), run here in the Pallas interpreter
against the chunked ``jax.numpy`` text AND against the recurrence token
by token in float64: outputs, end state, every cotangent; resets at a
chunk's edges; the unbounded family of decays; which of the two
lowerings a call takes, and the counter that says so. The compile for a
described v5e lives in tests/test_replay_ring_layout.py with the other
chip compiles.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import backend, deltanet
from ray_tpu.telemetry import metrics as telemetry_metrics

STREAMS, HEADS, DK, DV, CHUNK = 2, 2, 128, 128, 64
OPERANDS = ("state", "q", "k", "v", "g", "beta")


def _operands(chunks, t=None, seed=0, low=-1.0, b=STREAMS, h=HEADS):
    """A fragment of ``chunks`` chunks (or ``t`` tokens) from a NON-zero
    stored state. Stream 0 opens an episode at a chunk's first row, at a
    middle row and at a chunk's last row (in the second chunk, where
    there is one); stream 1 nowhere. ``low``: the log-decays are uniform
    in ``(low, 0)``."""
    t = t or chunks * CHUNK
    rng = np.random.default_rng(seed)
    f32 = lambda *s: rng.standard_normal(s).astype(np.float32)
    q, k, v = f32(b, t, h, DK) / np.sqrt(DK), f32(b, t, h, DK), f32(b, t, h, DV)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    g = rng.uniform(low, 0.0, (b, t, h)).astype(np.float32)
    beta = rng.uniform(0.1, 0.9, (b, t, h)).astype(np.float32)
    resets = np.zeros((b, t), np.float32)
    first = CHUNK if t > CHUNK else 0  # the second chunk's rows, if any
    for at in (first, first + min(t, CHUNK) // 3, first + min(t, CHUNK) - 1):
        resets[0, at] = 1.0
    return f32(b, h, DK, DV), q, k, v, g, beta, resets


def _kernel(*ops, chunk=CHUNK, heads=None):
    return deltanet.gated_delta_chunked_kernel(
        *ops, chunk=chunk, heads=heads, interpret=True)


def _text(*ops, chunk=CHUNK):
    return deltanet._chunked_text(*ops, chunk=chunk)


def _recurrence(state, q, k, v, g, beta, resets):
    """The module docstring's four lines, a token at a time, in the
    precision of its operands."""
    def token(s, x):
        qt, kt, vt, gt, bt, fresh = x
        s = jnp.where(fresh[:, None, None, None] > 0.5, 0.0, s)
        s = s * jnp.exp(gt)[..., None, None]
        d = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", s, kt))
        s = s + kt[..., :, None] * d[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, qt)

    steps = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta, resets))
    after, outs = jax.lax.scan(token, state, steps)
    return jnp.moveaxis(outs, 0, 1), after


def _scalar(rule, shapes, seed=1):
    """A scalar of BOTH outputs, each under weights of its own."""
    rng = np.random.default_rng(seed)
    w_o, w_s = (rng.standard_normal(s) for s in shapes)

    def of(*ops):
        o, after = rule(*ops)
        return jnp.sum(o * w_o.astype(o.dtype)) + jnp.sum(after * w_s.astype(o.dtype))

    return of


@functools.lru_cache(maxsize=None)
def _three_ways(chunks, low=-1.0):
    """``{lowering: ((o, state after), gradients)}`` of one fragment by
    the kernel pair, the text and the float64 recurrence."""
    ops = _operands(chunks, low=low)
    shapes = ((STREAMS, chunks * CHUNK, HEADS, DV), (STREAMS, HEADS, DK, DV))
    out = {}
    for name, rule in (("kernel", _kernel), ("text", _text)):
        out[name] = rule(*ops), jax.grad(
            _scalar(rule, shapes), argnums=tuple(range(6)))(*ops)
    with jax.enable_x64(True):
        wide = tuple(jnp.asarray(x, jnp.float64) for x in ops)
        out["float64"] = _recurrence(*wide), jax.grad(
            _scalar(_recurrence, shapes), argnums=tuple(range(6)))(*wide)
    return jax.tree_util.tree_map(np.asarray, out)


@pytest.mark.parametrize("against", ["text", "float64"])
@pytest.mark.parametrize("chunks", [1, 2, 8])
def test_kernel_agrees_with_the_text_and_the_recurrence(chunks, against):
    """``o`` and the end state at 1, 2 and 8 chunks (one chunk alone, two
    side by side in one step, four steps of two under the kernel's loop),
    with resets at a chunk's first, a middle and its last row."""
    (got_o, got_s), _ = _three_ways(chunks)["kernel"]
    (want_o, want_s), _ = _three_ways(chunks)[against]
    np.testing.assert_allclose(got_o, want_o, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got_s, want_s, rtol=2e-5, atol=2e-5)
    assert np.all(np.isfinite(got_o)) and np.all(np.isfinite(got_s))


@pytest.mark.parametrize("against", ["text", "float64"])
@pytest.mark.parametrize("chunks", [1, 2, 8])
@pytest.mark.parametrize("operand", range(len(OPERANDS)), ids=OPERANDS)
def test_every_cotangent_agrees(operand, chunks, against):
    """``jax.grad`` of a scalar of both outputs through the backward
    kernel, operand by operand, against the text's transposed scan and
    against the float64 recurrence's."""
    got = _three_ways(chunks)["kernel"][1][operand]
    want = _three_ways(chunks)[against][1][operand]
    scale = float(np.max(np.abs(want)))
    assert scale > 0.1
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5 * scale)


def test_a_reset_stops_the_state_and_the_gradient():
    """Stream 0 opens an episode in the second chunk: its end state and
    the outputs after it read nothing of the stored state nor of the
    tokens before, forward and backward; ``resets`` has a zero
    cotangent."""
    ops = _operands(2)
    opened = CHUNK + CHUNK - 1  # stream 0's last reset

    def after_the_reset(*ops):
        o, after = _kernel(*ops)
        return jnp.sum(o[0, opened:] ** 2) + jnp.sum(after[0] ** 2)

    d_state, d_q, d_v, d_resets = jax.grad(
        after_the_reset, argnums=(0, 1, 3, 6))(*ops)
    assert not np.any(np.asarray(d_state))
    assert not np.any(np.asarray(d_q)[0, :opened]) and np.any(np.asarray(d_q)[0, opened:])
    assert not np.any(np.asarray(d_v)[0, :opened]) and np.any(np.asarray(d_v)[0, opened:])
    assert not np.any(np.asarray(d_resets))
    other = (np.zeros_like(ops[0]),) + ops[1:]
    assert np.array_equal(np.asarray(_kernel(*ops)[1])[0], np.asarray(_kernel(*other)[1])[0])


@pytest.mark.parametrize("what", ["outputs", *OPERANDS])
def test_decays_from_nothing_down_to_minus_sixty_a_token(what):
    """The layer's family, ``-exp(A_log) softplus(.)``, has no floor: at
    log-decays uniform in (-60, 0), with some rows at 0 exactly, every
    ``exp(G_i - G_j)`` the masks keep is at most 1 and the kernels stay
    finite and on the float64 recurrence, value and gradients."""
    (got, got_grads) = _three_ways(2, low=-60.0)["kernel"]
    (want, want_grads) = _three_ways(2, low=-60.0)["float64"]
    if what == "outputs":
        for g, w in zip(got, want):
            assert np.all(np.isfinite(g))
            np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-5)
        return
    g, w = (x[OPERANDS.index(what)] for x in (got_grads, want_grads))
    assert np.all(np.isfinite(g))
    np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5 * float(np.max(np.abs(w))))


def test_a_decay_of_exactly_nothing_and_the_steepest():
    ops = list(_operands(2))
    ops[4] = ops[4].copy()
    ops[4][:, ::7], ops[4][:, 3::11] = 0.0, -60.0
    with jax.enable_x64(True):
        want = jax.tree_util.tree_map(
            np.asarray, _recurrence(*(jnp.asarray(x, jnp.float64) for x in ops)))
    for g, w in zip(_kernel(*ops), want):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("heads,asked", [(8, 2), (8, 4), (8, 8), (6, 4)])
def test_heads_a_grid_step_change_no_number(heads, asked):
    """A grid step's heads share nothing: two, four and eight of eight a
    step (and the two of six that four do not divide: their greatest
    common divisor) give the bits one a step gives, value and gradient."""
    ops = _operands(2, h=heads, seed=4)
    shapes = ((STREAMS, 2 * CHUNK, heads, DV), (STREAMS, heads, DK, DV))
    both = lambda n: (
        _kernel(*ops, heads=n),
        jax.grad(_scalar(functools.partial(_kernel, heads=n), shapes),
                 argnums=tuple(range(6)))(*ops))
    for g, w in zip(jax.tree_util.tree_leaves(both(asked)),
                    jax.tree_util.tree_leaves(both(1))):
        assert np.array_equal(g, w)


def test_a_fragment_shorter_than_a_chunk(monkeypatch):
    """48 tokens under a chunk of 64 are one chunk of 48: the kernels
    take it in the interpreter (the text's numbers), and on a TPU the
    dispatch keeps the text, 48 rows being no whole tile."""
    ops = _operands(None, t=48)
    for g, w in zip(_kernel(*ops), _text(*ops)):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-5)
    monkeypatch.setattr(backend, "is_tpu", lambda: True)
    assert not deltanet._chunked_kernel_applies(*ops[:6], CHUNK)
    assert _took(lambda: jax.eval_shape(
        lambda *o: deltanet.gated_delta_chunked(*o, chunk=CHUNK), *ops)) == {"xla/head": 1}


def test_under_an_outer_checkpoint_as_the_block_applies_it():
    """The model's block runs under ``jax.checkpoint``: the forward
    kernel is traced again for the recomputation, and the gradients of
    a function of the rule's output are the text's."""
    *ops, resets = _operands(2)

    def block(rule):
        @jax.checkpoint
        def body(*ops):
            o, after = rule(*ops, resets)
            return jnp.tanh(o) * ops[3], after  # the gate needs ``o`` again

        return lambda *ops: sum(jnp.sum(x * x) for x in body(*ops))

    want = jax.grad(block(_text), argnums=tuple(range(6)))(*ops)
    got = jax.jit(jax.grad(block(_kernel), argnums=tuple(range(6))))(*ops)
    for g, w in zip(got, want):
        np.testing.assert_allclose(
            g, w, rtol=2e-4, atol=2e-5 * float(jnp.max(jnp.abs(w))))


def test_sixteen_key_heads_under_thirty_two_value_heads_through_the_layer(monkeypatch):
    """``DeltaNetLayer.apply`` at the cell's heads (16 key heads repeated
    under 32 value heads of 128 x 128; hidden size 32): the layer's
    output, its new state and the gradient of every leaf on the kernel
    pair are the text's, the repeat's transpose being XLA's sum; the
    rule sits under ``linear_attn/rule`` and counts ``kernel/head``."""
    from ray_tpu.models.sequence_lm import kinds

    layer = kinds.DeltaNetLayer(k_heads=16, v_heads=32, dk=DK, dv=DV, conv=4)
    d, b, t = 32, 1, 2 * CHUNK
    rng = np.random.default_rng(3)
    p = {name: jnp.asarray(rng.standard_normal(shape).astype(np.float32)
                           * (0.3 if len(shape) > 1 else 1.0) / np.sqrt(shape[0]))
         for name, shape in layer.param_shapes(d).items()}
    p["A_log"], p["dt_bias"] = jnp.log(jnp.linspace(1.0, 16.0, 32)), jnp.ones(32)
    x = jnp.asarray(rng.standard_normal((b, t, d)).astype(np.float32))
    state = tuple(jnp.asarray(rng.standard_normal(shape).astype(np.float32))
                  for shape, _ in layer.state_shapes(b, 0, jnp.float32))
    fresh = np.zeros((b, t), bool)
    fresh[0, 70] = True
    ctx = {"scope": "learn/", "dtype": jnp.float32, "chunk": CHUNK, "eps": 1e-6,
           "fresh": jnp.asarray(fresh),
           "seg": jnp.cumsum(jnp.asarray(fresh, jnp.int32), axis=1)}

    def scalar(p, x, state):
        out, (s1, tail), _ = layer.apply(p, x, state, ctx)
        return jnp.sum(out * out) + jnp.sum(s1 * s1), (out, s1)

    grad = jax.value_and_grad(scalar, argnums=(0, 1, 2), has_aux=True)
    before = _counts()
    (_, want), want_grads = grad(p, x, state)
    assert _since(before) == {"xla/head": 1}
    _as_tpu_in_the_interpreter(monkeypatch)
    before = _counts()
    jaxpr = jax.make_jaxpr(lambda *a: layer.apply(*a, ctx)[0])(p, x, state)
    assert _since(before) == {"kernel/head": 1}
    (call,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "custom_vjp_call"]
    assert "learn/linear_attn/rule" in str(call.source_info.name_stack)
    (_, got), got_grads = grad(p, x, state)
    for g, w in zip(jax.tree_util.tree_leaves((got, got_grads)),
                    jax.tree_util.tree_leaves((want, want_grads))):
        np.testing.assert_allclose(
            g, w, rtol=2e-4, atol=2e-5 * max(float(jnp.max(jnp.abs(w))), 1e-3))


# -- which lowering a call takes -------------------------------------------

def _counts():
    return dict(telemetry_metrics.deltanet_chunked_lowerings())


def _since(before):
    after = _counts()
    return {k: int(v - before.get(k, 0)) for k, v in after.items()
            if v != before.get(k, 0)}


def _took(trace):
    before = _counts()
    trace()
    return _since(before)


def _as_tpu_in_the_interpreter(monkeypatch):
    """The dispatch as a TPU's, the kernels in the interpreter."""
    monkeypatch.setattr(backend, "is_tpu", lambda: True)
    monkeypatch.setattr(
        deltanet, "gated_delta_chunked_kernel",
        functools.partial(deltanet.gated_delta_chunked_kernel, interpret=True))


def _shapes(t=2 * CHUNK, dk=DK, dv=DV, channel=False, dtype=jnp.float32):
    f = lambda *s: jax.ShapeDtypeStruct(s, dtype)
    g = f(2, t, 4, dk) if channel else f(2, t, 4)
    return f(2, 4, dk, dv), f(2, t, 4, dk), f(2, t, 4, dk), f(2, t, 4, dv), g, f(2, t, 4)


def test_the_cpu_takes_the_text():
    ops = _operands(2)
    assert not deltanet._chunked_kernel_applies(*ops[:6], CHUNK)
    traced = lambda: jax.eval_shape(
        lambda *o: deltanet.gated_delta_chunked(*o, chunk=CHUNK), *ops)
    assert _took(traced) == {"xla/head": 1}
    got = deltanet.gated_delta_chunked(*ops, chunk=CHUNK)
    assert all(np.array_equal(g, w) for g, w in zip(got, _text(*ops)))


TEXT_CASES = [
    pytest.param(dict(dk=64), id="half-tile-dk"),
    pytest.param(dict(dv=192), id="dv-not-whole-lane-tiles"),
    pytest.param(dict(t=CHUNK), id="one-chunk-of-64-fills-no-tile"),
    pytest.param(dict(t=3 * CHUNK), id="three-chunks-of-64-pair-with-nothing"),
    pytest.param(dict(dtype=jnp.bfloat16), id="another-precision"),
]


@pytest.mark.parametrize("sizes", TEXT_CASES)
def test_odd_sizes_take_the_text_on_a_tpu(sizes, monkeypatch):
    monkeypatch.setattr(backend, "is_tpu", lambda: True)
    ops = _shapes(**sizes)
    assert not deltanet._chunked_kernel_applies(*ops, CHUNK)
    traced = lambda: jax.eval_shape(
        lambda *o: deltanet.gated_delta_chunked(*o, chunk=CHUNK), *ops)
    assert _took(traced) == {"xla/head": 1}


@pytest.mark.parametrize("t,chunk,rows", [
    (128, 64, 128), (512, 64, 128), (256, 128, 128), (256, 256, 256), (128, 32, 128)])
def test_whole_tiles_take_the_kernel_on_a_tpu(t, chunk, rows, monkeypatch):
    """With ``backend.is_tpu`` true the dispatch counts ``kernel``, for
    the value and under ``grad`` (one traced rule each), and a step of
    the kernels' walk takes whole 128-row tiles: chunks shorter than one
    side by side."""
    monkeypatch.setattr(backend, "is_tpu", lambda: True)
    ops = _shapes(t=t)
    assert deltanet._chunked_kernel_applies(*ops, chunk)
    assert chunk * deltanet._chunks_a_step(chunk, t // chunk) == rows
    rule = lambda *o: deltanet.gated_delta_chunked(*o, chunk=chunk)
    assert _took(lambda: jax.eval_shape(rule, *ops)) == {"kernel/head": 1}
    grad = jax.grad(lambda *o: jnp.sum(rule(*o)[0]), argnums=tuple(range(6)))
    assert _took(lambda: jax.eval_shape(grad, *ops)) == {"kernel/head": 1}


def test_a_decay_a_channel_keeps_the_text_on_a_tpu(monkeypatch):
    """``g`` of rank 4 (Kimi Delta Attention) lowers no ``pallas_call``
    for the fragment form on a TPU backend: the traced program is
    ``_chunked_text``'s equation for equation, and its numbers are that
    function's bit for bit."""
    rng = np.random.default_rng(9)
    ops = list(_operands(2))
    ops[4] = rng.uniform(-4.0, 0.0, ops[1].shape).astype(np.float32)
    want = _text(*ops)
    text = jax.make_jaxpr(lambda *o: _text(*o))(*ops)
    monkeypatch.setattr(backend, "is_tpu", lambda: True)
    assert not deltanet._chunked_kernel_applies(*ops[:6], CHUNK)
    before = _counts()
    traced = jax.make_jaxpr(lambda *o: deltanet.gated_delta_chunked(*o, chunk=CHUNK))(*ops)
    assert _since(before) == {"xla/channel": 1}
    assert "pallas_call" not in str(traced) and str(traced) == str(text)
    got = deltanet.gated_delta_chunked(*ops, chunk=CHUNK)
    assert all(np.array_equal(g, w) for g, w in zip(got, want))
