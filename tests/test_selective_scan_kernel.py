"""The fragment-form selective scan's two lowerings
(``ops/selective_scan.py``): the Pallas kernels, forward and backward
under one ``custom_vjp``, run here in the interpreter against the
``jax.numpy`` text that states the function; which of the two a call
takes, and the counter that says so. The compile for a described v5e
lives in tests/test_replay_ring_layout.py with the other chip compiles.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import backend, selective_scan
from ray_tpu.telemetry import metrics as telemetry_metrics

STREAMS, TOKENS, STATES, CHANNELS = 4, 48, 16, 256
OPERANDS = ("state0", "u", "dt", "A", "B", "C")


def _operands(b=STREAMS, t=TOKENS, n=STATES, c=CHANNELS, seed=0, stored=True):
    """A fragment from a NON-zero stored state. Stream 0 opens an
    episode at token 0, stream 1 inside a chunk (and again two tokens
    on), stream 2 on a chunk's first token, stream 3 nowhere."""
    rng = np.random.default_rng(seed)
    f32 = lambda *s: rng.standard_normal(s).astype(np.float32)
    dt = np.log1p(np.exp(f32(b, t, c) - 1.0))
    a = -np.exp(rng.uniform(-1.0, 1.5, (n, c))).astype(np.float32)
    resets = np.zeros((b, t), np.float32)
    resets[0, 0] = 1.0
    if b > 1:
        resets[1, 5] = resets[1, 7] = 1.0
    if b > 2:
        resets[2, selective_scan._CHUNK] = 1.0
    state = f32(b, n, c) if stored else np.zeros((b, n, c), np.float32)
    return state, f32(b, t, c), dt, a, f32(b, t, n), f32(b, t, n), resets


def _kernel(tile):
    return lambda *ops: selective_scan.selective_scan_kernel(
        *ops, tile=tile, interpret=True)


def _scalar(scan, seed=1):
    """A scalar of BOTH outputs, each under weights of its own."""
    rng = np.random.default_rng(seed)

    def of(*ops):
        y, after = scan(*ops)
        return (jnp.sum(y * rng.standard_normal(y.shape).astype(np.float32))
                + jnp.sum(after * rng.standard_normal(after.shape).astype(np.float32)))

    return of


@pytest.mark.parametrize("tile", [128, 256])
@pytest.mark.parametrize("stored", [True, False], ids=["stored-state", "zero-state"])
def test_kernel_agrees_with_the_text(tile, stored):
    """``y`` and the state after, to float32 rounding (the sum over the
    16 states may run in another order), and a token that opens an
    episode reads nothing of what came before it."""
    ops = _operands(stored=stored)
    want_y, want_after = selective_scan._scan_text(*ops)
    got_y, got_after = _kernel(tile)(*ops)
    np.testing.assert_allclose(got_y, want_y, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got_after, want_after, rtol=1e-6, atol=1e-6)
    # stream 0 is fresh at token 0: the stored state does not reach it
    other = (np.zeros_like(ops[0]),) + ops[1:]
    again_y, again_after = _kernel(tile)(*other)
    assert np.array_equal(np.asarray(again_y)[0], np.asarray(got_y)[0])
    assert np.array_equal(np.asarray(again_after)[0], np.asarray(got_after)[0])


@pytest.mark.parametrize("stream,opens", [(0, 0), (1, 5), (1, 7), (2, 16)])
def test_a_reset_starts_the_state_from_nothing(stream, opens):
    """At token 0, inside a chunk, again two tokens on and on a chunk's
    first token: what follows a reset, up to the next one, is the scan
    of those tokens alone from a zero state."""
    ops = _operands()
    state, u, dt, a, b, c, resets = ops
    later = [t for t in np.flatnonzero(resets[stream]) if t > opens]
    cut = slice(opens, later[0] if later else TOKENS)
    one = lambda v: v[stream : stream + 1, cut]
    alone_y, alone_after = selective_scan._scan_text(
        np.zeros_like(state[:1]), one(u), one(dt), a, one(b), one(c),
        np.zeros_like(one(resets)))
    got_y, got_after = _kernel(128)(*ops)
    np.testing.assert_allclose(
        np.asarray(got_y)[stream, cut], alone_y[0], rtol=2e-5, atol=2e-5)
    if not later:
        np.testing.assert_allclose(
            np.asarray(got_after)[stream], alone_after[0], rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("tile", [128, 256])
@pytest.mark.parametrize("operand", range(len(OPERANDS)), ids=OPERANDS)
def test_every_cotangent_agrees_with_the_text(operand, tile):
    """``jax.grad`` of a scalar of both outputs, through the backward
    kernel against the text's reverse scan, operand by operand."""
    ops = _operands()
    want = jax.grad(_scalar(selective_scan._scan_text), argnums=operand)(*ops)
    got = jax.grad(_scalar(_kernel(tile)), argnums=operand)(*ops)
    scale = float(jnp.max(jnp.abs(want)))
    assert scale > 1.0
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-6 * scale)


def test_a_reset_stops_the_gradient_where_it_stops_the_state():
    """Nothing flows to the stored state of a stream that opens an
    episode at token 0, nor to the inputs before a reset from the
    outputs after it; ``resets`` has a zero cotangent."""
    ops = _operands()

    def after_token_7(*ops):  # stream 1 resets at 7
        y, _ = _kernel(128)(*ops)
        return jnp.sum(y[1, 7:] ** 2)

    d_state, d_u = jax.grad(after_token_7, argnums=(0, 1))(*ops)
    assert not np.any(np.asarray(d_state)) and not np.any(np.asarray(d_u)[1, :7])
    assert np.any(np.asarray(d_u)[1, 7:])
    d_state, d_resets = jax.grad(_scalar(_kernel(128)), argnums=(0, 6))(*ops)
    assert not np.any(np.asarray(d_state)[0]) and np.any(np.asarray(d_state)[3])
    assert not np.any(np.asarray(d_resets))


@pytest.mark.parametrize("tile", [128, 256])
def test_under_an_outer_checkpoint_as_the_block_applies_it(tile):
    """The model's block runs under ``jax.checkpoint``: the forward
    kernel is traced again for the recomputation, and the gradients of a
    function of the scan's output are the text's."""
    ops = _operands(b=2, t=32)

    def block(scan):
        @jax.checkpoint
        def body(*ops):
            y, after = scan(*ops)
            return jnp.tanh(y) * ops[1], after  # the gate needs ``y`` again

        return lambda *ops: sum(jnp.sum(v * v) for v in body(*ops))

    want = jax.grad(block(selective_scan._scan_text), argnums=tuple(range(6)))(*ops)
    got = jax.jit(jax.grad(block(_kernel(tile)), argnums=tuple(range(6))))(*ops)
    for g, w in zip(got, want):
        np.testing.assert_allclose(
            g, w, rtol=1e-4, atol=2e-6 * float(jnp.max(jnp.abs(w))))


# -- which lowering a call takes -------------------------------------------

def _took(trace):
    before = dict(telemetry_metrics.selective_scan_lowerings())
    trace()
    after = telemetry_metrics.selective_scan_lowerings()
    return {k: after.get(k, 0) - before.get(k, 0) for k in ("kernel", "fragment")}


def _traced(ops):
    """Traced, not run: the counter counts traced forms, and off a TPU
    the kernel's own lowering does not exist."""
    return lambda: jax.eval_shape(selective_scan.selective_scan, *ops)


TEXT_CASES = [
    pytest.param(dict(c=192), id="channels-not-whole-lane-tiles"),
    pytest.param(dict(t=40), id="tokens-not-whole-chunks"),
    pytest.param(dict(n=12), id="states-not-whole-sublane-tiles"),
    pytest.param(dict(t=1 << 15, b=1, c=128), id="more-states-than-the-backward-holds"),
]


def test_the_cpu_takes_the_text():
    ops = _operands()
    assert not selective_scan._kernel_applies(*ops)
    assert _took(_traced(ops)) == {"kernel": 0, "fragment": 1}
    want = selective_scan._scan_text(*ops)
    got = selective_scan.selective_scan(*ops)
    assert all(np.array_equal(g, w) for g, w in zip(got, want))


@pytest.mark.parametrize("sizes", TEXT_CASES)
def test_odd_sizes_take_the_text_on_a_tpu(sizes, monkeypatch):
    monkeypatch.setattr(backend, "is_tpu", lambda: True)
    ops = jax.eval_shape(lambda: _operands(**{"b": 2, **sizes}))
    assert not selective_scan._kernel_applies(*ops)
    assert _took(_traced(ops)) == {"kernel": 0, "fragment": 1}


def test_another_precision_takes_the_text_on_a_tpu(monkeypatch):
    monkeypatch.setattr(backend, "is_tpu", lambda: True)
    ops = list(_operands())
    ops[1] = ops[1].astype(jnp.bfloat16)
    assert _took(_traced(ops)) == {"kernel": 0, "fragment": 1}


@pytest.mark.parametrize("channels,tile", [(256, 256), (384, 384), (5120, 1024)])
def test_whole_tiles_take_the_kernel_on_a_tpu(channels, tile, monkeypatch):
    """With ``backend.is_tpu`` true the dispatch counts ``kernel``, for
    the value and under ``grad`` (one traced scan each), and a grid step
    holds the widest tile of channels that divides them."""
    monkeypatch.setattr(backend, "is_tpu", lambda: True)
    ops = jax.eval_shape(lambda: _operands(b=2, c=channels))
    assert selective_scan._kernel_applies(*ops)
    assert selective_scan._tile_of(channels, TOKENS, STATES) == tile
    assert _took(_traced(ops)) == {"kernel": 1, "fragment": 0}
    grad = jax.grad(_scalar(selective_scan.selective_scan), argnums=(0, 1, 2, 3, 4, 5))
    assert _took(lambda: jax.eval_shape(grad, *ops)) == {"kernel": 1, "fragment": 0}
