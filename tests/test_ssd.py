"""The two forms of the Mamba-2 state-space recurrence (ops/ssd.py)
are the same function: a fragment in chunks from a start state against
the one-token step token by token, with resets inside a chunk, at its
first and at its last token, and fragments shorter and longer than the
chunk. float32 at precision "highest" in both forms: they agree to
rounding (1e-5 of values of order one)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from ray_tpu.ops import ssd

B, H, P, N = 3, 4, 8, 16


def _inputs(seed, t):
    rng = np.random.default_rng(seed)
    f32 = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    x, b, c = f32(B, t, H, P), f32(B, t, N), f32(B, t, N)
    dt = jax.nn.softplus(f32(B, t, H) - 1.0)
    a = -jnp.exp(jnp.asarray(rng.uniform(-1.0, 1.5, H), jnp.float32))
    return f32(B, H, P, N), x, dt, a, b, c


def _token_by_token(state, x, dt, a, b, c, resets):
    ys = []
    for i in range(x.shape[1]):
        if resets is not None:
            keep = 1.0 - resets[:, i]
            state = state * keep[:, None, None, None]
        state, y = ssd.ssd_step(state, x[:, i], dt[:, i], a, b[:, i], c[:, i])
        ys.append(y)
    return jnp.stack(ys, 1), state


def _resets(t, where):
    r = np.zeros((B, t), np.float32)
    for row, at in where:
        r[row, at] = 1.0
    return jnp.asarray(r)


@pytest.mark.parametrize("t,chunk,where", [
    (16, 8, ()),                       # two whole chunks, no reset
    (16, 8, ((0, 3), (1, 11))),        # a reset inside a chunk
    (16, 8, ((0, 8), (1, 0))),         # at a chunk's first token
    (16, 8, ((0, 7), (1, 15))),        # at a chunk's last token
    (16, 8, ((2, 2), (2, 5), (2, 6))), # three in one chunk
    (5, 8, ((1, 2),)),                 # a fragment shorter than the chunk
    (24, 8, ((0, 9),)),                # longer: three chunks
    (16, 256, ((1, 4),)),              # the published chunk, one chunk
])
def test_chunked_form_equals_the_step_token_by_token(t, chunk, where):
    state, x, dt, a, b, c = _inputs(7 + t + len(where), t)
    resets = _resets(t, where)
    want_y, want_s = _token_by_token(state, x, dt, a, b, c, resets)
    got_y, got_s = ssd.ssd_chunked(state, x, dt, a, b, c, resets=resets, chunk=chunk)
    np.testing.assert_allclose(got_y, want_y, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(got_s, want_s, atol=2e-5, rtol=2e-5)


def test_without_resets_and_against_the_recurrence_written_out():
    """``resets=None`` is no reset, and the step is the three published
    lines: decay, rank-one write, read."""
    state, x, dt, a, b, c = _inputs(3, 8)
    got_y, got_s = ssd.ssd_chunked(state, x, dt, a, b, c, chunk=4)
    s = np.asarray(state, np.float64)
    for i in range(8):
        decay = np.exp(np.asarray(dt[:, i], np.float64) * np.asarray(a, np.float64))
        write = np.einsum(
            "bh,bhp,bn->bhpn", np.asarray(dt[:, i], np.float64),
            np.asarray(x[:, i], np.float64), np.asarray(b[:, i], np.float64))
        s = decay[..., None, None] * s + write
        y = np.einsum("bhpn,bn->bhp", s, np.asarray(c[:, i], np.float64))
        np.testing.assert_allclose(got_y[:, i], y, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(got_s, s, atol=2e-5, rtol=2e-5)


def test_a_fragment_that_is_no_multiple_of_the_chunk_is_refused():
    state, x, dt, a, b, c = _inputs(5, 12)
    with pytest.raises(ValueError, match="not a multiple"):
        ssd.ssd_chunked(state, x, dt, a, b, c, chunk=8)


def test_gradients_of_the_two_forms_agree():
    """The learn program differentiates the chunked form; its gradient
    is the recurrence's (every input and the start state)."""
    state, x, dt, a, b, c = _inputs(11, 16)
    resets = _resets(16, ((0, 5), (2, 8)))
    probe = jnp.asarray(np.random.default_rng(2).standard_normal((B, 16, H, P)),
                        jnp.float32)

    def scalar(form):
        def f(state, x, dt, a, b, c):
            y, s = form(state, x, dt, a, b, c)
            return jnp.sum(y * probe) + jnp.sum(s)
        return f

    chunked = scalar(lambda *v: ssd.ssd_chunked(*v, resets=resets, chunk=8))
    stepped = scalar(lambda *v: _token_by_token(*v, resets))
    got = jax.grad(chunked, argnums=range(6))(state, x, dt, a, b, c)
    want = jax.grad(stepped, argnums=range(6))(state, x, dt, a, b, c)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-4, rtol=2e-4)


def test_the_step_is_counted_when_traced():
    from ray_tpu.telemetry import metrics

    before = metrics.ssm_step_lowerings().get("xla", 0)
    state, x, dt, a, b, c = _inputs(1, 1)
    jax.jit(ssd.ssd_step)(state, x[:, 0], dt[:, 0], a, b[:, 0], c[:, 0])
    assert metrics.ssm_step_lowerings()["xla"] == before + 1
