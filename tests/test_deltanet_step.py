"""The one-token gated delta rule's two lowerings (``ops/deltanet.py``):
the Pallas kernel, run here in the interpreter, against the four-line
``jax.numpy`` body that states the function; which of the two a call
takes, and the counter that says so. The compile for a described v5e
lives in tests/test_replay_ring_layout.py with the other chip compiles.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import deltanet
from ray_tpu.telemetry import metrics as telemetry_metrics


def _inputs(b, h, dk, dv, seed=0, channel=False):
    """``channel``: a decay a key channel (Kimi Delta Attention), ``g``
    ``(b, h, dk)``; else a decay a head."""
    rng = np.random.default_rng(seed)
    f32 = lambda *s: rng.standard_normal(s).astype(np.float32)
    s, q, k, v = f32(b, h, dk, dv), f32(b, h, dk) / np.sqrt(dk), f32(b, h, dk), f32(b, h, dv)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    g = -rng.uniform(0.01, 1.0, (b, h, dk) if channel else (b, h)).astype(np.float32)
    beta = rng.uniform(0.1, 0.9, (b, h)).astype(np.float32)
    return s, q, k, v, g, beta


def _as_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _lowerings():
    return dict(telemetry_metrics.deltanet_step_lowerings())


# (streams, heads, dk, dv), whether the kernel's lowering exists for it,
# whether the decay is a number a key channel
SHAPES = [
    pytest.param((2, 32, 128, 128), True, False, id="cell-32x128x128"),
    pytest.param((3, 8, 128, 256), True, False, id="one-block-of-8-heads"),
    pytest.param((2, 4, 64, 128), False, False, id="half-tile-dk-falls-back"),
    pytest.param((2, 6, 128, 128), False, False, id="odd-heads-fall-back"),
    pytest.param((2, 32, 128, 128), True, True, id="kda-cell-32x128x128-a-decay-a-channel"),
    pytest.param((3, 8, 128, 256), True, True, id="kda-one-block-of-8-heads"),
    pytest.param((2, 4, 64, 128), False, True, id="kda-half-tile-dk-falls-back"),
]


@pytest.mark.parametrize("shape,kernel,channel", SHAPES)
def test_step_lowerings_agree(shape, kernel, channel, monkeypatch):
    """State and output of the lowering a TPU would take against the
    body, to float32 rounding: a reset on some rows (``g = -inf``), ``g``
    at 0 and very negative, ``beta`` at both ends; with a decay a head
    and with a decay a key channel (a head's row of them on those rows)."""
    s, q, k, v, g, beta = _inputs(*shape, channel=channel)
    g[0, 0], g[0, 1], g[1, :2] = 0.0, -80.0, -np.inf
    beta[0, 2], beta[0, 3] = 0.0, 1.0
    want_s, want_o = deltanet._delta_step_body(s, q, k, v, g, beta)
    assert not np.any(np.asarray(want_s)[1, :2] - k[1, :2, :, None] * (
        beta[1, :2, None, None] * v[1, :2, None, :]))  # a reset row holds its write alone
    _as_tpu(monkeypatch)
    assert deltanet._kernel_applies(jnp.asarray(s)) is kernel
    if kernel:
        got_s, got_o = deltanet.gated_delta_step_kernel(
            s, q, k, v, g, beta, interpret=True)
    else:
        before = _lowerings()
        got_s, got_o = deltanet.gated_delta_step(s, q, k, v, g, beta)
        after = _lowerings()
        assert after.get("xla", 0) - before.get("xla", 0) == 1
        assert after.get("kernel", 0) == before.get("kernel", 0)
    np.testing.assert_allclose(got_s, want_s, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got_o, want_o, rtol=1e-5, atol=1e-5)
    assert np.all(np.isfinite(got_s)) and np.all(np.isfinite(got_o))


def test_kernel_takes_the_state_buffer_as_its_output(monkeypatch):
    """The call aliases the state in to the state out (no second 0.4 GB
    of state beside the first), and the dispatch picks it on a TPU
    backend only: here, on the CPU, the same call is the body."""
    args = [jnp.asarray(a) for a in _inputs(2, 16, 128, 128)]
    before = _lowerings()
    # a new function each time: jax keeps a trace by function and shapes
    cpu = jax.make_jaxpr(lambda *a: deltanet.gated_delta_step(*a))(*args)
    assert "pallas_call" not in str(cpu)
    _as_tpu(monkeypatch)
    tpu = jax.make_jaxpr(lambda *a: deltanet.gated_delta_step(*a))(*args)
    after = _lowerings()
    assert after.get("xla", 0) - before.get("xla", 0) == 1
    assert after.get("kernel", 0) - before.get("kernel", 0) == 1
    # the kernel is a jit of its own (one trace, one lowering a program)
    (inner,) = [e for e in tpu.jaxpr.eqns if e.primitive.name == "jit"]
    assert inner.params["name"] == "gated_delta_step_kernel"
    # nothing but that call touches a matrix: no pass before or after it
    assert not [
        e for e in tpu.jaxpr.eqns if e is not inner
        and any(getattr(x.aval, "shape", ()) == args[0].shape for x in e.outvars)
    ]
    eqns = inner.params["jaxpr"].jaxpr.eqns
    (call,) = [e for e in eqns if e.primitive.name == "pallas_call"]
    state_at = [i for i, var in enumerate(call.invars) if var.aval.shape == args[0].shape]
    assert tuple(call.params["input_output_aliases"]) == ((state_at[0], 0),)
    assert call.outvars[0].aval.shape == args[0].shape
    assert not [
        e for e in eqns if e is not call
        and any(getattr(x.aval, "shape", ()) == args[0].shape for x in e.outvars)
    ]


def test_chained_kernel_steps_match_the_chunked_form():
    """128 tokens through the kernel, one at a time, with an episode
    opening inside (``g = -inf``: the decay clears the matrix), against
    the learn program's chunked form from the same start state."""
    rng = np.random.default_rng(5)
    b, t, h, dk, dv = 2, 128, 8, 128, 128
    f32 = lambda *s: rng.standard_normal(s).astype(np.float32)
    q, k, v = f32(b, t, h, dk) / np.sqrt(dk), f32(b, t, h, dk), f32(b, t, h, dv)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    g = -rng.uniform(0.01, 1.0, (b, t, h)).astype(np.float32)
    beta = rng.uniform(0.1, 0.9, (b, t, h)).astype(np.float32)
    resets = np.zeros((b, t), np.float32)
    resets[0, 70] = resets[1, 0] = resets[1, 127] = 1.0
    s0 = f32(b, h, dk, dv)

    @jax.jit
    def chain(s):
        def one(s, x):
            qi, ki, vi, gi, bi, ri = x
            gi = jnp.where(ri[:, None] > 0.5, -jnp.inf, gi)
            return deltanet.gated_delta_step_kernel(
                s, qi, ki, vi, gi, bi, interpret=True)

        step_major = lambda a: jnp.moveaxis(jnp.asarray(a), 1, 0)
        return jax.lax.scan(one, s, tuple(map(step_major, (q, k, v, g, beta, resets))))

    s_end, outs = chain(jnp.asarray(s0))
    got, want_end = deltanet.gated_delta_chunked(
        jnp.asarray(s0), q, k, v, g, beta, resets=jnp.asarray(resets), chunk=64
    )
    np.testing.assert_allclose(got, jnp.moveaxis(outs, 0, 1), atol=5e-5)
    np.testing.assert_allclose(want_end, s_end, atol=5e-5)


def test_model_step_takes_the_kernel_on_a_tpu_and_the_body_here(monkeypatch):
    """The sequence model's one-token form at whole-tile head sizes:
    one counted lowering per DeltaNet layer, ``kernel`` where the
    backend is a TPU and ``xla`` on the CPU, with nothing passed down
    to say so; without a ``resets`` flag (the lane's act) the model
    makes no pass over a matrix but the step's own."""
    from ray_tpu.models.sequence_lm import SequenceLM

    model = SequenceLM(32, {
        "hidden_size": 32, "num_hidden_layers": 4, "full_attention_interval": 4,
        "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 16,
        "max_position_embeddings": 8, "linear_num_key_heads": 4,
        "linear_num_value_heads": 8, "linear_key_head_dim": 128,
        "linear_value_head_dim": 128, "linear_conv_kernel_dim": 4,
        "num_experts": 2, "num_experts_per_tok": 1,
        "moe_intermediate_size": 16, "shared_expert_intermediate_size": 16,
    }, dtype="float32")
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    state = jax.eval_shape(lambda: model.initial_state(2))
    obs = jax.ShapeDtypeStruct((2, 1, 1), jnp.int32)

    def traced():
        before = _lowerings()
        jaxpr = jax.make_jaxpr(lambda p, o, s: model.apply(p, o, s))(params, obs, state)
        after = _lowerings()
        return jaxpr, {k: after.get(k, 0) - before.get(k, 0) for k in ("kernel", "xla")}

    assert traced()[1] == {"kernel": 0, "xla": 3}
    _as_tpu(monkeypatch)
    jaxpr, took = traced()
    assert took == {"kernel": 3, "xla": 0}
    assert not [
        e for e in jaxpr.jaxpr.eqns if e.primitive.name == "select_n"
        and e.outvars[0].aval.shape == (2, 8, 128, 128)
    ]
