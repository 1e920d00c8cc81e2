"""The one-token state-space step's two lowerings (``ops/ssd.py``): the
Pallas kernel on a run's stacked leaf, run here in the interpreter,
against the ``jax.numpy`` body that states the function; which of the
two a call takes, and the counter that says so. The compile for a
described v5e lives in tests/test_replay_ring_layout.py with the other
chip compiles.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import ssd
from ray_tpu.telemetry import metrics as telemetry_metrics


def _inputs(b, h, p, n, seed=0):
    rng = np.random.default_rng(seed)
    f32 = lambda *s: rng.standard_normal(s).astype(np.float32)
    dt = np.log1p(np.exp(f32(b, h) - 1.0))
    a = -np.exp(rng.uniform(-1.0, 1.5, h)).astype(np.float32)
    return f32(b, h, p), dt, a, f32(b, n), f32(b, n)


def _leaf(shape, seed=1):
    return np.random.default_rng(seed).standard_normal(shape).astype(np.float32)


def _as_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _lowerings():
    return dict(telemetry_metrics.ssm_step_lowerings())


def _took(before):
    after = _lowerings()
    return {k: after.get(k, 0) - before.get(k, 0) for k in ("kernel", "xla")}


# the run's leaf (streams, layers, H, P, N) and the layer that steps
KERNEL_CASES = [
    pytest.param((2, 5, 64, 64, 128), layer, id=f"cell-5x64x64x128-layer-{layer}")
    for layer in range(5)
] + [
    pytest.param((3, 2, 8, 8, 256), 1, id="one-block-of-8-heads"),
    pytest.param((2, 3, 40, 16, 128), 0, id="heads-in-blocks-of-8"),
]


@pytest.mark.parametrize("shape,layer", KERNEL_CASES)
def test_kernel_agrees_with_the_body_and_leaves_the_other_layers(
        shape, layer, monkeypatch):
    """State and output of the kernel against the body on the layer's
    slice, to float32 rounding: ``dt`` at 0 (the matrix is kept, nothing
    written) and large (the matrix is forgotten), a zeroed row (an
    episode's first token). Every other layer of the leaf comes back
    bit for bit."""
    b, _, h, p, n = shape
    leaf = _leaf(shape)
    x, dt, a, bb, cc = _inputs(b, h, p, n)
    dt[0, 0], dt[0, 1] = 0.0, 60.0
    leaf[1, layer] = 0.0
    want_s, want_y = ssd._step_body(leaf[:, layer], x, dt, a, bb, cc)
    assert np.array_equal(want_s[0, 0], leaf[0, layer, 0])
    assert np.array_equal(want_s[1], (dt[1, :, None] * x[1])[..., None] * bb[1])
    _as_tpu(monkeypatch)
    assert ssd._kernel_applies(jnp.asarray(leaf))
    got, got_y = ssd.ssd_step_kernel(
        leaf, jnp.int32(layer), x, dt, a, bb, cc, interpret=True)
    np.testing.assert_allclose(got[:, layer], want_s, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got_y, want_y, rtol=1e-5, atol=2e-5)
    others = [i for i in range(shape[1]) if i != layer]
    assert np.array_equal(np.asarray(got)[:, others], leaf[:, others])
    assert np.all(np.isfinite(got)) and np.all(np.isfinite(got_y))


# shapes the kernel's lowering does not exist for, with the layer or None
FALLBACKS = [
    pytest.param((2, 3, 8, 8, 64), 1, id="half-tile-state-size"),
    pytest.param((2, 3, 6, 8, 128), 2, id="odd-heads"),
    pytest.param((2, 3, 8, 12, 128), 0, id="odd-head-size"),
    pytest.param((2, 8, 8, 128), None, id="no-layer-axis"),
]


@pytest.mark.parametrize("shape,layer", FALLBACKS)
def test_other_shapes_take_the_body_and_count_xla(shape, layer, monkeypatch):
    h, p, n = shape[-3:]
    leaf = _leaf(shape)
    x, dt, a, bb, cc = _inputs(shape[0], h, p, n)
    mine = leaf if layer is None else leaf[:, layer]
    want_s, want_y = ssd._step_body(mine, x, dt, a, bb, cc)
    _as_tpu(monkeypatch)
    assert not ssd._kernel_applies(jnp.asarray(leaf))
    before = _lowerings()
    got, got_y = ssd.ssd_step(
        leaf, x, dt, a, bb, cc, layer=None if layer is None else jnp.int32(layer))
    assert _took(before) == {"kernel": 0, "xla": 1}
    want = np.array(leaf)
    if layer is None:
        want = np.asarray(want_s)
    else:
        want[:, layer] = want_s
    assert np.array_equal(got, want) and np.array_equal(got_y, want_y)


def test_kernel_takes_the_leaf_as_its_output(monkeypatch):
    """The traced step on a TPU backend is one ``pallas_call`` that
    aliases the whole leaf in to the leaf out; nothing else produces a
    value of the leaf's shape or of a layer's slice (no slice taken out,
    none written back). Here, on the CPU, the same call is the body."""
    shape = (2, 5, 64, 64, 128)
    args = [jnp.asarray(v) for v in (_leaf(shape), *_inputs(2, 64, 64, 128))]
    step = lambda *v: ssd.ssd_step(*v, layer=jnp.int32(3))
    before = _lowerings()
    # a new function each time: jax keeps a trace by function and shapes
    cpu = jax.make_jaxpr(lambda *v: step(*v))(*args)
    assert "pallas_call" not in str(cpu)
    assert _took(before) == {"kernel": 0, "xla": 1}
    _as_tpu(monkeypatch)
    before = _lowerings()
    tpu = jax.make_jaxpr(lambda *v: step(*v))(*args)
    assert _took(before) == {"kernel": 1, "xla": 0}
    # the kernel is a jit of its own (one trace, one lowering a program)
    (inner,) = [e for e in tpu.jaxpr.eqns if e.primitive.name == "jit"]
    assert inner.params["name"] == "ssd_step_kernel"
    matrices = (shape, shape[:1] + shape[2:])  # the leaf, a layer's slice
    makes_matrices = lambda eqns, but: [
        e for e in eqns if e is not but
        and any(getattr(v.aval, "shape", ()) in matrices for v in e.outvars)
    ]
    assert not makes_matrices(tpu.jaxpr.eqns, inner)
    eqns = inner.params["jaxpr"].jaxpr.eqns
    (call,) = [e for e in eqns if e.primitive.name == "pallas_call"]
    leaf_at = [i for i, var in enumerate(call.invars) if var.aval.shape == shape]
    assert tuple(call.params["input_output_aliases"]) == ((leaf_at[0], 0),)
    assert call.outvars[0].aval.shape == shape
    assert not makes_matrices(eqns, call)


def test_chained_kernel_steps_match_the_chunked_form():
    """256 tokens through the kernel, one at a time on layer 1 of a
    stacked leaf, with an episode opening inside (the caller zeroes the
    rows first, as ``SequenceLM.reset_state`` does), against the learn
    program's chunked form from the same start state."""
    rng = np.random.default_rng(5)
    b, t, h, p, n = 2, 256, 8, 16, 128
    f32 = lambda *s: rng.standard_normal(s).astype(np.float32)
    x, bb, cc = f32(b, t, h, p), f32(b, t, n), f32(b, t, n)
    dt = np.log1p(np.exp(f32(b, t, h) - 1.0))
    a = -np.exp(rng.uniform(-1.0, 1.5, h)).astype(np.float32)
    resets = np.zeros((b, t), np.float32)
    resets[0, 70] = resets[1, 0] = resets[1, 255] = 1.0
    leaf = f32(b, 2, h, p, n)

    @jax.jit
    def chain(leaf):
        def one(leaf, inputs):
            xi, dti, bi, ci, ri = inputs
            leaf = leaf * (1.0 - ri)[:, None, None, None, None]
            return ssd.ssd_step_kernel(
                leaf, jnp.int32(1), xi, dti, a, bi, ci, interpret=True)

        step_major = lambda v: jnp.moveaxis(jnp.asarray(v), 1, 0)
        return jax.lax.scan(one, leaf, tuple(map(step_major, (x, dt, bb, cc, resets))))

    end, ys = chain(jnp.asarray(leaf))
    want, want_end = ssd.ssd_chunked(
        jnp.asarray(leaf[:, 1]), x, dt, a, bb, cc, resets=jnp.asarray(resets), chunk=64)
    np.testing.assert_allclose(jnp.moveaxis(ys, 0, 1), want, atol=2e-4, rtol=2e-5)
    np.testing.assert_allclose(end[:, 1], want_end, atol=2e-4, rtol=2e-5)
