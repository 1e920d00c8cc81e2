"""The Nature CNN trunk (Mnih et al. 2015) in plain ``jax.numpy``.

float32 throughout, under ``jax.default_matmul_precision("highest")``
(on a TPU a float32 matmul otherwise runs as bf16 passes). Weights are
made HERE from the seed, in one jitted call on the device, and handed
to the system under test — the reference takes nothing the program
has made.

``precision="int8"`` and ``"fp8"`` are the CONTROLS of the ``correct``
comparison: the same mathematics one step below the bf16 the
configurations state — inputs and weights of every conv and hidden
dense layer rounded per tensor to 127 symmetric levels, or to
float8 e4m3 (3 mantissa bits, scaled so the largest magnitude sits at
the format's 448), and their cotangents likewise on the way back
(8-bit operands in the forward and the backward matmuls), the step a
later PR would be tempted by on a chip with 393 int8 TOP/s. Both must
come out as not correct.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp


def layer_shapes(model: Dict, heads: Dict[str, int]) -> Dict[str, tuple]:
    """``{name: kernel shape}`` for convs, hidden dense layers and the
    named heads, from the configuration file's ``model`` block."""
    h, w, c = model["input_shape"]
    shapes = {}
    for i, (out_c, kernel, stride) in enumerate(model["conv_filters"]):
        kh, kw = kernel
        shapes[f"conv{i}"] = (kh, kw, c, out_c)
        h = (h - kh) // stride[0] + 1
        w = (w - kw) // stride[1] + 1
        c = out_c
    feat = h * w * c
    for j, width in enumerate(model["dense"]):
        shapes[f"dense{j}"] = (feat, width)
        feat = width
    for name, n in heads.items():
        shapes[name] = (feat, n)
    return shapes


def init_params(key, model: Dict, heads: Dict[str, int], head_scale: Dict[str, float]):
    """Seeded weights: normal, variance 1/fan_in (times ``head_scale``
    for a head), zero biases. One jitted call; float32."""
    shapes = layer_shapes(model, heads)

    @jax.jit
    def make(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            fan_in = 1
            for d in shape[:-1]:
                fan_in *= d
            std = (head_scale.get(name, 1.0) / fan_in) ** 0.5
            out[name] = {
                "kernel": std
                * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32
                ),
                "bias": jnp.zeros((shape[-1],), jnp.float32),
            }
        return out

    return make(key)


def _round_int8(x):
    """Symmetric per-tensor rounding to 127 levels."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.clip(jnp.round(x / scale), -127.0, 127.0) * scale


@jax.custom_vjp
def _fake_int8(x):
    """An int8 matmul operand: the value is rounded going forward and
    its cotangent is rounded coming back, as in a training path whose
    forward AND backward matmuls take int8 operands."""
    return _round_int8(x)


def _fake_int8_fwd(x):
    return _round_int8(x), None


def _fake_int8_bwd(_, g):
    return (_round_int8(g),)


_fake_int8.defvjp(_fake_int8_fwd, _fake_int8_bwd)


def _round_fp8(x):
    """Per-tensor scaled rounding to float8 e4m3 (largest finite 448)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


@jax.custom_vjp
def _fake_fp8(x):
    """As ``_fake_int8``, with float8 e4m3 operands both ways."""
    return _round_fp8(x)


_fake_fp8.defvjp(lambda x: (_round_fp8(x), None), lambda _, g: (_round_fp8(g),))

_QUANT = {"float32": lambda v: v, "int8": _fake_int8, "fp8": _fake_fp8}


def conv_valid(x, kernel, stride):
    """VALID cross-correlation, NHWC x HWIO, written out: one strided
    slice of the input per kernel tap, stacked into patches, then one
    matrix product at precision "highest". (No ``lax.conv``: besides
    being the plainest form, the TPU compiler takes minutes over the
    float32 kernel-gradient of an 8x8 stride-4 convolution on one
    input channel.)"""
    kh, kw, c, out_c = kernel.shape
    sh, sw = stride
    b, h, w, _ = x.shape
    oh, ow = (h - kh) // sh + 1, (w - kw) // sw + 1
    taps = [
        x[:, i : i + sh * (oh - 1) + 1 : sh, j : j + sw * (ow - 1) + 1 : sw, :]
        for i in range(kh)
        for j in range(kw)
    ]
    patches = jnp.stack(taps, axis=3).reshape(b * oh * ow, kh * kw * c)
    y = jnp.dot(
        patches,
        kernel.reshape(kh * kw * c, out_c),
        precision=jax.lax.Precision.HIGHEST,
    )
    return y.reshape(b, oh, ow, out_c)


def trunk(params, obs, model: Dict, precision: str = "float32"):
    """uint8 pixels -> features of the last hidden dense layer."""
    if precision not in _QUANT:
        raise ValueError(f"unknown reference precision {precision!r}")
    quant = _QUANT[precision]
    x = obs.astype(jnp.float32) / 255.0
    for i, (_, _, stride) in enumerate(model["conv_filters"]):
        p = params[f"conv{i}"]
        x = conv_valid(quant(x), quant(p["kernel"]), tuple(stride))
        x = jax.nn.relu(x + p["bias"])
    x = x.reshape(x.shape[0], -1)
    for j in range(len(model["dense"])):
        p = params[f"dense{j}"]
        x = jnp.dot(
            quant(x), quant(p["kernel"]), precision=jax.lax.Precision.HIGHEST
        )
        x = jax.nn.relu(x + p["bias"])
    return x


def head(params, name: str, feat):
    p = params[name]
    return (
        jnp.dot(feat, p["kernel"], precision=jax.lax.Precision.HIGHEST)
        + p["bias"]
    )
