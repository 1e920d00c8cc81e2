"""A/B the Nature-CNN first conv against its space-to-depth
reparametrization (MLPerf-style): conv 8x8 stride 4 on (84,84,4)
== conv 2x2 stride 1 on the 4x4-space-to-depth input (21,21,64),
with permuted weights. Same math, same FLOPs — but the weight-grad
convolution XLA derives from the stride-4 form is badly shaped for
the MXU (few taps, big dilation), while the s2d form's is a dense
2x2 conv over 64 input channels.

Times fwd and fwd+bwd of both at mb=512 via marginal fori_loop
scaling (the per-dispatch cost cancels). Run on the real chip.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

MB = 512
REPS = 200


def timed_loop(body, x0):
    runs = {}
    for reps in (REPS, 2 * REPS):

        @jax.jit
        def run(x, reps=reps):
            return jax.lax.fori_loop(0, reps, lambda i, x: body(x), x)

        jax.block_until_ready(run(x0))
        runs[reps] = run
    ts = {r: [] for r in runs}
    for _ in range(7):
        for reps, run in runs.items():
            t0 = time.perf_counter()
            jax.block_until_ready(run(x0))
            ts[reps].append(time.perf_counter() - t0)
    lo = float(np.median(ts[REPS]))
    hi = float(np.median(ts[2 * REPS]))
    return max(hi - lo, 1e-9) / REPS


def s2d(x, f=4):
    """(N,H,W,C) -> (N,H/f,W/f,C*f*f) space-to-depth."""
    n, h, w, c = x.shape
    x = x.reshape(n, h // f, f, w // f, f, c)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(
        n, h // f, w // f, f * f * c
    )


def main():
    import flax.linen as nn

    rng = np.random.default_rng(0)
    x = jnp.asarray(
        (rng.integers(0, 255, (MB, 84, 84, 4)) / 255.0).astype(
            np.float32
        )
    ).astype(jnp.bfloat16)
    macs = MB * 20 * 20 * 32 * 8 * 8 * 4

    variants = {}

    conv_a = nn.Conv(
        32, (8, 8), strides=(4, 4), padding="VALID",
        dtype=jnp.bfloat16,
    )
    pa = conv_a.init(jax.random.PRNGKey(0), x)
    variants["conv8x8s4"] = (conv_a, pa, x)

    xs = s2d(np.asarray(x, np.float32), 4)
    xs = jnp.asarray(xs).astype(jnp.bfloat16)
    conv_b = nn.Conv(
        32, (2, 2), strides=(1, 1), padding="VALID",
        dtype=jnp.bfloat16,
    )
    pb = conv_b.init(jax.random.PRNGKey(0), xs)
    variants["s2d+conv2x2s1"] = (conv_b, pb, xs)

    for name, (conv, p, xx) in variants.items():

        def fwd_body(v, conv=conv, p=p):
            y = conv.apply(p, v)
            return v + jnp.sum(y.astype(jnp.float32)).astype(
                v.dtype
            ) * jnp.bfloat16(1e-24)

        t_f = timed_loop(fwd_body, xx)

        def loss(pp, v, conv=conv):
            return jnp.sum(conv.apply(pp, v).astype(jnp.float32) ** 2)

        gfn = jax.grad(loss, argnums=(0, 1))

        def bwd_body(v, p=p, gfn=gfn):
            g0, g1 = gfn(p, v)
            return v + g1.astype(v.dtype) * jnp.bfloat16(1e-24)

        t_fb = timed_loop(bwd_body, xx)

        # weight-grad only (input grad DCE'd like the real first layer)
        gw = jax.grad(loss, argnums=0)

        def wgrad_body(v, p=p, gw=gw):
            g0 = gw(p, v)
            lead = jax.tree_util.tree_leaves(g0)[0]
            return v + jnp.sum(lead.astype(jnp.float32)).astype(
                v.dtype
            ) * jnp.bfloat16(1e-24)

        t_w = timed_loop(wgrad_body, xx)

        print(
            f"{name:14s} fwd {t_f*1e3:7.3f} ms ({2*macs/t_f/1e12:6.1f}"
            f" TF/s)  fwd+wgrad {t_w*1e3:7.3f} ms"
            f" ({4*macs/t_w/1e12:6.1f} TF/s)  fwd+full-bwd"
            f" {t_fb*1e3:7.3f} ms ({6*macs/t_fb/1e12:6.1f} TF/s)"
        )


if __name__ == "__main__":
    main()
