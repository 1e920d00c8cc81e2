"""Per-op decomposition of the PPO SGD nest (bench.py headline program).

Times, on the real chip at the headline geometry (mb=512, 84x84x4):
each conv / fc layer (fwd and fwd+bwd), the full loss fwd+bwd, the
row-gather + uint8->bf16 preprocessing, and the adam update — then
compares their sum against bench.py's epoch-isolated nest time,
attributing the MFU gap to specific ops.

Each op is timed as a jitted ``lax.fori_loop`` of REPS iterations whose
body feeds a scaled summary of the op's output back into its input
(loop-carried dependency), so XLA can neither dead-code-eliminate the
op nor hoist it out of the loop; the per-dispatch latency
amortizes across REPS on-device iterations.

Run: python benchmarks/profile_nest.py
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

MB = 512
H, W, C, NA = 84, 84, 4, 6
REPS = 50


def timed_loop(body, x0):
    """MARGINAL seconds per iteration of body: times a fori_loop at
    REPS and 4*REPS iterations and divides the difference — the fixed
    per-dispatch cost (which would otherwise swamp sub-ms ops)
    cancels."""
    runs = {}
    for reps in (REPS, 4 * REPS):

        @jax.jit
        def run(x, reps=reps):
            return jax.lax.fori_loop(
                0, reps, lambda i, x: body(x), x
            )

        jax.block_until_ready(run(x0))
        runs[reps] = run
    ts = {REPS: [], 4 * REPS: []}
    for _ in range(5):  # interleave against drift
        for reps, run in runs.items():
            t0 = time.perf_counter()
            jax.block_until_ready(run(x0))
            ts[reps].append(time.perf_counter() - t0)
    lo = float(np.median(ts[REPS]))
    hi = float(np.median(ts[4 * REPS]))
    return max(hi - lo, 1e-9) / (3 * REPS)


def feedback(x, scalar):
    """x + tiny*scalar — loop-carried dep that costs ~nothing."""
    return x + (scalar * 1e-24).astype(x.dtype)


def main():
    import flax.linen as nn

    from ray_tpu.models.cnn import NATURE_FILTERS, VisionNet

    rng = np.random.default_rng(0)
    obs_f = jnp.asarray(
        (rng.integers(0, 255, (MB, H, W, C)) / 255.0).astype(np.float32)
    ).astype(jnp.bfloat16)

    report = {}

    # -- per-layer conv/fc ----------------------------------------------
    x = obs_f
    ch_in = C
    total_fwd = total_fb = 0.0
    for li, (ch, kern, stride) in enumerate(NATURE_FILTERS):
        conv = nn.Conv(
            ch, kern, strides=stride, padding="VALID",
            dtype=jnp.bfloat16,
        )
        cp = conv.init(jax.random.PRNGKey(li), x)
        y = conv.apply(cp, x)

        t_f = timed_loop(
            lambda xx, cp=cp, conv=conv: feedback(
                xx, jnp.sum(conv.apply(cp, xx).astype(jnp.float32))
            ),
            x,
        )

        def lconv(cpp, xx, conv=conv):
            return jnp.sum(conv.apply(cpp, xx).astype(jnp.float32) ** 2)

        gfn = jax.grad(lconv, argnums=(0, 1))

        def bwd_body(xx, cp=cp, gfn=gfn):
            g0, g1 = gfn(cp, xx)
            return xx + g1.astype(xx.dtype) * jnp.bfloat16(1e-24)

        t_fb = timed_loop(bwd_body, x)

        kh, kw = kern
        oh, ow = int(y.shape[1]), int(y.shape[2])
        macs = MB * oh * ow * ch * kh * kw * ch_in
        report[f"conv{li}"] = dict(
            fwd_ms=t_f * 1e3,
            fwdbwd_ms=t_fb * 1e3,
            fwd_tflops=2 * macs / t_f / 1e12,
            fwdbwd_tflops=3 * 2 * macs / t_fb / 1e12,
            out=f"{oh}x{ow}x{ch}",
        )
        total_fwd += t_f
        total_fb += t_fb
        x = jax.nn.relu(y)
        ch_in = ch

    xf = x.reshape(MB, -1)
    fc = nn.Dense(512, dtype=jnp.bfloat16)
    fp = fc.init(jax.random.PRNGKey(9), xf)
    t_fc = timed_loop(
        lambda xx: feedback(
            xx, jnp.sum(fc.apply(fp, xx).astype(jnp.float32))
        ),
        xf,
    )

    def lfc(fpp, xx):
        return jnp.sum(fc.apply(fpp, xx).astype(jnp.float32) ** 2)

    gfc = jax.grad(lfc, argnums=(0, 1))

    def fc_bwd(xx):
        g0, g1 = gfc(fp, xx)
        return xx + g1.astype(xx.dtype) * jnp.bfloat16(1e-24)

    t_fcb = timed_loop(fc_bwd, xf)
    macs_fc = MB * xf.shape[1] * 512
    report["fc"] = dict(
        fwd_ms=t_fc * 1e3,
        fwdbwd_ms=t_fcb * 1e3,
        fwd_tflops=2 * macs_fc / t_fc / 1e12,
        fwdbwd_tflops=6 * macs_fc / t_fcb / 1e12,
    )
    total_fwd += t_fc
    total_fb += t_fcb

    # -- full model loss fwd+bwd (the real nest body) --------------------
    net = VisionNet(num_outputs=NA)
    obs_u8 = jnp.asarray(
        rng.integers(0, 255, (MB, H, W, C), dtype=np.uint8)
    )
    params = net.init(jax.random.PRNGKey(0), obs_u8)
    actions = jnp.asarray(rng.integers(0, NA, MB))
    adv = jnp.asarray(rng.standard_normal(MB).astype(np.float32))

    def loss(p, o):
        logits, value, _ = net.apply(p, o)
        logp = jax.nn.log_softmax(logits)[jnp.arange(MB), actions]
        return jnp.mean(-logp * adv) + jnp.mean(value**2)

    gl = jax.grad(loss)

    def train_body(p):
        g = gl(p, obs_u8)
        return jax.tree_util.tree_map(
            lambda a, b: a - b.astype(a.dtype) * 1e-24, p, g
        )

    t_step = timed_loop(train_body, params)

    # -- gather + preprocess (per-minibatch row gather in the nest) ------
    full = jnp.asarray(
        rng.integers(0, 255, (4096, H, W, C), dtype=np.uint8)
    )
    idx0 = jnp.asarray(rng.permutation(4096)[:MB])

    def gath(state):
        f, idx = state
        mb = f[idx].astype(jnp.bfloat16) / 255.0
        # loop-carried dep through idx so the gather can't hoist
        shift = (
            jnp.sum(mb.astype(jnp.float32)).astype(jnp.int32) % 2 + 1
        )
        return f, (idx + shift) % 4096

    t_g = timed_loop(gath, (full, idx0))

    # -- report ----------------------------------------------------------
    for k, v in report.items():
        print(
            f"{k:6s} fwd {v['fwd_ms']:7.3f} ms ({v['fwd_tflops']:5.1f}"
            f" TF/s)   fwd+bwd {v['fwdbwd_ms']:7.3f} ms"
            f" ({v['fwdbwd_tflops']:5.1f} TF/s)"
            f"  {v.get('out','')}"
        )
    print(f"layer-sum fwd {total_fwd*1e3:7.3f} ms  fwd+bwd "
          f"{total_fb*1e3:7.3f} ms")
    print(f"full train step (fwd+bwd+sgd) {t_step*1e3:7.3f} ms")
    print(f"gather+prep (4096->512)       {t_g*1e3:7.3f} ms")
    n_mb = 4096 // MB * 10
    print(
        f"\nnest estimate: {n_mb} x step = {n_mb*t_step*1e3:.1f} ms"
        f" + {n_mb} x gather = {n_mb*t_g*1e3:.1f} ms"
        f"   (bench.py nest_compute_s ~49.3 ms)"
    )


if __name__ == "__main__":
    main()
