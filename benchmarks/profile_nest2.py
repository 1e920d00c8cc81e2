"""Program-level decomposition of the real PPO SGD nest.

Builds the actual PPOJaxPolicy and times, via marginal scan-length
scaling (doubling the number of chained minibatch steps inside ONE
program, so the per-dispatch cost cancels):

  grad        value_and_grad(loss) alone, data resident
  grad+adam   + optax update + apply_updates + global_norm (the real
              mb_step body minus the row gather)
  full        + the per-minibatch row gather from the 4096-row batch
              (== the real mb_step)

Compare against bench.py's epoch-isolated nest_compute_s/80 to see
what the remaining gap is (epoch perm, stats, scan structure).

Run on the real chip: python benchmarks/profile_nest2.py
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

MB = 512
B = 4096
H, W, C, NA = 84, 84, 4, 6
STEPS = 40  # chained minibatch steps per program (doubled for margin)


def marginal(make_run, x0):
    """make_run(n_steps) -> jitted fn; returns marginal s/step.
    10x length spread: the step-count delta must put far more real
    compute between the two programs than the per-dispatch jitter,
    or the difference is noise."""
    n_lo, n_hi = STEPS, 10 * STEPS
    runs = {n: make_run(n) for n in (n_lo, n_hi)}
    for run in runs.values():
        jax.block_until_ready(run(x0))
    ts = {n: [] for n in runs}
    for _ in range(7):
        for n, run in runs.items():
            t0 = time.perf_counter()
            jax.block_until_ready(run(x0))
            ts[n].append(time.perf_counter() - t0)
    lo = float(np.median(ts[n_lo]))
    hi = float(np.median(ts[n_hi]))
    return max(hi - lo, 1e-9) / (n_hi - n_lo)


def main():
    import gymnasium as gym

    from ray_tpu.algorithms.ppo.ppo import PPOJaxPolicy

    pol = PPOJaxPolicy(
        gym.spaces.Box(0, 255, (H, W, C), np.uint8),
        gym.spaces.Discrete(NA),
        {
            "train_batch_size": B,
            "sgd_minibatch_size": MB,
            "num_sgd_iter": 10,
            "lr": 5e-5,
        },
    )
    rng = np.random.default_rng(0)
    batch = {
        "obs": rng.integers(0, 255, (B, H, W, C), dtype=np.uint8),
        "actions": rng.integers(0, NA, B).astype(np.int64),
        "action_logp": np.full(B, -1.79, np.float32),
        "action_dist_inputs": rng.standard_normal((B, NA)).astype(
            np.float32
        ),
        "advantages": rng.standard_normal(B).astype(np.float32),
        "value_targets": rng.standard_normal(B).astype(np.float32),
    }
    dev_batch = jax.device_put(batch)
    mb0 = jax.device_put(
        {k: v[:MB] for k, v in batch.items()}
    )
    loss_fn = pol.loss_with_aux
    params0 = pol.params
    opt0 = pol.opt_state
    tx = pol._tx
    coeffs = jax.device_put(pol._coeff_array())
    key = jax.random.PRNGKey(0)

    # -- (a) grad only, fixed resident minibatch -------------------------
    def make_grad_run(n):
        @jax.jit
        def run(params):
            def body(carry, rng_i):
                p = carry
                (loss, stats), g = jax.value_and_grad(
                    loss_fn, has_aux=True
                )(p, {}, mb0, rng_i, coeffs)
                p = jax.tree_util.tree_map(
                    lambda a, b: a - 1e-24 * b.astype(a.dtype), p, g
                )
                return p, loss

            rngs = jax.random.split(key, n)
            p, _ = jax.lax.scan(body, params, rngs)
            return p

        return run

    t_grad = marginal(make_grad_run, params0)

    # -- (b) + adam + global_norm ---------------------------------------
    def make_adam_run(n):
        @jax.jit
        def run(state):
            params, opt_state = state

            def body(carry, rng_i):
                p, o = carry
                (loss, stats), g = jax.value_and_grad(
                    loss_fn, has_aux=True
                )(p, {}, mb0, rng_i, coeffs)
                upd, o = tx.update(g, o, p)
                lr = coeffs["lr"]
                upd = jax.tree_util.tree_map(
                    lambda u: -lr * u.astype(jnp.float32), upd
                )
                p = optax.apply_updates(p, upd)
                gn = optax.global_norm(g)
                return (p, o), gn

            rngs = jax.random.split(key, n)
            (p, o), _ = jax.lax.scan(body, (params, opt_state), rngs)
            return p

        return run

    t_adam = marginal(make_adam_run, (params0, opt0))

    # -- (b2) flattened adam (one fused kernel over one flat vector) ----
    tx_flat = optax.flatten(optax.adam(5e-5))
    opt_flat = tx_flat.init(params0)

    def make_flat_run(n):
        @jax.jit
        def run(state):
            params, opt_state = state

            def body(carry, rng_i):
                p, o = carry
                (loss, stats), g = jax.value_and_grad(
                    loss_fn, has_aux=True
                )(p, {}, mb0, rng_i, coeffs)
                upd, o = tx_flat.update(g, o, p)
                lr = coeffs["lr"]
                upd = jax.tree_util.tree_map(
                    lambda u: -lr * u.astype(jnp.float32), upd
                )
                p = optax.apply_updates(p, upd)
                gn = optax.global_norm(g)
                return (p, o), gn

            rngs = jax.random.split(key, n)
            (p, o), _ = jax.lax.scan(body, (params, opt_state), rngs)
            return p

        return run

    t_flat = marginal(make_flat_run, (params0, opt_flat))

    # -- (b3) plain adam, no global_norm --------------------------------
    def make_nognorm_run(n):
        @jax.jit
        def run(state):
            params, opt_state = state

            def body(carry, rng_i):
                p, o = carry
                (loss, stats), g = jax.value_and_grad(
                    loss_fn, has_aux=True
                )(p, {}, mb0, rng_i, coeffs)
                upd, o = tx.update(g, o, p)
                lr = coeffs["lr"]
                upd = jax.tree_util.tree_map(
                    lambda u: -lr * u.astype(jnp.float32), upd
                )
                p = optax.apply_updates(p, upd)
                return (p, o), loss

            rngs = jax.random.split(key, n)
            (p, o), _ = jax.lax.scan(body, (params, opt_state), rngs)
            return p

        return run

    t_nognorm = marginal(make_nognorm_run, (params0, opt0))

    # -- (c) + per-step row gather from the full 4096 batch --------------
    def make_full_run(n):
        @jax.jit
        def run(state):
            params, opt_state = state

            def body(carry, rng_i):
                p, o = carry
                idx = jax.random.randint(rng_i, (MB,), 0, B)
                mb = {k: v[idx] for k, v in dev_batch.items()}
                (loss, stats), g = jax.value_and_grad(
                    loss_fn, has_aux=True
                )(p, {}, mb, rng_i, coeffs)
                upd, o = tx.update(g, o, p)
                lr = coeffs["lr"]
                upd = jax.tree_util.tree_map(
                    lambda u: -lr * u.astype(jnp.float32), upd
                )
                p = optax.apply_updates(p, upd)
                gn = optax.global_norm(g)
                return (p, o), gn

            rngs = jax.random.split(key, n)
            (p, o), _ = jax.lax.scan(body, (params, opt_state), rngs)
            return p

        return run

    t_full = marginal(make_full_run, (params0, opt0))

    print(f"grad only          {t_grad*1e3:7.3f} ms/step")
    print(f"grad+adam+gnorm    {t_adam*1e3:7.3f} ms/step")
    print(f"grad+FLAT adam+gn  {t_flat*1e3:7.3f} ms/step")
    print(f"grad+adam (no gn)  {t_nognorm*1e3:7.3f} ms/step")
    print(f"+row gather        {t_full*1e3:7.3f} ms/step")
    print("bench.py nest:       0.616 ms/step (49.3 ms / 80)")


if __name__ == "__main__":
    main()
