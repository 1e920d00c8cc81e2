"""On-hardware smoke tests (real TPU only).

The default test run forces the virtual 8-device CPU platform
(``conftest.py``); these tests only run under ``RAY_TPU_HW_TEST=1
pytest tests/test_tpu_hardware.py``, where the conftest leaves the real
backend in place. They validate, for exactly the shapes the hot paths
use, that the flash-attention kernel compiles and matches the XLA
reference (the concern raised for Mosaic tile alignment on small GTrXL
head dims; reference precedent:
``rllib/models/torch/attention_net.py:37`` shapes), and that the XLA
bodies of the replay plane's ops (Mosaic refused a kernel for each,
PR 21) match a host reference on the chip.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

pytestmark = pytest.mark.skipif(
    os.environ.get("RAY_TPU_HW_TEST") != "1"
    or jax.default_backend() != "tpu",
    reason="requires RAY_TPU_HW_TEST=1 and a real TPU backend",
)


# (B, H, T, S, D): GTrXL unrolls (small T, head_dim 16-32) and a
# square block like ring attention's per-hop tile.
FLASH_SHAPES = [(32, 1, 20, 70, 32), (8, 2, 10, 60, 16), (4, 4, 100, 100, 64)]
STATS_SHAPES = [(8, 128, 64), (4, 256, 128)]


@pytest.mark.parametrize("shape", FLASH_SHAPES)
def test_flash_attention_on_tpu(shape):
    from ray_tpu.ops.flash_attention import flash_attention

    B, H, T, S, D = shape
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    M = S - T
    out = flash_attention(q, k, v, causal_offset=M, use_pallas=True)
    ref = flash_attention(q, k, v, causal_offset=M, use_pallas=False)
    # MXU matmuls accumulate through bf16 passes on TPU; tolerance is
    # set for that, not for fp32 HBM math.
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-2)


@pytest.mark.parametrize("shape", STATS_SHAPES)
def test_flash_block_stats_on_tpu(shape):
    from ray_tpu.ops.flash_attention import (
        _reference_attention,
        flash_block_attention_stats,
    )

    N, T, D = shape
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(N, T, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(N, T, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(N, T, D)), jnp.float32)
    acc, m, l = flash_block_attention_stats(q, k, v, jnp.int32(T))
    out = np.asarray(acc) / np.maximum(np.asarray(l)[..., None], 1e-30)
    ref = np.asarray(_reference_attention(q, k, v, None))
    np.testing.assert_allclose(out, ref, atol=2e-2)


def test_auto_is_the_kernel_on_a_tpu():
    """``use_pallas=None`` is decided by the backend, with no lowering
    probe and nothing caught: on the chip auto is the kernel, bitwise
    the forced kernel."""
    from ray_tpu.ops.flash_attention import flash_attention

    rng = np.random.default_rng(7)
    q, k, v = (
        jnp.asarray(rng.normal(size=(4, 2, 64, 32)), jnp.float32)
        for _ in range(3)
    )
    np.testing.assert_array_equal(
        np.asarray(flash_attention(q, k, v, causal_offset=0)),
        np.asarray(
            flash_attention(q, k, v, causal_offset=0, use_pallas=True)
        ),
    )


# -- the PPO hot-path ops at their hot-path shapes ----------------------
#
# Pixel PPO (tuned_examples/ppo/ponglite*-ppo.yaml): the frame pool is
# (M, 84, 84, 1) uint8 = (M, 1764) uint32 lanes, the rebuild gathers
# R = 4 * 2048 rows of it per nest; the fused lane's GAE scans
# (16, 128) fragments on one chip and (4, 128) per shard on four.
# These are XLA bodies, and the XLA body is what must be right on the
# chip.

POOL_D = 84 * 84 // 4
ROWS = 2048


def _pool(rng, m):
    return rng.integers(0, 2**32, (m, POOL_D), dtype=np.uint32)


def test_row_gather_scatter_auto_hot_shape_bitwise():
    from ray_tpu.ops.framestack import gather_rows, scatter_rows

    rng = np.random.default_rng(3)
    src = _pool(rng, ROWS + 48)
    idx = rng.integers(0, ROWS + 44, ROWS)[:, None] + np.arange(4)
    out = jax.jit(gather_rows)(jnp.asarray(src), jnp.asarray(idx))
    assert out.shape == (ROWS, 4, POOL_D)
    np.testing.assert_array_equal(np.asarray(out), src[idx])

    pos = rng.permutation(ROWS + 48)[:ROWS]
    vals = _pool(rng, ROWS)
    want = src.copy()
    want[pos] = vals
    out = jax.jit(scatter_rows)(
        jnp.asarray(src), jnp.asarray(pos), jnp.asarray(vals)
    )
    np.testing.assert_array_equal(np.asarray(out), want)


def test_build_stacks_auto_hot_shape_bitwise():
    """The call the learn program makes, on uint8 frames, against the
    host materialization."""
    from ray_tpu.ops.framestack import (
        build_stacks,
        materialize_stacks_np,
    )

    rng = np.random.default_rng(4)
    frames = rng.integers(0, 256, (ROWS + 48, 84, 84, 1), dtype=np.uint8)
    idx = rng.integers(0, ROWS + 44, ROWS).astype(np.int32)
    out = jax.jit(lambda f, i: build_stacks(f, i, 4))(
        jnp.asarray(frames), jnp.asarray(idx)
    )
    assert out.shape == (ROWS, 84, 84, 4)
    np.testing.assert_array_equal(
        np.asarray(out), materialize_stacks_np(frames, idx, 4)
    )


def _gae_inputs(shape):
    rng = np.random.default_rng(5)
    r = rng.standard_normal(shape).astype(np.float32)
    v = rng.standard_normal(shape).astype(np.float32)
    nv = rng.standard_normal(shape).astype(np.float32)
    term = rng.random(shape) < 0.02
    done = term | (rng.random(shape) < 0.02)
    return r, v, nv, term, done


@pytest.mark.parametrize("shape", [(16, 128), (4, 128)])
def test_gae_fragment_auto_hot_shape(shape):
    """The associative scan against the sequential float32
    recurrence it reassociates: the op's stated 1e-4 contract."""
    from ray_tpu.ops.gae import compute_gae_fragment

    r, v, nv, term, done = _gae_inputs(shape)
    gamma, lam = 0.99, 0.95
    adv, vt = jax.jit(
        lambda *x: compute_gae_fragment(*x, gamma, lam)
    )(*map(jnp.asarray, (r, v, nv, term, done)))
    deltas = r + gamma * nv * (1.0 - term) - v
    coeffs = gamma * lam * (1.0 - done)
    want = np.zeros(shape, np.float32)
    run = np.zeros(shape[0], np.float32)
    for t in range(shape[1] - 1, -1, -1):
        run = (deltas[:, t] + coeffs[:, t] * run).astype(np.float32)
        want[:, t] = run
    np.testing.assert_allclose(
        np.asarray(adv), want, atol=1e-4, rtol=1e-4
    )
    np.testing.assert_allclose(
        np.asarray(vt), want + v, atol=1e-4, rtol=1e-4
    )


def test_device_sumtree_auto_runs_the_xla_descent():
    """The device tree's draw runs the XLA f64 descent on the chip
    and reproduces the host tree's draw."""
    from ray_tpu.ops.segment_tree import DeviceSumTree, SumSegmentTree

    cap = 1024
    rng = np.random.default_rng(6)
    leaves = rng.random(cap) + 0.01
    host = SumSegmentTree(cap)
    host.set_items(np.arange(cap), leaves)
    dt = DeviceSumTree(cap)
    dt.set_powered(np.arange(cap), leaves)
    rand = rng.random(64)
    want = np.clip(
        host.find_prefixsum_idx(
            (rand + np.arange(64)) / 64 * host.sum(0, cap)
        ),
        0,
        cap - 1,
    )
    idx, weights = dt.draw(rand, cap, 0.4)
    np.testing.assert_array_equal(np.asarray(idx), want)
    assert np.isfinite(np.asarray(weights)).all()


def test_pbt_trials_jit_on_tpu(tmp_path):
    """Tune trials with resources_per_trial={'TPU': 1} time-slice the
    driver's mesh: every trainable's jitted step runs on the REAL TPU
    backend, and PBT exploit still works across the population
    (reference: GPU trial resources via placement groups,
    tune/execution/ray_trial_executor.py)."""
    import ray_tpu.tune.tune as tune
    from ray_tpu.tune.schedulers import PopulationBasedTraining
    from ray_tpu.tune.search import uniform
    from ray_tpu.tune.trainable import Trainable

    platforms = []

    class JitTrainable(Trainable):
        def setup(self, config):
            self.lr = config["lr"]
            self.w = jnp.zeros(())
            self._step_fn = jax.jit(lambda w, lr: w + lr)

        def step(self):
            self.w = self._step_fn(self.w, self.lr)
            platforms.append(
                next(iter(self.w.devices())).platform
            )
            return {"episode_reward_mean": float(self.w)}

        def get_exploit_state(self):
            return {"w": jax.device_get(self.w)}

        def apply_exploit(self, state, scalars):
            self.w = jnp.asarray(state["w"])
            self.lr = scalars.get("lr", self.lr)

        def get_exploit_scalars(self):
            return {"lr": self.lr}

    ana = tune.run(
        JitTrainable,
        config={"lr": uniform(0.01, 0.1)},
        num_samples=3,
        scheduler=PopulationBasedTraining(
            time_attr="training_iteration",
            perturbation_interval=2,
            hyperparam_mutations={"lr": uniform(0.01, 0.1)},
        ),
        resources_per_trial={"TPU": 1},
        max_iterations=6,
        local_dir=str(tmp_path),
        verbose=0,
    )
    assert len(ana.trials) == 3
    assert platforms and all(p == "tpu" for p in platforms), set(
        platforms
    )
    assert all(
        t.last_result.get("training_iteration") == 6
        for t in ana.trials
    )
