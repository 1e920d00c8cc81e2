"""On-hardware smoke tests (real TPU only).

The default test run forces the virtual 8-device CPU platform
(``conftest.py``); these tests only run under ``RAY_TPU_HW_TEST=1
pytest tests/test_tpu_hardware.py``, where the conftest leaves the real
backend in place. They validate, for exactly the shapes the hot paths
use, that each Pallas kernel either compiles and matches the XLA
reference (flash attention — the concern raised for Mosaic tile
alignment on small GTrXL head dims; reference precedent:
``rllib/models/torch/attention_net.py:37`` shapes) or is refused by
Mosaic with the message its module quotes, in which case ``auto`` runs
the XLA path and THAT is checked against a host reference.
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


def test_auto_is_a_rule_not_a_probe():
    """``use_pallas=None`` is decided by the backend and a per-kernel
    constant — there is no lowering probe to cache, and nothing to
    catch: flash attention compiles here, the row copy and the GAE
    scan do not (below), so auto picks XLA for those."""
    from ray_tpu.ops import flash_attention, framestack, gae
    from ray_tpu.ops._pallas import kernel_selected

    assert kernel_selected(None, False, compiles_on_tpu=True) is True
    assert kernel_selected(None, False, compiles_on_tpu=False) is False
    assert flash_attention._COMPILES_ON_TPU is True
    assert framestack._COMPILES_ON_TPU is False
    assert gae._COMPILES_ON_TPU is False


# -- the PPO hot-path ops at their hot-path shapes ----------------------
#
# Pixel PPO (tuned_examples/ppo/ponglite*-ppo.yaml): the frame pool is
# (M, 84, 84, 1) uint8 = (M, 1764) uint32 lanes, the rebuild gathers
# R = 4 * 2048 rows of it per nest; the fused lane's GAE scans
# (16, 128) fragments on one chip and (4, 128) per shard on four.
# Mosaic refuses both kernels (the messages are quoted in
# ops/framestack.py and ops/gae.py): forcing one raises, auto runs
# the XLA path, and the XLA path is what must be right on the chip.
# When a jax release lifts a refusal the ``raises`` case fails — flip
# that kernel's _COMPILES_ON_TPU and turn the case into a parity test.

POOL_D = 84 * 84 // 4
ROWS = 2048


def _pool(rng, m):
    return rng.integers(0, 2**32, (m, POOL_D), dtype=np.uint32)


def test_row_copy_kernels_are_refused_when_forced():
    from ray_tpu.ops.framestack import gather_rows, scatter_rows

    rng = np.random.default_rng(2)
    src = jnp.asarray(_pool(rng, ROWS + 48))
    idx = jnp.asarray(rng.integers(0, ROWS + 48, 4 * ROWS), jnp.int32)
    with pytest.raises(ValueError, match="divisible by 8 and 128"):
        jax.jit(lambda s, i: gather_rows(s, i, use_pallas=True))(
            src, idx
        )
    pos = jnp.asarray(rng.permutation(ROWS + 48)[:ROWS], jnp.int32)
    with pytest.raises(ValueError, match="divisible by 8 and 128"):
        jax.jit(
            lambda r, p, v: scatter_rows(r, p, v, use_pallas=True)
        )(src, pos, src[:ROWS])


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
    """The call the learn program makes (``use_pallas=None``), on
    uint8 frames, against the host materialization."""
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


def test_gae_scan_kernel_is_refused_when_forced():
    from ray_tpu.ops.gae import compute_gae_fragment

    with pytest.raises(Exception, match="multiple of 128"):
        jax.jit(
            lambda *x: compute_gae_fragment(*x, use_pallas=True)
        )(*map(jnp.asarray, _gae_inputs((16, 128))))


@pytest.mark.parametrize("shape", [(16, 128), (4, 128)])
def test_gae_fragment_auto_hot_shape(shape):
    """Auto (the associative scan) against the sequential float32
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
    """The f64 prefix descent can never compile through Mosaic, so
    auto must not select (or probe) it: the device tree's draw runs
    the XLA body on the chip and reproduces the host tree's draw."""
    from ray_tpu.ops.segment_tree import DeviceSumTree, SumSegmentTree

    cap = 1024
    rng = np.random.default_rng(6)
    leaves = rng.random(cap) + 0.01
    host = SumSegmentTree(cap)
    host.set_items(np.arange(cap), leaves)
    dt = DeviceSumTree(cap)
    assert dt.use_pallas is None
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
