"""Host key schedules as one program (``JaxPolicy._split_chain``).

docs/data_plane.md "rng split order": every lane advances the
policy's rng by sequential ``rng, r = jax.random.split(rng)`` calls in
the actor lane's order. The lanes compose that chain inside ONE jitted
program; these tests hold each schedule to its written-out host loop
bit for bit, and the standalone rollout to two dispatches.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from ray_tpu.algorithms.ppo.ppo import PPOConfig, PPOJaxPolicy
from ray_tpu.env.jax_control import CartPoleJax
from ray_tpu.execution.jax_rollout import JaxRolloutEngine
from ray_tpu.sharding import compile as compile_lib


def _policy(seed=5):
    env = CartPoleJax({"max_steps": 10})
    cfg = PPOConfig().to_dict()
    cfg.update(
        seed=seed,
        num_workers=0,
        num_envs_per_worker=8,
        train_batch_size=32,
        sgd_minibatch_size=16,
        num_sgd_iter=1,
        model={"fcnet_hiddens": [16]},
    )
    return env, PPOJaxPolicy(env.observation_space, env.action_space, cfg)


def _same_bits(got, want):
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(got, want)


# -- the written-out loops ------------------------------------------------


def _loop_rollout(rng, T):
    keys = []
    for _ in range(T):
        rng, r = jax.random.split(rng)
        keys.append(r)
    return rng, jnp.stack(keys)


def _loop_superstep(rng, k, k_max, refresh, td_rng):
    keys, pri_keys = [], []
    for _ in range(k):
        rng, r = jax.random.split(rng)
        keys.append(r)
        if refresh:
            if td_rng:
                rng, r2 = jax.random.split(rng)
            else:
                r2 = jnp.zeros_like(r)
            pri_keys.append(r2)
    pad = jnp.zeros_like(keys[0])
    keys += [pad] * (k_max - k)
    pri_keys += [pad] * (k_max - k)
    return rng, jnp.stack(keys), (jnp.stack(pri_keys) if refresh else None)


def _loop_rollout_superstep(rng, k, k_max, T):
    learn_keys, ro_keys = [], []
    for _ in range(k):
        rng, slot = _loop_rollout(rng, T)
        ro_keys.append(slot)
        rng, r = jax.random.split(rng)
        learn_keys.append(r)
    learn_keys += [jnp.zeros_like(learn_keys[0])] * (k_max - k)
    ro_keys += [jnp.zeros_like(ro_keys[0])] * (k_max - k)
    return rng, jnp.stack(learn_keys), jnp.stack(ro_keys)


# -- bit identity ----------------------------------------------------------


@pytest.mark.parametrize("T", [1, 32, 128])
def test_standalone_rollout_keys_are_the_sequential_loops(T):
    """Two rollouts in a row: the stack the rollout program is handed
    and the stream left in ``policy._rng`` are those of T sequential
    host splits, and the schedule takes no learn split."""
    env, pol = _policy()
    eng = JaxRolloutEngine(pol, env, 8, T, seed=5)
    handed = []
    dispatch = eng.rollout_from

    def capture(params, carry, ro_rngs, coeffs):
        handed.append(ro_rngs)
        return dispatch(params, carry, ro_rngs, coeffs)

    eng.rollout_from = capture
    rng = pol._rng
    for i in range(2):
        eng.rollout()
        rng, want = _loop_rollout(rng, T)
        assert handed[i].shape == (T, 2)
        _same_bits(handed[i], want)
        _same_bits(pol._rng, rng)


@pytest.mark.parametrize(
    "k,k_max,refresh,td_rng",
    [
        (1, 1, False, False),
        (3, 8, False, False),
        (8, 8, True, False),
        (3, 8, True, False),
        (8, 8, True, True),
        (3, 8, True, True),
    ],
)
def test_superstep_keys_are_the_sequential_loops(k, k_max, refresh, td_rng):
    _, pol = _policy()
    rng = pol._rng
    for _ in range(2):
        rngs, pri = pol._superstep_host_keys(k, k_max, refresh, td_rng)
        rng, want, want_pri = _loop_superstep(rng, k, k_max, refresh, td_rng)
        _same_bits(rngs, want)
        if refresh:
            _same_bits(pri, want_pri)
            # a padded slot's keys are zero, and every key of a
            # priority pass that consumes none
            assert not np.asarray(pri)[k:].any()
            assert td_rng or not np.asarray(pri).any()
        else:
            assert pri is None
        assert not np.asarray(rngs)[k:].any()
        _same_bits(pol._rng, rng)


@pytest.mark.parametrize("k,k_max,T", [(1, 1, 128), (2, 4, 8), (4, 4, 1)])
def test_rollout_superstep_keys_are_the_sequential_loops(k, k_max, T):
    _, pol = _policy()
    rng = pol._rng
    for _ in range(2):
        rngs, ro_rngs = pol._rollout_host_keys(k, k_max, T)
        rng, want, want_ro = _loop_rollout_superstep(rng, k, k_max, T)
        assert ro_rngs.shape == (k_max, T, 2)
        _same_bits(rngs, want)
        _same_bits(ro_rngs, want_ro)
        _same_bits(pol._rng, rng)


def test_lanes_share_one_stream():
    """The schedules interleave on one stream: a standalone rollout's
    T splits, then a superstep's learn splits, read as the one loop."""
    _, pol = _policy()
    rng = pol._rng
    ro = pol._rollout_keys(8)
    rngs, _ = pol._superstep_host_keys(2, 2, False, False)
    rng, want_ro = _loop_rollout(rng, 8)
    rng, want, _ = _loop_superstep(rng, 2, 2, False, False)
    _same_bits(ro, want_ro)
    _same_bits(rngs, want)
    _same_bits(pol._rng, rng)


# -- dispatch count --------------------------------------------------------


def _live_programs():
    with compile_lib._LOCK:
        return list(compile_lib._REGISTRY)


def _per_label(programs, key):
    out = {}
    for f in programs:
        out[f.label] = out.get(f.label, 0) + getattr(f, key)
    return out


def _grown(before, after):
    return {
        label: n - before.get(label, 0)
        for label, n in after.items()
        if n != before.get(label, 0)
    }


def test_standalone_rollout_is_two_dispatches():
    """After warm-up one ``rollout()`` executes exactly two programs,
    the key schedule and the rollout program, and traces nothing; an
    engine of another T builds a second chain beside the first."""
    # The counts are read from the programs THIS test makes: whatever
    # was alive before it is held to the end (so none of it is
    # collected, and no id of it reused, between two readings) and
    # never counted.
    theirs = _live_programs()
    not_ours = {id(f) for f in theirs}

    def ours(key):
        made = [f for f in _live_programs() if id(f) not in not_ours]
        return _per_label(made, key)

    env, pol = _policy()
    eng = JaxRolloutEngine(pol, env, 8, 8, seed=5)
    eng.rollout()  # warm-up: both programs trace here
    calls, traces = ours("calls"), ours("traces")
    eng.rollout()
    assert _grown(calls, ours("calls")) == {
        "rollout_keys[8]": 1,
        "jax_rollout[CartPoleJax:8x8]": 1,
    }
    assert _grown(traces, ours("traces")) == {}

    chains = pol._split_chain_fns
    first = chains[("rollout_keys", (8,), None, None)]
    assert (first.traces, first.calls) == (1, 2)
    other = JaxRolloutEngine(pol, env, 8, 16, seed=5)
    other.rollout()
    assert _grown(traces, ours("traces")) == {
        "rollout_keys[16]": 1,
        "jax_rollout[CartPoleJax:8x16]": 1,
    }
    second = chains[("rollout_keys", (16,), None, None)]
    assert second is not first and (second.traces, second.calls) == (1, 1)
    eng.rollout()
    assert (first.traces, first.calls) == (1, 3)
