"""The plain reference against the policy on tiny CPU shapes, and the
controls: the reference one precision step down must come out as NOT
correct under the limits of the configuration's perf/limits file."""

import numpy as np
import pytest

from perf import control as control_lib
from perf import correct as correct_lib
from perf import manifest as manifest_lib

SEEDS = (11, 2**31 + 12)


@pytest.fixture(scope="module")
def readings(tmp_path_factory):
    """The readings perf/control.py takes on the chip, on the tiny
    cell: system and both controls, two seeds, one process."""
    from perf.tests.conftest import make_tiny_root

    root = make_tiny_root(str(tmp_path_factory.mktemp("tiny")))
    cell = manifest_lib.load_cell("tiny.dqn", root)
    return cell, control_lib.readings(cell, SEEDS, require_tpu=False)


def test_system_is_within_every_limit(readings):
    cell, rows = readings
    assert cell.learner_check_shape == (128, 4)
    for row in rows:
        for name, value in row["system"].items():
            if name in cell.limits:
                assert value <= cell.limit(name), (row["seed"], name, value)


@pytest.mark.parametrize("precision", ["int8", "fp8"])
def test_control_comes_out_as_not_correct(readings, precision):
    cell, rows = readings
    assert precision in cell.control_precisions
    for row in rows:
        over = [n for n, v in row[precision].items()
                if n in cell.limits and v > cell.limit(n)]
        assert "grad_rel_l2" in over, (row["seed"], row[precision])
        assert row[precision]["grad_rel_l2"] > 3 * row["system"]["grad_rel_l2"]
        # the structure of the control's superstep is the reference's own
        assert row[precision]["superstep_rows_refreshed_wrongly"] == 0


def test_reference_updates_are_clipped_adam_by_hand():
    """``updates`` against optax's clip + Adam driven by hand on the
    reference's own loss: the optimizer arithmetic, apart from the
    program."""
    import jax
    import jax.numpy as jnp
    import optax

    from perf.reference import nature_cnn_dqn_per as ref

    config = {
        "model": {"input_shape": [20, 20, 2],
                  "conv_filters": [[4, [4, 4], [2, 2]], [4, [3, 3], [1, 1]]],
                  "dense": [8]},
        "algo_config": {"gamma": 0.99, "n_step": 1, "double_q": True, "lr": 1e-2,
                        "adam_epsilon": 1.5e-4, "grad_clip": 0.05},
    }
    params = ref.init_params(jax.random.PRNGKey(1), config, 3)
    rng = np.random.default_rng(4)
    k, rows = 3, 16
    one = [ref.make_batch(rng, config, rows, 3) for _ in range(k)]
    batches = {c: jnp.stack([b[c] for b in one]) for c in one[0]}
    out = ref.updates(params, batches, config)
    tx = optax.chain(optax.clip_by_global_norm(0.05),
                     optax.scale_by_adam(eps=1.5e-4))
    p, state = params, tx.init(params)
    for i in range(k):
        b = {c: v[i] for c, v in batches.items()}
        value, g = jax.value_and_grad(ref.loss)(p, b, config, "float32", params)
        assert optax.global_norm(g) > 0.05  # the clip bites
        u, state = tx.update(g, state, p)
        p = jax.tree_util.tree_map(lambda w, x: w - 1e-2 * x, p, u)
        np.testing.assert_allclose(out["losses"][i], value, rtol=1e-5)
        np.testing.assert_allclose(
            out["abs_td"][i], jnp.abs(ref.td_error(p, params, b, config)),
            rtol=1e-4, atol=1e-6,
        )
    for a, b in zip(jax.tree_util.tree_leaves(out["params"]),
                    jax.tree_util.tree_leaves(p)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


def test_refreshed_leaves_apply_in_update_order():
    leaves = np.array([1.0, 2.0, 3.0, 4.0])
    out = correct_lib.refreshed_leaves(
        leaves, np.array([[0, 1], [1, 3]]), np.array([[0.5, 0.25], [4.0, 0.0]]), 0.5
    )
    np.testing.assert_allclose(
        out, [np.sqrt(0.5 + 1e-6), np.sqrt(4.0 + 1e-6), 3.0, np.sqrt(1e-6)], rtol=1e-6
    )


def test_stratified_draw_reference():
    from perf.reference import nature_cnn_dqn_per as ref

    leaves = np.array([1.0, 3.0, 0.5, 0.5, 5.0])
    idx, w = ref.stratified_draw(leaves, np.array([0.0, 0.5, 0.999, 0.2]), 0.4)
    # strata of 2.5 mass each: 0 -> row 0; 3.75 -> row 1; ~7.5 -> row 4; 8.0 -> row 4
    assert idx.tolist() == [0, 1, 4, 4]
    assert w.max() <= 1.0 + 1e-6 and w[0] > w[2]
