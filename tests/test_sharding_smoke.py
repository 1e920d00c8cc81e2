"""Tier-1 smoke for the sharding runtime's public surface, so
signature drift in it fails a test instead of a driver run."""

import pytest

pytestmark = pytest.mark.smoke


def test_sharding_public_api_surface():
    """The names documented in docs/sharding.md exist and compose."""
    import jax
    import numpy as np

    from ray_tpu import sharding as sl

    mesh = sl.get_mesh()
    assert sl.BATCH_AXIS == "batch"
    rep, dat = sl.replicated(mesh), sl.batch_sharded(mesh)
    fn = sl.sharded_jit(
        lambda p, x: (p, x.sum()),
        in_specs=(rep, dat),
        out_specs=(rep, rep),
        label="smoke",
    )
    p = jax.device_put(np.float32(2.0), rep)
    x = jax.device_put(np.ones(16, np.float32), dat)
    _, s = fn(p, x)
    assert float(s) == 16.0
    assert fn.stats()["recompiles"] == 0
    assert sl.compile_stats()["functions"] >= 1
