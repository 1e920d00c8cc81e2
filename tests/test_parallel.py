"""Mesh, collective, and ring-attention tests (8-device CPU mesh)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from ray_tpu.parallel import collectives as coll
from ray_tpu.parallel.ring_attention import (
    full_attention_reference,
    ring_attention,
)
from ray_tpu.sharding import get_mesh


@pytest.fixture(scope="module")
def mesh8():
    return get_mesh(axis_shapes=[("sp", 8)])


def _smap(fn, mesh, in_specs, out_specs):
    return jax.jit(
        jax.shard_map(
            fn,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            check_vma=False,
        )
    )


def test_allreduce_psum(mesh8):
    x = np.arange(8.0, dtype=np.float32)
    fn = _smap(
        lambda x: coll.allreduce(x, "sp"), mesh8, P("sp"), P("sp")
    )
    out = np.asarray(fn(x))
    np.testing.assert_allclose(out, np.full(8, x.sum()), rtol=1e-6)


def test_allgather(mesh8):
    x = np.arange(8.0, dtype=np.float32)
    fn = _smap(
        lambda x: coll.allgather(x, "sp"), mesh8, P("sp"), P(None)
    )
    out = np.asarray(fn(x))
    # every shard gathers the full (replicated) vector
    assert out.shape == (8,)
    np.testing.assert_allclose(out, x)


def test_reducescatter(mesh8):
    x = np.tile(np.arange(8.0, dtype=np.float32), (8, 1))  # (8, 8)
    fn = _smap(
        lambda x: coll.reducescatter(x.reshape(-1), "sp"),
        mesh8,
        P("sp", None),
        P("sp"),
    )
    out = np.asarray(fn(x))
    np.testing.assert_allclose(out, np.arange(8.0) * 8.0)


def test_broadcast(mesh8):
    x = np.arange(8.0, dtype=np.float32)
    fn = _smap(
        lambda x: coll.broadcast(x, "sp", src=3), mesh8, P("sp"), P("sp")
    )
    out = np.asarray(fn(x))
    np.testing.assert_allclose(out, np.full(8, 3.0))


def test_send_recv_shift(mesh8):
    x = np.arange(8.0, dtype=np.float32)
    fn = _smap(
        lambda x: coll.send_recv_shift(x, "sp", 1),
        mesh8,
        P("sp"),
        P("sp"),
    )
    out = np.asarray(fn(x))
    np.testing.assert_allclose(out, np.roll(x, 1))


def test_host_group_allreduce():
    import ray_tpu as ray

    ray.init(ignore_reinit_error=True)

    @ray.remote
    class Holder:
        def __init__(self, v):
            self.v = np.full(4, float(v), np.float32)

        def get_v(self):
            return self.v

        def set_v(self, v):
            self.v = v
            return True

    actors = [Holder.remote(i) for i in range(3)]
    group = coll.HostGroup(actors)
    reduced = group.allreduce("get_v", "set_v", op="mean")
    np.testing.assert_allclose(reduced, np.full(4, 1.0))
    vals = group.gather("get_v")
    for v in vals:
        np.testing.assert_allclose(v, np.full(4, 1.0))


# ---------------- ring attention ----------------


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(mesh8, causal):
    rng = jax.random.PRNGKey(0)
    B, T, H, D = 2, 64, 4, 16
    kq, kk, kv = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (B, T, H, D), jnp.float32)
    k = jax.random.normal(kk, (B, T, H, D), jnp.float32)
    v = jax.random.normal(kv, (B, T, H, D), jnp.float32)

    want = np.asarray(full_attention_reference(q, k, v, causal=causal))
    got = np.asarray(
        ring_attention(q, k, v, mesh8, axis_name="sp", causal=causal)
    )
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_ring_attention_long_sequence(mesh8):
    """Sequence longer than any single shard's block."""
    rng = jax.random.PRNGKey(1)
    B, T, H, D = 1, 256, 2, 8
    kq, kk, kv = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (B, T, H, D), jnp.float32)
    k = jax.random.normal(kk, (B, T, H, D), jnp.float32)
    v = jax.random.normal(kv, (B, T, H, D), jnp.float32)
    want = np.asarray(full_attention_reference(q, k, v, causal=True))
    got = np.asarray(
        ring_attention(q, k, v, mesh8, axis_name="sp", causal=True)
    )
    np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_pallas_blocks_match_full(mesh8, causal):
    """The fused Pallas block kernel (interpret mode on CPU) inside the
    ring produces the same exact attention as the XLA block math."""
    rng = jax.random.PRNGKey(2)
    B, T, H, D = 2, 64, 2, 16
    kq, kk, kv = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (B, T, H, D), jnp.float32)
    k = jax.random.normal(kk, (B, T, H, D), jnp.float32)
    v = jax.random.normal(kv, (B, T, H, D), jnp.float32)
    want = np.asarray(full_attention_reference(q, k, v, causal=causal))
    got = np.asarray(
        ring_attention(
            q, k, v, mesh8, axis_name="sp", causal=causal,
            use_pallas=True, interpret=True,
        )
    )
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.slow  # ~17 s: pallas-vs-XLA gradient parity (moved out
# of tier-1 with PR 7, budget rule; the XLA ring-attention path and
# its numerics stay covered by the remaining tests in this file)
def test_ring_attention_pallas_gradients_match_xla(mesh8):
    """The Pallas-forward ring's custom VJP (XLA ring rematerialized)
    must match the XLA ring's gradients."""
    rng = jax.random.PRNGKey(3)
    B, T, H, D = 1, 32, 2, 8
    kq, kk, kv = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (B, T, H, D), jnp.float32)
    k = jax.random.normal(kk, (B, T, H, D), jnp.float32)
    v = jax.random.normal(kv, (B, T, H, D), jnp.float32)

    def loss(use_pallas):
        def fn(q, k, v):
            out = ring_attention(
                q, k, v, mesh8, axis_name="sp", causal=True,
                use_pallas=use_pallas, interpret=use_pallas,
            )
            return jnp.sum(out**2)

        return jax.grad(fn, argnums=(0, 1, 2))(q, k, v)

    g_pallas = loss(True)
    g_xla = loss(False)
    for a, b in zip(g_pallas, g_xla):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4
        )
