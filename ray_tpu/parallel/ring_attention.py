"""Ring attention: sequence-parallel exact attention over a mesh axis.

The reference has NO sequence/context parallelism (SURVEY §5.7 — its
sequence handling stops at padded chopping, ``rnn_sequencing.py:34``); this
module is the deliberate TPU-first extension: long sequences are sharded
along time over a ("sp",) mesh axis, each device holds a Q/K/V block, and
K/V blocks rotate around the ICI ring via ``lax.ppermute`` while a
flash-attention-style online softmax accumulates exact results
(Liu et al., "Ring Attention with Blockwise Transformers", 2023 —
reimplemented from the paper's math, not ported code).

Communication pattern: n-1 ppermute hops of the local K/V block — each hop
overlaps with the local block matmul, so the MXU stays busy while ICI moves
the next block.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.ops import backend
from ray_tpu.ops.flash_attention import flash_block_attention_stats

NEG_INF = -1e30


def _block_attn(q, k, v, mask):
    """One (Tq, Tk) block: returns (unnormalized out, row max, row sum).

    q: (B, Tq, H, D), k/v: (B, Tk, H, D), mask: (Tq, Tk) bool or None.
    """
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
    scores = (
        jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    )
    if mask is not None:
        scores = jnp.where(mask[None, None], scores, NEG_INF)
    m = jnp.max(scores, axis=-1)  # (B, H, Tq)
    p = jnp.exp(scores - m[..., None])
    l = jnp.sum(p, axis=-1)  # (B, H, Tq)
    # f32 accumulation like the Pallas block kernel, so the XLA ring
    # (also the custom-VJP backward) computes the same function
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return o, m, l


def _block_attn_flash(qf, kf, vf, offset, shape, interpret):
    """The same (unnormalized out, row max, row sum) block computation
    as :func:`_block_attn`, via the fused Pallas kernel
    (``ops/flash_attention.py flash_block_attention_stats``); ``offset``
    is the runtime banded-causal bound (j <= i + offset). qf/kf/vf are
    pre-flattened (B·H, T, D) blocks — the layout transform is
    hop-invariant, so callers hoist it out of the ring scan and rotate
    the flattened K/V directly."""
    B, H = shape
    Tq, D = qf.shape[1:]
    acc, m, l = flash_block_attention_stats(
        qf, kf, vf, offset, interpret=interpret
    )
    o = acc.reshape(B, H, Tq, D).transpose(0, 2, 1, 3)
    return o, m.reshape(B, H, Tq), l.reshape(B, H, Tq)


def _merge(o1, m1, l1, o2, m2, l2):
    """Combine two partial softmax accumulators (flash-attention merge)."""
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    l = l1 * a1 + l2 * a2
    # broadcast (B,H,Tq) -> (B,Tq,H,1)
    s1 = jnp.transpose(a1, (0, 2, 1))[..., None]
    s2 = jnp.transpose(a2, (0, 2, 1))[..., None]
    o = o1 * s1 + o2 * s2
    return o, m, l


def ring_attention_local(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    axis_name: str = "sp",
    causal: bool = False,
    use_pallas: bool = False,
    interpret: bool = False,
) -> jnp.ndarray:
    """Per-shard body; call inside shard_map over the ``axis_name`` axis.

    q/k/v: (B, T_local, H, D) — this shard's sequence block. Returns the
    attention output for the local Q block, exact w.r.t. the full
    sequence. ``use_pallas`` computes each block with the fused Pallas
    kernel (runtime banded offset, since the bound depends on the
    traced device index); the XLA block math is the default and the
    differentiable path.
    """
    n = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    B, Tq, H, D = q.shape
    Tk = k.shape[1]

    q_pos = my * Tq + jnp.arange(Tq)  # global positions of local Q rows
    if use_pallas:
        # flatten once; the ring rotates the flattened K/V blocks
        q = q.transpose(0, 2, 1, 3).reshape(B * H, Tq, D)
        k = k.transpose(0, 2, 1, 3).reshape(B * H, Tk, D)
        v = v.transpose(0, 2, 1, 3).reshape(B * H, Tk, D)

    def hop(carry, step):
        o_acc, m_acc, l_acc, k_cur, v_cur = carry
        src_shard = (my - step) % n  # whose K/V block we now hold
        if use_pallas:
            # j <= i + offset ⟺ src*Tk + j <= my*Tq + i
            offset = (
                my * Tq - src_shard * Tk
                if causal
                else jnp.asarray(Tk, jnp.int32)
            )
            o, m, l = _block_attn_flash(
                q, k_cur, v_cur, offset, (B, H), interpret
            )
        else:
            if causal:
                k_pos = src_shard * Tk + jnp.arange(Tk)
                mask = k_pos[None, :] <= q_pos[:, None]
            else:
                mask = None
            o, m, l = _block_attn(q, k_cur, v_cur, mask)
        o_acc, m_acc, l_acc = _merge(o_acc, m_acc, l_acc, o, m, l)
        # rotate K/V to the next device (skip the final, unused hop
        # is harmless — keeps the scan body uniform)
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        return (o_acc, m_acc, l_acc, k_cur, v_cur), None

    o0 = jnp.zeros((B, Tq, H, D), jnp.float32)
    m0 = jnp.full((B, H, Tq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Tq), jnp.float32)
    (o, m, l, _, _), _ = jax.lax.scan(
        hop, (o0, m0, l0, k, v), jnp.arange(n)
    )
    denom = jnp.transpose(l, (0, 2, 1))[..., None]
    return (o / jnp.maximum(denom, 1e-30)).astype(q.dtype)


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    *,
    axis_name: str = "sp",
    causal: bool = False,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Full-array entry point: shards (B, T, H, D) inputs along T over
    ``axis_name`` and runs the ring. T must divide by the axis size.

    ``use_pallas=None`` auto-selects the fused block kernel on TPU
    backends and the XLA block math elsewhere. The Pallas forward is
    paired with a custom VJP that differentiates through the XLA ring
    (identical math, rematerialized), so training works either way.
    On TPU the two paths agree to MXU matmul precision (~5e-3 abs for
    f32 at T≈256 — both sit that far from a float64 reference); on CPU
    they agree to ~1e-4."""
    if use_pallas is None:
        use_pallas = interpret or backend.is_tpu()

    def run(q, k, v, pallas: bool):
        body = functools.partial(
            ring_attention_local,
            axis_name=axis_name,
            causal=causal,
            use_pallas=pallas,
            interpret=interpret,
        )
        spec = P(None, axis_name)
        fn = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            # fresh accumulators in the scan carry start axis-unvarying
            # and become varying after the first merge; skip the check
            check_vma=False,
        )
        return fn(q, k, v)

    if not use_pallas:
        return run(q, k, v, False)

    @jax.custom_vjp
    def fwd(q, k, v):
        return run(q, k, v, True)

    def fwd_rule(q, k, v):
        return run(q, k, v, True), (q, k, v)

    def bwd_rule(res, g):
        q, k, v = res
        _, vjp = jax.vjp(lambda a, b, c: run(a, b, c, False), q, k, v)
        return vjp(g)

    fwd.defvjp(fwd_rule, bwd_rule)
    return fwd(q, k, v)


def full_attention_reference(q, k, v, causal: bool = False):
    """Single-device exact attention (golden for tests)."""
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
    scores = (
        jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    )
    if causal:
        T = q.shape[1]
        mask = jnp.tril(jnp.ones((T, T), bool))
        scores = jnp.where(mask[None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
