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


# the learn form's attention at the three sequence cells' sizes: a group
# of streams, tokens, key heads, query heads a key head, head, cache
# depth, window (SmallThinker's full layer and rings, Qwen3-Next, granite)
FRAGMENT_SHAPES = {
    "smallthinker_full": (4, 256, 4, 7, 128, 8192, None),
    "smallthinker_ring": (4, 256, 4, 7, 128, 4096, 4096),
    "qwen3next": (4, 128, 2, 8, 256, 2048, None),
    "granite4h": (4, 256, 8, 4, 64, 2048, None),
}


def _rows_with_a_reset(pos0, t):
    """``seg`` and ``positions`` ``(B, T)`` of streams that start at
    ``pos0``, the second with an episode reset in the fragment's middle."""
    fresh = np.zeros((pos0.shape[0], t), bool)
    fresh[1, t // 2] = True
    seg = jnp.asarray(np.cumsum(fresh, 1), jnp.int32)
    steps = np.arange(t)[None]
    opened = np.maximum.accumulate(np.where(fresh, steps, -1), axis=1)
    positions = jnp.where(
        seg == 0, pos0[:, None] + steps, steps - opened).astype(jnp.int32)
    return seg, positions


@pytest.mark.parametrize("cell", list(FRAGMENT_SHAPES))
def test_fragment_attention_on_tpu(cell, monkeypatch):
    """``cached_attention``'s fragment form takes the kernel on the
    chip by its own rule, and output and gradients agree with the XLA
    text (the rule's other branch) within bfloat16's rounding."""
    from ray_tpu.ops import flash_attention
    from ray_tpu.ops.cached_attention import cached_attention
    from ray_tpu.telemetry import metrics

    b, t, kv, group, d, depth, window = FRAGMENT_SHAPES[cell]
    h = kv * group
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    q = jax.random.normal(keys[0], (b, t, h, d), jnp.float32)
    k = jax.random.normal(keys[1], (b, t, kv, d), jnp.float32)
    v = jax.random.normal(keys[2], (b, t, kv, d), jnp.float32)
    caches = tuple(
        jax.random.normal(key, (b, depth, kv * d), jnp.bfloat16)
        for key in keys[3:5])
    w = jax.random.normal(keys[5], (b, t, h, d), jnp.float32)
    # an empty cache, one part full with a reset inside the fragment, one
    # just past a block's edge, and one deeper than a ring
    pos0 = jnp.asarray([0, depth // 4 + 3, 513, 2 * depth - 256], jnp.int32)
    if window is None:
        pos0 = jnp.minimum(pos0, depth - t)
    seg, positions = _rows_with_a_reset(pos0, t)
    rows = {"seg": seg, "positions": positions, "pos0": pos0}

    def run():
        # new functions a side: a jit of the same one would not trace again
        def attention(q, k, v):
            return cached_attention(
                q, k, v, caches, rows, scale=d ** -0.5, window=window,
                dtype=jnp.bfloat16, scope="swa" if window else "attn")[0]

        grads = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(attention(q, k, v) * w), argnums=(0, 1, 2)))
        return jax.jit(attention)(q, k, v), grads(q, k, v)

    count = lambda path: metrics.attention_fragment_lowerings().get(path, 0)
    before = count("kernel"), count("xla")
    out, grads = run()
    assert (count("kernel"), count("xla")) == (before[0] + 2, before[1])
    monkeypatch.setattr(
        flash_attention, "fragment_kernel_applies", lambda *a: False)
    want, want_grads = run()
    assert (count("kernel"), count("xla")) == (before[0] + 2, before[1] + 2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-2)
    for got, ref in zip(grads, want_grads):
        scale = float(jnp.max(jnp.abs(ref)))
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(ref, np.float32),
            atol=2e-2 * scale)


# streams, key heads, query heads a key head, head, depth: the four
# cells' full-depth layers at a few streams, and (a window as well) the
# three ring cells' window layers
STEP_SHAPES = {
    "smallthinker": (5, 4, 7, 128, 8192),
    "laguna": (5, 8, 6, 128, 4096),
    "qwen3next": (5, 2, 8, 256, 2048),
    "granite4h": (5, 8, 4, 64, 2048),
    "smallthinker_ring": (5, 4, 7, 128, 4096, 4096),
    "laguna_ring": (5, 8, 8, 128, 512, 512),
    "phi4flash_ring": (5, 10, 4, 128, 512, 512),
}


@pytest.mark.parametrize("cell", list(STEP_SHAPES))
def test_step_attention_on_tpu(cell, monkeypatch):
    """``cached_attention``'s one-token form, over a full-depth cache
    or a ring, takes the step kernel on the chip by its own rule, and
    the kernel's output agrees with the text (the rule's other branch)
    within bfloat16's rounding: an empty stream, a block's edge from
    both sides and a full cache in one batch (a ring's last stream has
    turned it three times; every slot holds noise, so a row the text's
    positions hide and the kernel's slot numbers do not would show)."""
    from ray_tpu.ops import flash_attention
    from ray_tpu.ops.cached_attention import cached_attention
    from ray_tpu.telemetry import metrics

    b, kv, group, d, depth, *window = STEP_SHAPES[cell]
    window = window[0] if window else None
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(keys[0], (b, 1, kv * group, d), jnp.float32)
    k = jax.random.normal(keys[1], (b, 1, kv, d), jnp.float32)
    v = jax.random.normal(keys[2], (b, 1, kv, d), jnp.float32)
    caches = tuple(
        jax.random.normal(key, (b, depth, kv * d), jnp.bfloat16)
        for key in keys[3:5])
    pos0 = jnp.asarray(
        [0, 510, 511, 512, 3 * depth + 17 if window else depth - 1], jnp.int32)
    rows = {"seg": jnp.zeros((b, 1), jnp.int32), "positions": pos0[:, None],
            "pos0": pos0}

    def run():
        return jax.jit(lambda q, k, v: cached_attention(
            q, k, v, caches, rows, scale=d ** -0.5, window=window,
            dtype=jnp.bfloat16, scope="swa" if window else "attn")[0])(q, k, v)

    count = lambda path: metrics.attention_step_lowerings().get(path, 0)
    before = count("kernel"), count("xla")
    out = run()
    assert (count("kernel"), count("xla")) == (before[0] + 1, before[1])
    monkeypatch.setattr(flash_attention, "step_kernel_applies", lambda *a: False)
    want = run()
    assert (count("kernel"), count("xla")) == (before[0] + 1, before[1] + 1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-2)


def test_latent_fragment_on_tpu():
    """The latent layer's fragment at the Xing4 cell's width takes the
    kernel by the rule (one key head of 576 lanes, the 32 query heads in
    four tiles), and the absorbed product's output and gradients (the
    queries, the own rows and ``W_kvb``) agree with the expanded text
    within bfloat16's rounding."""
    from ray_tpu.ops import flash_attention, latent_attention

    b, t, h, dn, rope, latent, dv, depth = 4, 128, 32, 128, 64, 512, 128, 2048
    bf = jnp.bfloat16
    assert flash_attention.fragment_kernel_applies(
        t, h, 1, latent + rope, depth, bf)
    assert flash_attention.fragment_head_tile(t, h, 1, latent + rope) == 8
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    operands = (
        jax.random.normal(keys[0], (b, t, h, dn), jnp.float32),
        jax.random.normal(keys[1], (b, t, h, rope), jnp.float32),
        jax.random.normal(keys[2], (b, t, latent + rope), bf),
        jax.random.normal(keys[3], (latent, h * (dn + dv)), jnp.float32)
        * latent ** -0.5,
    )
    cache = jax.random.normal(keys[4], (b, depth, latent + rope), bf)
    w = jax.random.normal(keys[5], (b, t, h, dv), jnp.float32)
    # an empty cache, one part full with a reset inside the fragment, one
    # just past a block's edge, a full one
    pos0 = jnp.asarray([0, depth // 4 + 3, 513, depth - t], jnp.int32)
    seg, positions = _rows_with_a_reset(pos0, t)
    scale = (dn + rope) ** -0.5

    def kernel(q_nope, q_pe, rows_new, kv_b):
        return latent_attention.absorbed_fragment(
            q_nope, q_pe, rows_new, cache, kv_b, seg, positions, pos0, scale, bf)

    def text(q_nope, q_pe, rows_new, kv_b):
        return latent_attention.expanded_fragment(
            q_nope, q_pe, rows_new, cache, kv_b, seg, pos0, scale, bf, block=2)

    def run(f):
        grads = jax.jit(jax.grad(
            lambda *a: jnp.sum(f(*a) * w), argnums=(0, 1, 2, 3)))
        return jax.jit(f)(*operands), grads(*operands)

    out, grads = run(kernel)
    want, want_grads = run(text)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-2)
    for got, ref in zip(grads, want_grads):
        scale = float(jnp.max(jnp.abs(ref.astype(jnp.float32))))
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(ref, np.float32),
            atol=2e-2 * scale)


def test_latent_step_on_tpu(monkeypatch):
    """The latent layer's one-token form at the Xing4 cell's width takes
    the step kernel by the rule (one key head of 576 lanes, the 32 query
    heads one tile, the value the block's leading 512 lanes) and agrees
    with the text (the rule's other branch) within bfloat16's rounding:
    an empty stream, a block's edge from both sides and a full cache in
    one batch; both counters say which ran."""
    from ray_tpu.ops import flash_attention, latent_attention
    from ray_tpu.telemetry import metrics

    b, h, dn, rope, latent, dv, depth = 5, 32, 128, 64, 512, 128, 2048
    bf = jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    operands = (
        jax.random.normal(keys[0], (b, 1, h, dn), jnp.float32),
        jax.random.normal(keys[1], (b, 1, h, rope), jnp.float32),
        jax.random.normal(keys[2], (b, 1, latent + rope), bf),
        jax.random.normal(keys[3], (b, depth, latent + rope), bf),
        jax.random.normal(keys[4], (latent, h * (dn + dv)), jnp.float32)
        * latent ** -0.5,
    )
    pos0 = jnp.asarray([0, 510, 511, 512, depth - 1], jnp.int32)
    rows = {"seg": jnp.zeros((b, 1), jnp.int32), "positions": pos0[:, None],
            "pos0": pos0}

    def run():
        return jax.jit(lambda *a: latent_attention.latent_attention(
            *a, rows, scale=(dn + rope) ** -0.5, dtype=bf)[:2])(*operands)

    forms = lambda: (metrics.mla_decode_lowerings().get("absorbed_kernel", 0),
                     metrics.mla_decode_lowerings().get("absorbed", 0),
                     metrics.attention_step_lowerings().get("kernel", 0))
    before = forms()
    out, cache = run()
    assert forms() == (before[0] + 1, before[1], before[2] + 1)
    monkeypatch.setattr(
        flash_attention, "step_kernel_applies", lambda *a, **value: False)
    want, want_cache = run()
    assert forms() == (before[0] + 1, before[1] + 1, before[2] + 1)
    np.testing.assert_array_equal(np.asarray(cache), np.asarray(want_cache))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-2)


def test_eva_step_on_tpu():
    """The two-store one-token kernel at the EvaByte cell's sizes (16
    streams at depths ``640 i + s``, 8 heads of 128, stores of 2,048 and
    640 rows) against the text within bfloat16's rounding, at fragment
    offsets that give every span length in both stores and both edges of
    a window; the stores' blocks outside the masks hold NaN, so a block
    fetched that the masks do not name would show."""
    from ray_tpu.ops import eva_attention

    b, h, d, window, chunk, summaries = 16, 8, 128, 2048, 16, 640
    bf = jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    q = (jax.random.normal(keys[0], (b, h, d), jnp.float32) * d ** -0.5).astype(bf)
    stores = [jax.random.normal(key, (b, rows, h * d), bf)
              for key, rows in zip(keys[1:], (window, window, summaries, summaries))]
    for s in (0, 127, 128, 511, 639):
        positions = 640 * jnp.arange(b, dtype=jnp.int32) + s
        want = eva_attention.step_text(q, stores, positions, window, chunk)
        held = eva_attention.step_blocks(*eva_attention.rows_seen(positions, window, chunk))
        poisoned = [
            jnp.where(jnp.arange(x.shape[1])[None, :, None]
                      >= eva_attention.STEP_BLOCK * n[:, None, None], jnp.nan, x)
            for x, n in zip(stores, (held[0], held[0], held[1], held[1]))]
        got = jax.jit(lambda q, *x: eva_attention.step_attention(
            q, x, positions, window=window, chunk=chunk))(q, *poisoned)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-2)


def test_selective_scan_on_tpu():
    """The fragment-form selective scan at the Phi-4-mini-flash cell's
    sizes (16 streams x 256 tokens, 16 states x 5,120 channels, a reset
    a stream but the first) as the dispatch runs it on the chip: the
    call takes the kernels, ``y`` and the state after are the text's to
    float32 rounding, and so is every cotangent."""
    from ray_tpu.ops import selective_scan
    from ray_tpu.telemetry import metrics

    b, t, n, c = 16, 256, 16, 5120
    keys = jax.random.split(jax.random.PRNGKey(0), 7)
    normal = lambda k, *shape: jax.random.normal(keys[k], shape, jnp.float32)
    at = jax.random.randint(keys[6], (b,), 0, t)
    resets = jnp.zeros((b, t), jnp.float32).at[jnp.arange(1, b), at[1:]].set(1.0)
    ops = (normal(0, b, n, c), normal(1, b, t, c),
           jax.nn.softplus(normal(2, b, t, c) - 4.0), -jnp.exp(normal(3, n, c) * 0.5),
           normal(4, b, t, n), normal(5, b, t, n), resets)

    def scalar(scan):
        def of(*ops):
            y, after = scan(*ops)
            return jnp.sum(y * y) + jnp.sum(after * after)
        return jax.jit(jax.value_and_grad(of, argnums=tuple(range(6))))

    before = metrics.selective_scan_lowerings().get("kernel", 0)
    got = scalar(selective_scan.selective_scan)(*ops)
    assert metrics.selective_scan_lowerings()["kernel"] == before + 1
    want = scalar(selective_scan._scan_text)(*ops)
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w)) < 1e-5


def test_fragment_kernel_forced_on_a_refused_shape_raises():
    """A head of 96 is neither whole lane tiles nor a part of one: the
    rule keeps such a layer on the XLA text, and the kernel called for
    it all the same raises the lowering's own message."""
    from ray_tpu.ops import flash_attention

    b, t, kv, group, d, depth = 2, 128, 2, 4, 96, 512
    assert not flash_attention.fragment_kernel_applies(
        t, kv * group, kv, d, depth, jnp.bfloat16)
    assert flash_attention.fragment_kernel_applies(
        t, kv * group, kv, 128, depth, jnp.bfloat16)
    # a query tile the backward pass cannot hold in VMEM stays on the text
    assert not flash_attention.fragment_kernel_applies(
        4096, 28, 4, 128, 8192, jnp.bfloat16)
    zeros = lambda *shape: jnp.zeros(shape, jnp.bfloat16)
    rows = jnp.zeros((b, t), jnp.int32)
    with pytest.raises(Exception, match="divisible by 8 and 128"):
        jax.jit(flash_attention.fragment_attention)(
            zeros(b, t, kv, group, d), zeros(b, t, kv, d), zeros(b, t, kv, d),
            zeros(b, depth, kv * d), zeros(b, depth, kv * d),
            jnp.zeros((b,), jnp.int32), rows, rows)


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
