"""Flash-attention Pallas kernel vs the XLA reference (interpret mode
runs the real kernel on CPU)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.flash_attention import (
    _reference_attention,
    flash_attention,
)


@pytest.fixture(autouse=True)
def _release_compiled_programs():
    """A kernel in the Pallas interpreter is some 600 memory mappings of
    XLA:CPU code a case, which jax's caches keep, and a tier-1 worker
    that passes the kernel's ``vm.max_map_count`` of 65,530 dies of a
    segmentation fault in a LATER file's compile (PR 66:
    ``tests/test_latent_lm.py``, in three whole runs, once the ten cases
    under a choice were here). ``jax.clear_caches`` after EVERY case of
    this file, not the ten alone: ``--dist load`` deals the file's 81
    cases to all six workers, and each gives back whatever its worker
    held (21,922 mappings to 747 after 36 cases). With the fixture on
    the ten cases alone the whole run lost a worker again."""
    yield
    jax.clear_caches()


def _qkv(rng, B=2, H=2, T=24, S=40, D=16, dtype=jnp.float32):
    q = jnp.asarray(rng.standard_normal((B, H, T, D)), dtype)
    k = jnp.asarray(rng.standard_normal((B, H, S, D)), dtype)
    v = jnp.asarray(rng.standard_normal((B, H, S, D)), dtype)
    return q, k, v


@pytest.mark.parametrize(
    "T,S,offset",
    [
        (24, 40, None),  # full attention, uneven non-multiple shapes
        (24, 40, 16),    # GTrXL band: memory_len offset
        (32, 32, 0),     # plain causal self-attention
        (130, 200, 7),   # spills over the 128 block size
    ],
)
def test_kernel_matches_reference(T, S, offset):
    rng = np.random.default_rng(0)
    q, k, v = _qkv(rng, T=T, S=S)
    out = flash_attention(
        q, k, v, causal_offset=offset, interpret=True
    )
    ref = flash_attention(
        q, k, v, causal_offset=offset, use_pallas=False
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_rows_with_no_valid_keys_are_zero_in_both_paths():
    # offset -3: queries 0..2 have no valid keys; the op defines those
    # rows as ZERO in both the kernel and the XLA reference (which is
    # also the backward pass), so forward and vjp agree
    rng = np.random.default_rng(1)
    q, k, v = _qkv(rng, T=8, S=8)
    out = flash_attention(q, k, v, causal_offset=-3, interpret=True)
    ref = flash_attention(q, k, v, causal_offset=-3, use_pallas=False)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )
    np.testing.assert_allclose(np.asarray(out[:, :, :3]), 0.0)
    assert np.abs(np.asarray(out[:, :, 3:])).max() > 0


def test_gradients_flow_and_match_reference():
    rng = np.random.default_rng(2)
    q, k, v = _qkv(rng, T=16, S=16, D=8)

    def loss_kernel(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal_offset=0, interpret=True)
            ** 2
        )

    def loss_ref(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal_offset=0, use_pallas=False)
            ** 2
        )

    g_kernel = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_kernel, g_ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4
        )


def test_bf16_inputs():
    rng = np.random.default_rng(3)
    q, k, v = _qkv(rng, T=16, S=16, dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, interpret=True)
    ref = _reference_attention(
        q.reshape(4, 16, 16), k.reshape(4, 16, 16),
        v.reshape(4, 16, 16), None,
    ).reshape(2, 2, 16, 16)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=3e-2, rtol=3e-2,
    )


# -- the fragment kernel against ``cached_attention``'s XLA text -----------

def _fragment(b=3, t=16, kv=2, group=4, d=128, depth=32, window=None,
              pos0=(0, 10, 32), resets=((), (5,), ()), dtype=jnp.float32,
              seed=0):
    """A fragment of ``b`` streams at ``pos0`` with episode resets at
    ``resets``: its operands as the model hands them over, and the rows
    the lane derives from the resets."""
    rng = np.random.default_rng(seed)
    h = kv * group
    normal = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    q, k, v = normal(b, t, h, d), normal(b, t, kv, d), normal(b, t, kv, d)
    caches = tuple(normal(b, depth, kv * d).astype(dtype) for _ in range(2))
    fresh = np.zeros((b, t), bool)
    for i, at in enumerate(resets):
        fresh[i, list(at)] = True
    seg = jnp.asarray(np.cumsum(fresh, 1), jnp.int32)
    steps = np.arange(t)[None]
    opened = np.maximum.accumulate(np.where(fresh, steps, -1), axis=1)
    pos0 = jnp.asarray(pos0, jnp.int32)
    positions = jnp.where(
        seg == 0, pos0[:, None] + steps, steps - opened).astype(jnp.int32)
    rows = {"seg": seg, "positions": positions, "pos0": pos0}
    return (q, k, v) + caches, rows, normal(b, t, h, d)


def _text_and_kernel(rows, kv, window, dtype, block_k):
    """``(q, k, v, k_cache, v_cache) -> o (B, T, heads, D)`` twice: the
    model's XLA text (the rule's branch off a TPU) and the kernel in the
    interpreter."""
    from ray_tpu.ops.cached_attention import cached_attention
    from ray_tpu.ops.flash_attention import fragment_attention

    def text(q, k, v, kc, vc):
        return cached_attention(
            q, k, v, (kc, vc), rows, scale=q.shape[-1] ** -0.5, window=window,
            dtype=dtype, scope="swa" if window else "attn")[0]

    def kernel(q, k, v, kc, vc):
        b, t, h, d = q.shape
        qh = (q * d ** -0.5).astype(dtype).reshape(b, t, kv, h // kv, d)
        return fragment_attention(
            qh, k.astype(dtype), v.astype(dtype), kc, vc, rows["pos0"],
            rows["seg"], rows["positions"], window=window, block_k=block_k,
            interpret=True).reshape(b, t, h, d)

    return text, kernel


def _latent_fragment(b=3, t=16, heads=4, dn=16, rope=16, latent=128, dv=16,
                     depth=32, pos0=(0, 10, 32), resets=((), (5,), ()),
                     dtype=jnp.float32, seed=0, **_):
    """The latent layer's fragment: ``(q_nope, q_pe, rows_new, kv_b,
    cache)`` as the latent layer hands them over (a row of
    ``latent + rope`` lanes: not whole lane tiles, the value its leading
    ``latent``), the lane's rows, and a cotangent."""
    _, rows, _ = _fragment(b=b, t=t, depth=depth, pos0=pos0, resets=resets)
    rng = np.random.default_rng(seed)
    normal = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    operands = (
        normal(b, t, heads, dn), normal(b, t, heads, rope),
        normal(b, t, latent + rope).astype(dtype),
        normal(latent, heads * (dn + dv)) * latent ** -0.5,
        normal(b, depth, latent + rope).astype(dtype))
    return operands, rows, normal(b, t, heads, dv)


def _latent_text_and_kernel(rows, dtype, block_k, head_tile):
    """``(q_nope, q_pe, rows_new, kv_b, cache) -> o (B, T, heads, dv)``
    twice: ``expanded_fragment``'s text (every key rebuilt through
    ``W_kvb``, the masked score matrix) and the absorbed product on the
    kernel in the interpreter."""
    from ray_tpu.ops import latent_attention

    scale = 0.1

    def text(q_nope, q_pe, rows_new, kv_b, cache):
        return latent_attention.expanded_fragment(
            q_nope, q_pe, rows_new, cache, kv_b, rows["seg"], rows["pos0"],
            scale, dtype, block=2)

    def kernel(q_nope, q_pe, rows_new, kv_b, cache):
        return latent_attention.absorbed_fragment(
            q_nope, q_pe, rows_new, cache, kv_b, rows["seg"], rows["positions"],
            rows["pos0"], scale, dtype, block_k=block_k, head_tile=head_tile,
            interpret=True)

    return text, kernel


_FRAGMENT_CASES = {
    # (i) no window: an empty cache, one part full with an episode reset
    # inside the fragment, a full one; blocks of 16 keys, so the streams
    # skip two, one and no stored block
    "full_depths_and_a_reset": dict(),
    "full_reset_at_the_first_token": dict(resets=((0,), (5, 9), ())),
    # (ii) rings of 24 slots under a window of 24: not yet turned, just
    # at the window, turned (positions 30, 47 and 100 deep)
    "ring_not_turned": dict(window=24, depth=24, pos0=(0, 10, 23), block_k=8),
    "ring_at_the_window": dict(
        window=24, depth=24, pos0=(24, 24, 25), resets=((), (7,), ()), block_k=8),
    "ring_turned": dict(window=24, depth=24, pos0=(30, 100, 47), block_k=8),
    "ring_shorter_than_the_window": dict(
        window=40, depth=32, pos0=(0, 20, 32), block_k=16),
    # (iii) the three cells' heads and groups
    "head_64_group_4": dict(d=64, kv=4, group=4),
    "head_128_group_7": dict(d=128, kv=2, group=7),
    "head_256_group_8": dict(d=256, kv=1, group=8),
    "head_64_group_4_ring": dict(
        d=64, kv=2, group=4, window=24, depth=24, pos0=(3, 100, 24), block_k=8),
    "bfloat16": dict(dtype=jnp.bfloat16, group=7),
    # (iv) the mixed-geometry cell's two layers at its fragment of 256
    # and the rule's own key blocks of 512: six query heads a key head
    # over a cache of 4,096 (streams 700 and 3,840 deep: six and no
    # stored blocks skipped), eight over a ring of 512 that is one key
    # block, narrower than two fragments, not yet turned and turned
    "group_6_cache_4096": dict(
        b=2, t=256, kv=1, group=6, depth=4096, pos0=(700, 3840),
        resets=((), (100,)), block_k=None),
    "group_8_ring_512": dict(
        b=2, t=256, kv=1, group=8, window=512, depth=512, pos0=(300, 3840),
        resets=((), (100,)), block_k=None),
    # (v) the latent layer: ONE key head of 144 lanes (no whole lane
    # tiles) whose leading 128 are its value, the absorbed product
    # against ``expanded_fragment``'s text; the group of four query heads
    # in two tiles and in four, streams empty, part full and full, a
    # reset inside the fragment and one at its first token
    "latent_two_head_tiles": dict(latent=True, head_tile=2),
    "latent_four_head_tiles_reset_at_the_first_token": dict(
        latent=True, head_tile=1, resets=((0,), (5, 9), ())),
    "latent_one_head_tile": dict(latent=True, head_tile=4),
    "latent_bfloat16": dict(latent=True, head_tile=2, dtype=jnp.bfloat16),
}
_CONTRACT_CASES = {
    "cache_cotangents_are_zeros": dict(),
    "a_skipped_block_changes_nothing": dict(pos0=(0, 10, 16)),
    "latent_cache_cotangents_are_zeros": dict(latent=True, head_tile=2),
    "latent_a_skipped_block_changes_nothing": dict(
        latent=True, head_tile=2, pos0=(0, 10, 16)),
}


@pytest.mark.parametrize("name", list(_FRAGMENT_CASES) + list(_CONTRACT_CASES))
def test_fragment_kernel(name):
    case = dict({**_FRAGMENT_CASES, **_CONTRACT_CASES}[name])
    block_k = case.pop("block_k", 16)
    dtype = case.get("dtype", jnp.float32)
    latent = case.pop("latent", False)
    if latent:
        operands, rows, w = _latent_fragment(**case)
        text, kernel = _latent_text_and_kernel(
            rows, dtype, block_k, case["head_tile"])
    else:
        operands, rows, w = _fragment(**case)
        text, kernel = _text_and_kernel(
            rows, case.get("kv", 2), case.get("window"), dtype, block_k)
    # the stored rows come last; everything before them is differentiated
    learned = tuple(range(4 if latent else 3))
    stored = tuple(range(len(learned), len(operands)))
    loss = lambda f: lambda *a: jnp.sum(f(*a) * w)
    if name.endswith("cache_cotangents_are_zeros"):
        # the stored rows are the rollout's: the text would hand them a
        # gradient, the kernel by its contract hands them none
        got = jax.grad(loss(kernel), argnums=stored)(*operands)
        want = jax.grad(loss(text), argnums=stored)(*operands)
        assert all(float(jnp.max(jnp.abs(g))) == 0.0 for g in got)
        assert all(float(jnp.max(jnp.abs(g))) > 0.1 for g in want)
        return
    if name.endswith("a_skipped_block_changes_nothing"):
        # streams at 0, 10 and 16 of 32 slots: the second block of 16 is
        # skipped for all three, so a cache cut to the first block, or
        # one whose second block holds other rows, gives the same bits
        both = jax.value_and_grad(loss(kernel), argnums=learned)
        want = both(*operands)
        fresh, caches = operands[:len(learned)], operands[len(learned):]
        cut = both(*fresh, *(c[:, :16] for c in caches))
        other = both(*fresh, *(
            c.at[:, 16:].set(7.0 - 14.0 * n) for n, c in enumerate(caches)))
        for got in (cut, other):
            for a, b in zip(jax.tree_util.tree_leaves(got),
                            jax.tree_util.tree_leaves(want)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        return
    tol = dict(atol=1e-4, rtol=1e-4) if dtype == jnp.float32 else dict(
        atol=0.15, rtol=5e-2)
    np.testing.assert_allclose(
        np.asarray(kernel(*operands)), np.asarray(text(*operands)), **tol)
    got = jax.grad(loss(kernel), argnums=learned)(*operands)
    want = jax.grad(loss(text), argnums=learned)(*operands)
    for a, b in zip(got, want):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), **tol)


_BLOCK_CASES = {
    # a clean pass under the block-causal rule, and a noisy pass whose
    # own keys are the clean pass's rows and then its own: streams empty,
    # part full and full, an episode reset on a block's first token
    "block_rule": dict(t=16, depth=32, pos0=(0, 8, 16), resets=((), (4,), ())),
    "noisy_pass": dict(
        t=16, depth=32, pos0=(0, 8, 16), resets=((), (4,), ()), noisy=True),
    "noisy_pass_bfloat16": dict(
        t=16, depth=32, pos0=(0, 8, 16), resets=((), (4,), ()), noisy=True,
        dtype=jnp.bfloat16),
    # the block-diffusion cell's layer at its fragment of 256 and the
    # rule's own key blocks of 512: eight query heads a key head over a
    # cache of 4,096, 512 own keys in the noisy pass
    "block_rule_cache_4096": dict(
        b=2, t=256, kv=1, group=8, depth=4096, pos0=(700, 3840),
        resets=((), (100,)), block_k=None),
    "noisy_pass_cache_4096": dict(
        b=2, t=256, kv=1, group=8, depth=4096, pos0=(700, 3840),
        resets=((), (100,)), block_k=None, noisy=True),
}


@pytest.mark.parametrize("name", list(_BLOCK_CASES))
def test_fragment_kernel_block_rules(name):
    """The kernel's block rule and its two own-key blocks against
    ``cached_attention``'s XLA text (``fragment_masks`` with a block,
    ``noisy_masks``), forward and every gradient, the clean rows'
    included."""
    from ray_tpu.ops.cached_attention import cached_attention
    from ray_tpu.ops.flash_attention import fragment_attention

    case = dict(_BLOCK_CASES[name])
    block_k, noisy = case.pop("block_k", 16), case.pop("noisy", False)
    dtype, kv, block = case.get("dtype", jnp.float32), case.get("kv", 2), 4
    (q, k, v, kc, vc), rows, w = _fragment(**case)
    rng = np.random.default_rng(9)
    clean = tuple(
        jnp.asarray(rng.standard_normal(k.shape), jnp.float32) for _ in range(2))

    def text(q, k, v, ck, cv):
        r = dict(rows, clean=(ck, cv)) if noisy else rows
        return cached_attention(
            q, k, v, (kc, vc), r, scale=q.shape[-1] ** -0.5, window=None,
            dtype=dtype, scope="attn", block=block)[0]

    def kernel(q, k, v, ck, cv):
        b, t, h, d = q.shape
        qh = (q * d ** -0.5).astype(dtype).reshape(b, t, kv, h // kv, d)
        return fragment_attention(
            qh, k.astype(dtype), v.astype(dtype), kc, vc, rows["pos0"],
            rows["seg"], rows["positions"], block=block,
            clean=(ck.astype(dtype), cv.astype(dtype)) if noisy else None,
            block_k=block_k, interpret=True).reshape(b, t, h, d)

    tol = dict(atol=1e-4, rtol=1e-4) if dtype == jnp.float32 else dict(
        atol=0.15, rtol=5e-2)
    operands = (q, k, v) + clean
    np.testing.assert_allclose(
        np.asarray(kernel(*operands)), np.asarray(text(*operands)), **tol)
    loss = lambda f: lambda *a: jnp.sum(f(*a) * w)
    learned = (0, 1, 2, 3, 4) if noisy else (0, 1, 2)
    got = jax.grad(loss(kernel), argnums=learned)(*operands)
    want = jax.grad(loss(text), argnums=learned)(*operands)
    for a, b in zip(got, want):
        assert float(jnp.max(jnp.abs(b))) > 0.1
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), **tol)


_CHOSEN_CASES = {
    # streams empty, part full and full (blocks of 16 keys: two, one and
    # no stored block skipped), an episode reset inside the fragment
    "depths_and_a_reset": dict(),
    "reset_at_the_first_token": dict(resets=((0,), (5, 9), ())),
    # the cell's group of eight query heads a key head, and a packed one
    "group_8": dict(kv=1, group=8),
    # its eight query heads in two tiles, as a tighter VMEM would cut
    # them: the own keys' gradients summed over the tiles
    "group_8_in_two_head_tiles": dict(kv=1, group=8, head_tile=4),
    "head_64_group_4": dict(d=64, kv=4, group=4),
    "bfloat16": dict(dtype=jnp.bfloat16),
    # the rule's own key blocks of 512 at the cell's fragment of 256
    "group_8_cache_2048": dict(
        b=2, t=256, kv=1, group=8, depth=2048, pos0=(700, 1800),
        resets=((), (100,)), block_k=None),
    # what the operand must leave alone
    "cache_cotangents_are_zeros": dict(),
    "a_skipped_block_changes_nothing": dict(pos0=(0, 10, 16)),
    "all_seen_is_the_call_without_a_choice": dict(),
}


def _choice(rows, depth, seed=1):
    """A choice ``(B, T, depth + T)`` over a fragment's rows: half the
    pairs at random (seen or not: the kernel's masks stay on), stream
    1's fourth query with NO chosen stored row, stream 2's eighth with no
    chosen own row, and every query with at least one row it sees."""
    from ray_tpu.ops.cached_attention import fragment_masks

    b, t = rows["seg"].shape
    seen = np.concatenate([np.asarray(m) for m in fragment_masks(
        rows["seg"], rows["pos0"], None, depth, None)], axis=-1)
    chosen = np.random.default_rng(seed).random((b, t, depth + t)) < 0.5
    chosen[1 % b, 3, :depth] = False
    chosen[2 % b, 7, depth:] = False
    none = ~(chosen & seen).any(-1)
    own = depth + np.arange(t)
    chosen[:, np.arange(t), own] |= none  # a query sees its own key
    return jnp.asarray(chosen), jnp.asarray(seen)


@pytest.mark.parametrize("name", list(_CHOSEN_CASES))
def test_fragment_kernel_under_a_choice(name):
    """The kernel pair with a learned index's choice as one more operand
    (``fragment_attention(chosen=)``) against the text under the same
    choice (``cached_attention._selected_text``): ``o`` and the
    gradients of ``q``, ``k``, ``v``; the stored rows get none, a stored
    block at or past the start position is neither read nor is its part
    of the choice, and a choice of every row seen gives the bits of the
    call without one."""
    from ray_tpu.ops import cached_attention
    from ray_tpu.ops.flash_attention import fragment_attention

    case = dict(_CHOSEN_CASES[name])
    block_k, head_tile = case.pop("block_k", 16), case.pop("head_tile", None)
    dtype, kv = case.get("dtype", jnp.float32), case.get("kv", 2)
    (q, k, v, kc, vc), rows, w = _fragment(**case)
    depth = kc.shape[1]
    chosen, seen = _choice(rows, depth)

    def heads_of(q):
        b, t, h, d = q.shape
        return (q * d ** -0.5).astype(dtype).reshape(b, t, kv, h // kv, d)

    def kernel(q, k, v, kc, vc, chosen=chosen):
        stored = kc.shape[1]  # the kernels take the choice in two parts
        return fragment_attention(
            heads_of(q), k.astype(dtype), v.astype(dtype), kc, vc, rows["pos0"],
            rows["seg"], rows["positions"], block_k=block_k, head_tile=head_tile,
            chosen=None if chosen is None else (
                chosen[..., :stored], chosen[..., stored:]),
            interpret=True).reshape(q.shape)

    def text(q, k, v, kc, vc):
        return cached_attention._selected_text(
            q.shape[2], heads_of(q), k.astype(dtype), v.astype(dtype), kc, vc,
            chosen & seen, jax.named_scope).reshape(q.shape)

    operands = (q, k, v, kc, vc)
    loss = lambda f: lambda *a: jnp.sum(f(*a) * w)
    if name == "cache_cotangents_are_zeros":
        got = jax.grad(loss(kernel), argnums=(3, 4))(*operands)
        want = jax.grad(loss(text), argnums=(3, 4))(*operands)
        assert all(float(jnp.max(jnp.abs(g))) == 0.0 for g in got)
        assert all(float(jnp.max(jnp.abs(g))) > 0.1 for g in want)
        return
    under = lambda mask: jax.value_and_grad(
        lambda *a: jnp.sum(kernel(*a, chosen=mask) * w), argnums=(0, 1, 2))
    if name == "a_skipped_block_changes_nothing":
        # streams at 0, 10 and 16 of 32 slots: the second block of 16 is
        # skipped for all three, so caches and a choice cut to the first
        # block, or with other rows and another choice in the second,
        # give the same bits
        want = under(chosen)(*operands)
        cut = under(jnp.concatenate(
            [chosen[..., :16], chosen[..., depth:]], axis=-1))(
                q, k, v, kc[:, :16], vc[:, :16])
        other = under(chosen.at[..., 16:depth].set(~chosen[..., 16:depth]))(
            q, k, v, kc.at[:, 16:].set(7.0), vc.at[:, 16:].set(-7.0))
        for got in (cut, other):
            for a, b in zip(jax.tree_util.tree_leaves(got),
                            jax.tree_util.tree_leaves(want)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        return
    if name == "all_seen_is_the_call_without_a_choice":
        none = under(None)(*operands)
        for mask in (seen, jnp.ones_like(seen)):
            for a, b in zip(jax.tree_util.tree_leaves(under(mask)(*operands)),
                            jax.tree_util.tree_leaves(none)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        return
    tol = dict(atol=1e-4, rtol=1e-4) if dtype == jnp.float32 else dict(
        atol=0.15, rtol=5e-2)
    np.testing.assert_allclose(
        np.asarray(kernel(*operands)), np.asarray(text(*operands)), **tol)
    got = under(chosen)(*operands)[1]
    want = jax.grad(loss(text), argnums=(0, 1, 2))(*operands)
    for a, b in zip(got, want):
        assert float(jnp.max(jnp.abs(b))) > 0.1
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), **tol)


def test_fragment_rule_blocks_and_pairs():
    from ray_tpu.ops import cached_attention, flash_attention as fa

    # off a TPU the rule says XLA, whatever the shape
    assert not fa.fragment_kernel_applies(256, 28, 4, 128, 8192, jnp.bfloat16)
    assert fa.fragment_block_k(8192) == 512
    # the mixed-geometry cell's layers: eight stored blocks, and one
    assert fa.fragment_block_k(4096) == fa.fragment_block_k(512) == 512
    assert fa.fragment_block_k(2048 + 256) == 256
    assert fa.fragment_block_k(24) == 0
    # 16 stored blocks of 512 and the own: a stream at 0 skips all 16, one
    # at 513 skips 14, one past the cache none
    skipped, walked = fa.fragment_key_blocks(
        jnp.asarray([0, 513, 9000], jnp.int32), 8192)
    assert (int(skipped), walked) == (30, 51)
    # the pairs a window layer sees, against the text's own count
    _, rows, _ = _fragment(window=24, depth=24, pos0=(3, 100, 24),
                           resets=((), (7,), ()))
    slots, steps = np.arange(24), np.arange(16)
    want = 0
    for n in range(3):
        last = int(rows["pos0"][n]) - 1
        held = last - (last - slots) % 24
        seg, pos = np.asarray(rows["seg"][n]), np.asarray(rows["positions"][n])
        want += np.sum((seg == 0)[:, None] & (held >= 0)[None]
                       & (pos[:, None] - held[None] < 24))
        want += np.sum((steps[:, None] >= steps[None]) & (seg[:, None] == seg[None])
                       & (steps[:, None] - steps[None] < 24))
    got = cached_attention.pairs_seen(*cached_attention.fragment_masks(
        rows["seg"], rows["pos0"], rows["positions"], 24, 24))
    assert float(got.sum()) == float(want)


@pytest.mark.parametrize("tokens,heads,kv,head,depth,tile", [
    (256, 28, 4, 128, 8192, 7),   # SmallThinker's full layer
    (256, 28, 4, 128, 4096, 7),   # its rings
    (128, 16, 2, 256, 2048, 8),   # Qwen3-Next's gated layer
    (256, 32, 8, 64, 2048, 8),    # Granite's: two key heads of four a block
    (256, 48, 8, 128, 4096, 6),   # Laguna's full layers: 6 x 256 query rows a key head
    (256, 64, 8, 128, 512, 8),    # its rings: 8 x 256, one key block
    # Xing4's latent rows: one key head of 576 lanes for 32 query heads,
    # whose 4,096 rows the backward pass cannot hold: four tiles of 8
    (128, 32, 1, 576, 2048, 8),
])
def test_fragment_rule_admits_the_cells_layers_on_a_tpu(
        monkeypatch, tokens, heads, kv, head, depth, tile):
    """What the rule says where the backend is a TPU, from the shapes
    alone: every sequence cell's attention layer takes the kernel, in
    bfloat16 only, the softmax kinds with a key block's whole group of
    query heads in one tile, and a fragment of 4,096 tokens does not."""
    from ray_tpu.ops import flash_attention as fa

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert fa.fragment_kernel_applies(tokens, heads, kv, head, depth, jnp.bfloat16)
    assert fa.fragment_head_tile(tokens, heads, kv, head) == tile
    assert not fa.fragment_kernel_applies(tokens, heads, kv, head, depth, jnp.float32)
    assert not fa.fragment_kernel_applies(4096, heads, kv, head, depth, jnp.bfloat16)
    assert not fa.fragment_kernel_applies(
        tokens, heads, kv, head, depth + 24, jnp.bfloat16)


# -- the step kernel against ``cached_attention``'s one-token text ---------

def _step(b=5, kv=2, group=4, d=128, depth=2048, pos0=(0, 510, 511, 512, 2047),
          dtype=jnp.bfloat16, seed=0):
    """One token of ``b`` streams at ``pos0``: the operands as the model
    hands them over and the rows the lane derives."""
    rng = np.random.default_rng(seed)
    normal = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    q, k, v = normal(b, 1, kv * group, d), normal(b, 1, kv, d), normal(b, 1, kv, d)
    caches = tuple(normal(b, depth, kv * d).astype(dtype) for _ in range(2))
    pos0 = jnp.asarray(pos0, jnp.int32)
    rows = {"seg": jnp.zeros((b, 1), jnp.int32), "positions": pos0[:, None],
            "pos0": pos0}
    return (q, k, v) + caches, rows, normal(b, 1, kv * group, d)


def _step_text_and_kernel(rows, kv, dtype, block_k, monkeypatch, gate=False,
                          window=None):
    """``(q, k, v, k_cache, v_cache) -> o (B, 1, heads, D)`` twice
    through ``ops/cached_attention``: its XLA text (the rule's
    branch off a TPU) and, the rule forced, the kernel in the
    interpreter; with ``gate`` a sigmoid gate of the query after it, as
    a gated layer applies one; with a ``window`` the caches are rings
    and the text's mask comes from the positions their slots hold."""
    from ray_tpu.ops import flash_attention as fa
    from ray_tpu.ops.cached_attention import cached_attention

    def text(q, k, v, kc, vc):
        o = cached_attention(
            q, k, v, (kc, vc), rows, scale=q.shape[-1] ** -0.5, window=window,
            dtype=dtype, scope="attn")[0]
        return o * jax.nn.sigmoid(q) if gate else o

    def kernel(*operands):
        with monkeypatch.context() as patch:
            patch.setattr(fa, "step_kernel_applies", lambda *a: True)
            patch.setattr(fa, "step_attention", functools.partial(
                fa.step_attention, block_k=block_k, interpret=True))
            return text(*operands)

    return text, kernel


_STEP_CASES = {
    # the four geometries that run it, each with a stream at depth 0
    # beside a full one and rows held of 1, 511, 512, 513 and the full
    # depth in one batch (key blocks of 512: one to four held)
    "heads_28_kv_4_d_128": dict(kv=4, group=7),
    "heads_48_kv_8_d_128_gated": dict(kv=8, group=6, gate=True),
    "heads_16_kv_2_d_256": dict(kv=2, group=8, d=256),
    "heads_32_kv_8_d_64_packed": dict(kv=8, group=4, d=64),
    # a cache of one key block; small blocks, float32: the arithmetic alone
    "one_block": dict(depth=512, pos0=(0, 5, 510, 511, 300)),
    "float32_blocks_of_16": dict(
        depth=64, pos0=(0, 15, 16, 17, 63), block_k=16, dtype=jnp.float32),
    # past the cache's end the scatter drops the key and every slot is seen
    "a_stream_past_the_depth": dict(depth=1024, pos0=(0, 1023, 1024, 2000, 512)),
}
# a ring of ``depth`` slots (every slot filled with noise, as a finished
# episode leaves them): the kernel reads the ``min(pos0 + 1, depth)``
# leading slots, the text the slots whose POSITION is inside the window
_STEP_RING_CASES = {
    # key blocks of 512: one held, two by a row, all; a stream in its
    # first turn's last step beside one a step into its second
    "ring_before_the_first_turn": dict(
        kv=4, group=7, depth=2048, window=2048, pos0=(0, 5, 511, 512, 1500)),
    "ring_at_the_first_turn": dict(
        depth=1024, window=1024, pos0=(1021, 1022, 1023, 1024, 1025)),
    "ring_wrapped_several_times": dict(
        depth=512, window=512, pos0=(512, 1023, 1024, 3 * 512 + 17, 8191)),
    # position 0 again over the rows of the episode before, and a
    # neighbour deep in its own
    "ring_just_reset_over_old_rows": dict(
        depth=64, window=64, pos0=(0, 1, 0, 200, 15), block_k=16,
        dtype=jnp.float32),
    # an episode shorter than the window: the ring is the episode
    "ring_depth_below_the_window": dict(
        depth=64, window=100, pos0=(0, 15, 16, 62, 63), block_k=16,
        dtype=jnp.float32),
    "ring_heads_40_kv_20_d_64_packed": dict(
        kv=20, group=2, d=64, depth=512, window=512, pos0=(0, 63, 511, 512, 4000)),
}
_STEP_CONTRACT_CASES = {
    "gradient_is_the_texts": dict(
        depth=64, pos0=(0, 15, 16, 17, 63), block_k=16, dtype=jnp.float32),
    "a_skipped_block_changes_nothing": dict(
        depth=64, pos0=(0, 15, 20, 31, 7), block_k=16, dtype=jnp.float32),
    "ring_gradient_is_the_texts": dict(
        depth=64, window=64, pos0=(0, 15, 63, 64, 150), block_k=16,
        dtype=jnp.float32),
    # no ring has written past slot 31 yet
    "ring_a_skipped_block_changes_nothing": dict(
        depth=64, window=64, pos0=(0, 15, 20, 31, 7), block_k=16,
        dtype=jnp.float32),
}


@pytest.mark.parametrize(
    "name", list(_STEP_CASES) + list(_STEP_RING_CASES) + list(_STEP_CONTRACT_CASES))
def test_step_kernel(name, monkeypatch):
    case = dict({**_STEP_CASES, **_STEP_RING_CASES, **_STEP_CONTRACT_CASES}[name])
    block_k, gate = case.pop("block_k", None), case.pop("gate", False)
    window = case.pop("window", None)
    dtype = case.get("dtype", jnp.bfloat16)
    operands, rows, w = _step(**case)
    text, kernel = _step_text_and_kernel(
        rows, case.get("kv", 2), dtype, block_k, monkeypatch, gate, window)
    loss = lambda f: lambda *a: jnp.sum(f(*a) * w)
    if name.endswith("a_skipped_block_changes_nothing"):
        # no stream is deeper than 32 of 64 slots: the last two blocks of
        # 16 are skipped for all, so other rows there give the same bits
        q, k, v, *caches = operands
        other = (c.at[:, 33:].set(7.0 - 14.0 * n) for n, c in enumerate(caches))
        np.testing.assert_array_equal(
            np.asarray(kernel(q, k, v, *other)), np.asarray(kernel(*operands)))
        return
    tol = dict(atol=1e-4, rtol=1e-4) if dtype == jnp.float32 else dict(
        atol=2e-2, rtol=2e-2)
    np.testing.assert_allclose(
        np.asarray(kernel(*operands)), np.asarray(text(*operands)), **tol)
    if name.endswith("gradient_is_the_texts"):
        # rollout takes none; a differentiated call gets the text's, in
        # the query, the own key and value and the stored rows
        got = jax.grad(loss(kernel), argnums=range(5))(*operands)
        want = jax.grad(loss(text), argnums=range(5))(*operands)
        for a, b in zip(got, want):
            assert float(jnp.max(jnp.abs(b))) > 1e-3
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), **tol)


def _latent_step(b=5, heads=4, dn=16, rope=16, latent=128, dv=24, depth=64,
                 pos0=(0, 15, 16, 17, 63), dtype=jnp.bfloat16, seed=0):
    """One token of ``b`` streams at ``pos0`` as the latent layer hands
    it to ``absorbed_step``: ``(q_nope, q_pe, cache, kv_b)`` (a row of
    ``latent + rope`` lanes: one key head, no whole lane tiles, the
    value its leading ``latent``; the step's own row already in the
    cache) and a cotangent."""
    rng = np.random.default_rng(seed)
    normal = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    operands = (
        normal(b, heads, dn), normal(b, heads, rope),
        normal(b, depth, latent + rope).astype(dtype),
        normal(latent, heads * (dn + dv)) * latent ** -0.5)
    return operands, jnp.asarray(pos0, jnp.int32), normal(b, heads, dv)


def _latent_step_text_and_kernel(pos0, dtype, block_k):
    """``(q_nope, q_pe, cache, kv_b) -> o (B, heads, dv)`` twice:
    ``absorbed_step``'s text and the same on the step kernel in the
    interpreter."""
    from ray_tpu.ops import latent_attention

    def text(q_nope, q_pe, cache, kv_b, **kernel):
        return latent_attention.absorbed_step(
            q_nope, q_pe, cache, kv_b, pos0, 0.1, dtype, **kernel)

    return text, functools.partial(
        text, kernel=True, block_k=block_k, interpret=True)


_LATENT_STEP_CASES = {
    # one key head of 144 lanes (no whole lane tiles), its leading 128
    # the value, four query heads of 24-wide values; streams at depth 0,
    # inside a block, on a block's edge from both sides and full
    "blocks_of_128": dict(depth=512, pos0=(0, 100, 127, 128, 511), block_k=128),
    "blocks_of_256": dict(depth=1024, pos0=(0, 255, 256, 700, 1023), block_k=256),
    "blocks_of_512": dict(depth=2048, pos0=(0, 511, 512, 513, 2047), block_k=512),
    # seven query heads: the tile's rows padded to the sublanes
    "heads_7_one_block": dict(heads=7, depth=128, pos0=(0, 5, 126, 127, 64),
                              block_k=128),
    "float32_blocks_of_16": dict(block_k=16, dtype=jnp.float32),
    "gradient_is_the_texts": dict(block_k=16, dtype=jnp.float32),
    "a_skipped_block_changes_nothing": dict(
        pos0=(0, 15, 20, 31, 7), block_k=16, dtype=jnp.float32),
}


@pytest.mark.parametrize("name", list(_LATENT_STEP_CASES))
def test_latent_step_kernel(name):
    """The latent row on the step kernel (one cache: a key block's
    leading lanes are its values) against ``absorbed_step``'s text."""
    case = dict(_LATENT_STEP_CASES[name])
    block_k = case.pop("block_k")
    dtype = case.get("dtype", jnp.bfloat16)
    operands, pos0, w = _latent_step(**case)
    text, kernel = _latent_step_text_and_kernel(pos0, dtype, block_k)
    if name == "a_skipped_block_changes_nothing":
        # no stream is deeper than 32 of 64 slots: the last two blocks
        # of 16 are skipped for all, so other rows there give the same bits
        q_nope, q_pe, cache, kv_b = operands
        np.testing.assert_array_equal(
            np.asarray(kernel(q_nope, q_pe, cache.at[:, 33:].set(7.0), kv_b)),
            np.asarray(kernel(*operands)))
        return
    tol = dict(atol=1e-4, rtol=1e-4) if dtype == jnp.float32 else dict(
        atol=2e-2, rtol=2e-2)
    np.testing.assert_allclose(
        np.asarray(kernel(*operands)), np.asarray(text(*operands)), **tol)
    if name == "gradient_is_the_texts":
        loss = lambda f: lambda *a: jnp.sum(f(*a) * w)
        got = jax.grad(loss(kernel), argnums=range(4))(*operands)
        want = jax.grad(loss(text), argnums=range(4))(*operands)
        for a, b in zip(got, want):
            assert float(jnp.max(jnp.abs(b))) > 1e-3
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), **tol)


def test_step_attention_takes_one_cache_for_one_key_head_only():
    """No value cache means ONE key head and a ``value_dim``: anything
    else is refused before a kernel is built."""
    from ray_tpu.ops import flash_attention as fa

    q = jnp.zeros((2, 1, 2, 4, 128), jnp.bfloat16)
    cache = jnp.zeros((2, 64, 256), jnp.bfloat16)
    held = jnp.ones((2,), jnp.int32)
    for operands, value_dim in (((q, cache, None), 128),      # two key heads
                                ((q[:, :, :1], cache, None), None),
                                ((q, cache, cache), 128)):
        with pytest.raises(ValueError, match="ONE key head"):
            fa.step_attention(*operands, held, value_dim=value_dim, block_k=16)


@pytest.mark.parametrize("heads,kv,head,depth,value", [
    (28, 4, 128, 8192, None),  # SmallThinker's full layer
    (48, 8, 128, 4096, None),  # Laguna's full layers
    (16, 2, 256, 2048, None),  # Qwen3-Next's gated layer
    (32, 8, 64, 2048, None),   # Granite's: two key heads a block
    # Xing4's latent rows: one key head of 576 lanes (whole half lane
    # tiles) whose leading 512 are its value, in the one cache
    (32, 1, 576, 2048, 512),
])
def test_step_rule_by_shape(monkeypatch, heads, kv, head, depth, value):
    """Off a TPU the rule says XLA whatever the shape; on one, from the
    shapes alone: every cell's full-depth layer in bfloat16, and neither
    float32 nor a cache of no whole key block (a ring never asks: its
    window sends it to the text before the rule) nor a row that is no
    whole half lane tile."""
    from ray_tpu.ops import flash_attention as fa

    rule = functools.partial(fa.step_kernel_applies, value_dim=value)
    assert not rule(heads, kv, head, depth, jnp.bfloat16)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert rule(heads, kv, head, depth, jnp.bfloat16)
    assert not rule(heads, kv, head, depth, jnp.float32)
    assert not rule(heads, kv, head, depth + 24, jnp.bfloat16)
    assert not rule(heads, kv, 96 + (value or 0), depth, jnp.bfloat16)
    # two slots of a block of all key heads, keys and values, fit VMEM;
    # one cache belongs to ONE key head
    assert not rule(16 * heads, 16 * kv, head, depth, jnp.bfloat16)
    if value:
        # a value of no whole lane tiles; no lanes after it; a cache
        # whose two streams' further lanes do not fit beside the blocks
        assert not fa.step_kernel_applies(
            heads, kv, head, depth, jnp.bfloat16, value_dim=value - 64)
        assert not fa.step_kernel_applies(
            heads, kv, head, depth, jnp.bfloat16, value_dim=head)
        assert not rule(heads, kv, head, 16 * depth, jnp.bfloat16)
        # and a latent row handed over as two caches is no softmax head
        assert not fa.step_kernel_applies(heads, kv, head, depth, jnp.bfloat16)
    # 16 blocks of 512: rows held of 1, 512, 513 and past the cache skip
    # 15, 15, 14 and none
    skipped, held = fa.step_key_blocks(
        jnp.asarray([[1, 512], [513, 9000]], jnp.int32), 8192)
    assert (int(skipped), held) == (44, 64)
