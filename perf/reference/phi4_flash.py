"""Plain reference of the Phi-4-mini-flash-reasoning block stack
(``model_type: phi4flash``: SambaY, the decoder-hybrid-decoder of Ren et
al., arXiv:2507.06607, with differential attention, Ye et al.,
arXiv:2410.05258; its state-space layers are Mamba-1, Gu & Dao,
arXiv:2312.00752; the cross-decoder is YOCO's, Sun et al.,
arXiv:2405.05254) as a token-level PPO policy: ``jax.numpy``, float32,
every product at precision "highest", nothing from ``ray_tpu``.

Written the long way where the system is clever. The selective scan is
the recurrence ONE TOKEN AT A TIME under ``lax.scan`` (convolution
window, decay, write, read); a new episode zeroes the matrix and the
window before its first token. Attention is the full masked score
matrix over every stored row and the fragment's own, a few streams at a
time, each of a pair's TWO softmax maps on its own and the pair's FOUR
products written out (two score products, two value products); the
masks are written in positions. A cross layer is handed the full
layer's stored rows and its keys and values of the fragment as plain
arrays; the gated-memory layer is handed the scan's output as a plain
array. Only the two ends of ``forward`` know that a window layer's
state is a ring (``_stored_rows``, ``_write``). Its own GAE, PPO loss,
global-norm clip and Adam step are at the end.

The layers (``d`` hidden, ``L`` the PUBLISHED depth, ``i`` a layer's
PUBLISHED index; the file holds ``layer_indices`` where the depth is
cut). Every block is ``x <- x + F(LN(x))`` then ``x <- x + MLP(LN'(x))``,
``LN`` a LayerNorm with weight and bias, ``MLP(h) = W_2 (silu(g) * u)``,
``[g | u] = W_1 h``. The embedding is tied to the head; no positions
anywhere. Mixer ``F`` of layer ``i``:

- ``i`` even, ``i <= L/2``: Mamba-1. ``[u | z] = h W_in``; ``u <-
  silu(conv(u) + b_conv)``; ``[r | B | C] = u W_x``; ``dt = softplus(r
  W_dt + b_dt)``; ``A = -exp(A_log)``; ``S_t[c, n] = exp(dt_t[c] A[c, n])
  S_{t-1}[c, n] + dt_t[c] B_t[n] u_t[c]``; ``y_t[c] = sum_n S_t[c, n]
  C_t[n] + D[c] u_t[c]``; ``F = (y * silu(z)) W_out``. Layer ``L/2`` also
  hands on ``m_t = y_t`` (after ``D``, before the gate): the memory.
- ``i`` odd, ``i < L/2``: differential attention on a window of
  ``sliding_window``; ``i = L/2 + 1``: the same at full depth, its keys
  and values handed on. ``[q | k | v] = h W_qkv + b``; heads of ``dh``;
  query pair ``j`` is ``(q_2j, q_2j+1)``, key pair ``g = j // (pairs a
  key pair)`` is ``(k_2g, k_2g+1)``, ``V_g = [v_2g | v_2g+1]``; ``o_j =
  softmax(q_2j k_2g^T / sqrt(dh)) V_g - lambda_i softmax(q_2j+1 k_2g+1^T
  / sqrt(dh)) V_g``; ``lambda_i = exp(lq1 . lk1) - exp(lq2 . lk2) +
  lambda0_i``, ``lambda0_i = 0.8 - 0.6 exp(-0.3 i)``; ``o_j <- rms(o_j) *
  w * (1 - lambda0_i)`` over its ``2 dh`` numbers; ``F = [o_0 ..] W_o +
  b_o``.
- ``i`` even, ``i > L/2``: gated memory unit, ``F = (m * silu(h W_1))
  W_2``, ``m`` the memory of the same token.
- ``i`` odd, ``i > L/2 + 1``: cross-attention: ``q = h W_q + b``, keys
  and values layer ``L/2 + 1``'s (the token's own among them), the
  differential form with this layer's own vectors, norm and ``W_o``.

DEPARTURES, each a reparametrisation under seeded weights: every
LayerNorm weight (and none other) is stored zero-centred, ``(1 + w)``,
as the policy stores every norm; ``W_qkv`` and its bias are held as
their three column blocks ``q_proj`` / ``k_proj`` / ``v_proj``, ``W_1``
of the feed-forward as ``mlp_gate`` / ``mlp_up``; ``A_log`` and the
scan's matrix are held ``(state, channel)``, the transpose of the
definition's ``(channel, state)``, so that the channels lie along a
device's lanes (16 states along them would pad eightfold). Parameters
are then two levels deep in the policy's own names and shapes, so
``to_policy_tree`` is the identity and a caller may hand the policy's
arrays in as views. ``init_params`` returns HOST arrays.

``precision`` "int8" and "fp8" are the CONTROLS: inputs and weights of
every projection (``W_in``, ``W_x``, ``W_dt``, ``W_out``, q/k/v/o, the
gated memory's two, the feed-forward's three) and the head rounded per
tensor to 127 levels or to float8 e4m3, and their cotangents likewise:
one step below the bfloat16 operands the configuration states.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
# streams whose per-token matrices, or keys, values and scores, are
# alive at once
STREAMS = 2
SCAN, WINDOW, FULL, MEMORY, CROSS = (
    "selective_scan", "sliding_attention", "attention", "gated_memory",
    "cross_attention")
# the kind of layer whose keys and values the cross layers read
EXPORTS_CACHE = FULL


# -- the controls ---------------------------------------------------------------


def _round_int8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.clip(jnp.round(x / scale), -127.0, 127.0) * scale


def _round_fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _both_ways(rounding):
    @jax.custom_vjp
    def fake(x):
        return rounding(x)

    fake.defvjp(lambda x: (rounding(x), None), lambda _, g: (rounding(g),))
    return fake


_QUANT = {
    "float32": lambda v: v,
    "int8": _both_ways(_round_int8),
    "fp8": _both_ways(_round_fp8),
}


# -- sizes and weights ----------------------------------------------------------


def kind_of(i: int, depth: int, period: int = 2) -> str:
    """The mixer of PUBLISHED layer ``i`` of ``depth``."""
    if i <= depth // 2:
        return SCAN if i % period == 0 else WINDOW
    if i == depth // 2 + 1:
        return FULL
    return MEMORY if i % period == 0 else CROSS


def sizes(config: Dict, num_actions: int) -> Dict:
    c = config
    layers = int(c["num_hidden_layers"])
    indices = tuple(int(i) for i in c.get("layer_indices") or range(layers))
    depth = int(c.get("published_num_hidden_layers", layers))
    d, heads = int(c["hidden_size"]), int(c["num_attention_heads"])
    return {
        "D": d, "V": int(num_actions), "L": layers, "indices": indices,
        "depth": depth,
        "kinds": tuple(kind_of(i, depth, int(c.get("mb_per_layer", 2)))
                       for i in indices),
        "eps": float(c["layer_norm_eps"]),
        "H": heads, "Hkv": int(c["num_key_value_heads"]), "dh": d // heads,
        "W": int(c["sliding_window"]), "S": int(c["max_position_embeddings"]),
        "F": int(c["intermediate_size"]),
        "I": int(c.get("mamba_expand", 2)) * d, "N": int(c.get("mamba_d_state", 16)),
        "R": int(c.get("mamba_dt_rank", -(-d // 16))),
        "K": int(c.get("mamba_d_conv", 4)),
        "T": int(c["algo_config"]["model"]["max_seq_len"]),
    }


def lambda0(index: int) -> float:
    return 0.8 - 0.6 * float(np.exp(-0.3 * index))


def cache_rows(z: Dict, kind: str) -> int:
    return min(z["W"], z["S"]) if kind == WINDOW else z["S"]


def param_shapes(config: Dict, num_actions: int) -> Dict[str, Dict[str, tuple]]:
    z = sizes(config, num_actions)
    d, f, i, n, r, dh = z["D"], z["F"], z["I"], z["N"], z["R"], z["dh"]
    wide, kv = z["H"] * dh, z["Hkv"] * dh
    out = {
        "embed": {"embedding": (z["V"], d)},  # the output head too
        "final_norm": {"weight": (d,), "bias": (d,)},
        "value": {"kernel": (d, 1), "bias": (1,)},
    }
    for li, kind in enumerate(z["kinds"]):
        layer = {
            "input_norm": (d,), "input_norm_bias": (d,),
            "post_norm": (d,), "post_norm_bias": (d,),
            "mlp_gate": (d, f), "mlp_up": (d, f), "mlp_down": (f, d),
        }
        if kind == SCAN:
            layer.update({
                "in_proj": (d, 2 * i), "conv": (i, z["K"]), "conv_bias": (i,),
                "x_proj": (i, r + 2 * n), "dt_proj": (r, i), "dt_bias": (i,),
                "A_log": (n, i), "D": (i,), "out_proj": (i, d),
            })
        elif kind == MEMORY:
            layer.update({"gmu_in": (d, i), "gmu_out": (i, d)})
        else:
            layer.update({
                "q_proj": (d, wide), "q_bias": (wide,),
                "o_proj": (wide, d), "o_bias": (d,),
                "lambda_q1": (dh,), "lambda_k1": (dh,),
                "lambda_q2": (dh,), "lambda_k2": (dh,),
                "diff_norm": (2 * dh,),
            })
            if kind != CROSS:
                layer.update({"k_proj": (d, kv), "k_bias": (kv,),
                              "v_proj": (d, kv), "v_bias": (kv,)})
        out[f"layer_{li}"] = layer
    return out


def init_params(key, config: Dict, num_actions: int, host: bool = True):
    """Seeded weights, one jitted call a group, brought to the host
    (``host=False``: left on the device). ASSUMED, the config states
    none of it: matrices normal with variance 1 / rows (the convolution
    1 / width); the tied table 0.7 x normal of variance 1 / hidden, so
    that the logits ``LN(x) E^T`` have deviation 0.7 (a random policy
    that is not near-deterministic); LayerNorm weights (zero-centred)
    and every bias 0.1 x normal, small and not zero (a weight the system
    dropped would otherwise go unseen); the four lambda vectors 0.1 x
    normal; the pair norm's weight 1 + 0.1 x normal; Mamba's own
    initialisation for the recurrence: ``A_log = log(1..state)`` a
    channel, ``D = 1``, ``dt_bias`` the inverse softplus of a
    log-uniform step in (0.001, 0.1), ``W_dt`` uniform in
    ``+-dt_rank^-1/2``."""
    shapes = param_shapes(config, num_actions)
    # XLA's own bit generator: a threefry stream for 697 M weights is a
    # minute of compiling on the chip, and this is a few seconds
    key = jax.random.wrap_key_data(
        jnp.tile(jax.random.key_data(key).astype(jnp.uint32).ravel(), 2)[:4],
        impl="rbg")

    def make(key, group):
        # ONE draw a group, cut into its leaves; a matrix whose rows are
        # not whole lane tiles (the convolution's (inner, 4), W_x's
        # (inner, 192)) gets a draw of its own: cut out of the group's,
        # its reshape makes the chip's compiler lay the WHOLE draw out
        # in rows padded to 128 lanes, 15.8 GB for a scan layer
        leaves = sorted(shapes[group].items())
        apart = {leaf for leaf, shape in leaves if len(shape) > 1 and shape[-1] % 128}
        counts = [0 if leaf in apart else int(np.prod(shape)) for leaf, shape in leaves]
        draws = jax.random.normal(key, (sum(counts),), jnp.float32)
        out, at = {}, 0
        for n, ((leaf, shape), count) in enumerate(zip(leaves, counts)):
            if leaf in apart:
                x = jax.random.normal(jax.random.fold_in(key, n), shape, jnp.float32)
            else:
                x = draws[at : at + count].reshape(shape)
            at += count
            if leaf == "A_log":
                x = jnp.broadcast_to(jnp.log(
                    jnp.arange(1, shape[0] + 1, dtype=jnp.float32))[:, None], shape)
            elif leaf == "D":
                x = jnp.ones(shape, jnp.float32)
            elif leaf == "dt_bias":
                # log-uniform in (0.001, 0.1) from the normal draw
                dt = jnp.exp(np.log(1e-3) + jax.scipy.stats.norm.cdf(x) * np.log(100.0))
                x = dt + jnp.log(-jnp.expm1(-dt))
            elif leaf == "dt_proj":
                x = (2.0 * jax.scipy.stats.norm.cdf(x) - 1.0) / np.sqrt(shape[0])
            elif leaf == "diff_norm":
                x = 1.0 + 0.1 * x
            elif len(shape) == 1:
                x = 0.1 * x
            elif leaf == "embedding":
                x = 0.7 * x / np.sqrt(shape[1])
            elif leaf == "conv":
                x = x / np.sqrt(shape[-1])
            else:
                x = x / np.sqrt(shape[-2])
            out[leaf] = x
        return out

    out = {}
    for g, group in enumerate(sorted(shapes)):
        made = jax.jit(make, static_argnums=1)(jax.random.fold_in(key, g), group)
        out[group] = {k: np.asarray(v) for k, v in made.items()} if host else made
    return out


def to_policy_tree(params, config: Dict):
    return {group: dict(leaves) for group, leaves in params.items()}


def from_policy_tree(tree, config: Dict):
    return {group: dict(leaves) for group, leaves in tree.items()}


# -- the model --------------------------------------------------------------------


def _layer_norm(x, w, b, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * (
        1.0 + w) + b


def _mm(x, w, q_):
    return jnp.dot(q_(x), q_(w), precision=HI)


def _swiglu(x, wg, wu, wd, q_):
    return _mm(jax.nn.silu(_mm(x, wg, q_)) * _mm(x, wu, q_), wd, q_)


def initial_state(z: Dict, rows: int):
    """As the policy lays it out: a scan layer its matrix ``(rows,
    state, inner)`` and the last ``conv - 1`` inputs of its convolution,
    float32; a window layer its ring of keys and of values, the full
    layer its episode's (bfloat16); the gated-memory and cross layers
    nothing; last the position."""
    state = []
    for kind in z["kinds"]:
        if kind == SCAN:
            state.append(jnp.zeros((rows, z["N"], z["I"]), jnp.float32))
            state.append(jnp.zeros((rows, z["K"] - 1, z["I"]), jnp.float32))
        elif kind in (WINDOW, FULL):
            for _ in range(2):
                state.append(jnp.zeros(
                    (rows, cache_rows(z, kind), z["Hkv"] * z["dh"]), jnp.bfloat16))
    state.append(jnp.zeros((rows,), jnp.int32))
    return tuple(state)


def _positions(pos0, fresh):
    """Each token's position in its episode: a fresh token is at 0."""
    def step(pos, f):
        pos = jnp.where(f, 0, pos)
        return pos + 1, pos

    end, positions = jax.lax.scan(step, pos0, fresh.T)
    return positions.T, end


def _stored_rows(cache, pos0):
    """The rows a stream holds, newest first: ``(rows (B, n, row), their
    positions (B, n))`` for the ``n`` slots of ``cache``, a position
    below zero where the episode has no such row yet. The row of
    position ``p`` lies in slot ``p mod n`` (``p`` itself while the cache
    is as deep as the episode)."""
    n = cache.shape[1]
    at = pos0[:, None] - 1 - jnp.arange(n)[None]  # (B, n)
    rows = jnp.take_along_axis(cache, (at % n)[..., None], axis=1)
    return rows.astype(jnp.float32), at


def _write(cache, rows, positions):
    """``cache`` after the fragment's ``rows`` ``(B, T, row)``, token by
    token, each at its position mod the cache's depth, in the type the
    cache came in."""
    n, b = cache.shape[1], cache.shape[0]

    def one(c, xs):
        row_t, pos_t = xs
        return c.at[jnp.arange(b), pos_t % n].set(row_t.astype(c.dtype)), None

    out, _ = jax.lax.scan(one, cache, (jnp.moveaxis(rows, 1, 0), positions.T))
    return out


def _in_groups(f, args):
    """``f`` over ``STREAMS`` streams at a time, each group recomputed
    in the backward pass; results joined along the streams."""
    b = args[0].shape[0]
    k = STREAMS if b % STREAMS == 0 else 1
    out = jax.lax.map(
        jax.checkpoint(f),
        tuple(a.reshape((b // k, k) + a.shape[1:]) for a in args),
    )
    return jax.tree_util.tree_map(lambda a: a.reshape((b,) + a.shape[2:]), out)


def _scan(p, x, matrix, window, fresh, z, q_):
    """One Mamba-1 layer over a fragment, token by token. ``matrix``
    ``(B, state, inner)``, ``window`` ``(B, conv - 1, inner)`` the last
    inputs of the convolution. Returns ``(F, the memory, matrix after,
    window after)``."""
    i, n, r = z["I"], z["N"], z["R"]
    uz = _mm(x, p["in_proj"], q_)
    u, gate = uz[..., :i], uz[..., i:]
    a = -jnp.exp(p["A_log"])  # (state, inner)

    def some_streams(xs):
        u, matrix, window, fresh = xs

        def token(carry, xs):
            s, w = carry
            u_t, f_t = xs
            # a new episode starts from nothing
            s = jnp.where(f_t[:, None, None], 0.0, s)
            w = jnp.where(f_t[:, None, None], 0.0, w)
            w = jnp.concatenate([w, u_t[:, None]], axis=1)  # (b, conv, inner)
            u_t = jax.nn.silu(jnp.sum(w * p["conv"].T[None], axis=1) + p["conv_bias"])
            rbc = _mm(u_t, p["x_proj"], q_)
            b_t, c_t = rbc[:, r : r + n], rbc[:, r + n :]
            dt = jax.nn.softplus(_mm(rbc[:, :r], p["dt_proj"], q_) + p["dt_bias"])
            s = jnp.exp(dt[:, None, :] * a) * s + (
                (dt * u_t)[:, None, :] * b_t[:, :, None])
            y = jnp.sum(s * c_t[:, :, None], axis=1) + p["D"] * u_t
            return (s, w[:, 1:]), y

        (s, w), ys = jax.lax.scan(
            token, (matrix, window), (jnp.moveaxis(u, 1, 0), fresh.T))
        return jnp.moveaxis(ys, 0, 1), s, w

    y, matrix, window = _in_groups(some_streams, (u, matrix, window, fresh))
    return (_mm(y * jax.nn.silu(gate), p["out_proj"], q_), _memory(y, gate),
            matrix, window)


def _memory(y, gate):
    """What the exporting scan layer hands on: its output after the
    ``D`` skip and BEFORE the gate."""
    return y


def _keys_and_values(p, x, z, q_):
    return (_mm(x, p["k_proj"], q_) + p["k_bias"],
            _mm(x, p["v_proj"], q_) + p["v_bias"])


def _two_maps(q, keys, values, mask, lam, scale):
    """A pair's output from its four products: ``q``, ``keys`` ``(b, t |
    s, pairs, 2, dh)``, ``values`` ``(b, s, pairs, 2 dh)``, ``mask`` ``(b,
    t, s)``. Each map is a softmax of its own."""
    def one_map(which):
        scores = jnp.einsum(
            "btjd,bsjd->bjts", q[:, :, :, which], keys[:, :, :, which],
            precision=HI) * scale
        w = jax.nn.softmax(jnp.where(mask[:, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("bjts,bsjd->btjd", w, values, precision=HI)

    return one_map(0) - lam * one_map(1)


def _diff_attention(p, x, k, v, k_old, v_old, at, positions, fresh, z, q_,
                    window: bool, index: int):
    """Differential attention of the fragment's queries over the stored
    rows ``k_old``, ``v_old`` (positions ``at``) and the fragment's own
    ``k``, ``v`` ``(B, T, kv heads x dh)``: every pair's two maps, each
    its own softmax over a dense mask."""
    b, t, _ = x.shape
    h, hkv, dh = z["H"], z["Hkv"], z["dh"]
    q = (_mm(x, p["q_proj"], q_) + p["q_bias"]).reshape(b, t, h // 2, 2, dh)
    episode = jnp.cumsum(fresh.astype(jnp.int32), axis=1)
    lam0 = lambda0(index)
    lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
           - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + lam0)

    def some_streams(xs):
        q, k, v, k_old, v_old, at, ep, pos = xs
        # a key pair and its value, for each of the query pairs it serves
        pairs = lambda a: jnp.repeat(
            a.reshape(a.shape[:2] + (hkv // 2, 2, dh)), h // hkv, axis=2)
        keys = pairs(jnp.concatenate([k_old, k], axis=1))  # (b, s, h/2, 2, dh)
        values = pairs(jnp.concatenate([v_old, v], axis=1))
        values = values.reshape(values.shape[:3] + (2 * dh,))  # V_g
        key_pos = jnp.concatenate([at, pos], axis=1)  # (b, n + t)
        key_ep = jnp.concatenate([jnp.zeros_like(at), ep], axis=1)
        behind = pos[:, :, None] - key_pos[:, None, :]
        mask = (key_pos >= 0)[:, None] & (key_ep[:, None] == ep[:, :, None]) & (
            behind >= 0)
        if window:
            mask = mask & (behind < z["W"])

        return _two_maps(q, keys, values, mask, lam, dh ** -0.5)

    o = _in_groups(
        some_streams, (q, k, v, k_old, v_old, at, episode, positions))
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + z["eps"])
    o = o * p["diff_norm"] * (1.0 - lam0)
    return _mm(o.reshape(b, t, h * dh), p["o_proj"], q_) + p["o_bias"]


def forward(params, tokens, state, fresh, config: Dict, num_actions: int,
            precision: str = "float32"):
    """A fragment from its start state. ``tokens`` ``(B, T)`` int;
    ``state`` as ``initial_state`` (caches in any float type); ``fresh``
    ``(B, T)`` bool (the token opens an episode). Returns ``{"logits"
    (B, T, V), "value" (B, T), "state", "routes", "memory"}``;
    ``routes`` is one row of zeros ``(1, B*T, 1)``: there is no router;
    ``memory`` the exporting scan's output ``(B, T, inner)``."""
    z, q_ = sizes(config, num_actions), _QUANT[precision]
    fresh = fresh.astype(bool)
    pos0 = state[-1]
    positions, pos1 = _positions(pos0, fresh)
    table = params["embed"]["embedding"]
    x = table[tokens.astype(jnp.int32)]
    norm = lambda x, p, name: _layer_norm(x, p[name], p[name + "_bias"], z["eps"])

    def feed_forward(x, p):
        return x + _swiglu(norm(x, p, "post_norm"), p["mlp_gate"], p["mlp_up"],
                           p["mlp_down"], q_)

    state_out, at_leaf = [], 0
    memory = shared = None
    for li, (kind, index) in enumerate(zip(z["kinds"], z["indices"])):
        p = params[f"layer_{li}"]
        if kind == SCAN:
            @jax.checkpoint
            def layer(x, p, matrix, window):
                y, m, matrix, window = _scan(
                    p, norm(x, p, "input_norm"), matrix, window, fresh, z, q_)
                return feed_forward(x + y, p), m, matrix, window

            x, m, matrix, window = layer(x, p, state[at_leaf], state[at_leaf + 1])
            if index == z["depth"] // 2:
                memory = m
            state_out.extend([matrix, window])
            at_leaf += 2
        elif kind in (WINDOW, FULL):
            @jax.checkpoint
            def layer(x, p, k_cache, v_cache, window=kind == WINDOW, index=index):
                h = norm(x, p, "input_norm")
                k, v = _keys_and_values(p, h, z, q_)
                k_old, at = _stored_rows(k_cache, pos0)
                v_old, _ = _stored_rows(v_cache, pos0)
                y = _diff_attention(p, h, k, v, k_old, v_old, at, positions, fresh,
                                    z, q_, window, index)
                return feed_forward(x + y, p), (k, v, k_old, v_old, at)

            x, made = layer(x, p, state[at_leaf], state[at_leaf + 1])
            if kind == EXPORTS_CACHE:
                shared = made
            state_out.extend([_write(state[at_leaf], made[0], positions),
                              _write(state[at_leaf + 1], made[1], positions)])
            at_leaf += 2
        elif kind == MEMORY:
            @jax.checkpoint
            def layer(x, p, m):
                h = norm(x, p, "input_norm")
                y = _mm(m * jax.nn.silu(_mm(h, p["gmu_in"], q_)), p["gmu_out"], q_)
                return feed_forward(x + y, p)

            x = layer(x, p, memory)
        else:
            @jax.checkpoint
            def layer(x, p, made, index=index):
                y = _diff_attention(p, norm(x, p, "input_norm"), *made, positions,
                                    fresh, z, q_, False, index)
                return feed_forward(x + y, p)

            x = layer(x, p, shared)
    state_out.append(pos1)
    feat = _layer_norm(x, params["final_norm"]["weight"],
                       params["final_norm"]["bias"], z["eps"])
    logits = _mm(feat, table.T, q_)  # the tied head
    value = (
        jnp.dot(feat, params["value"]["kernel"], precision=HI)
        + params["value"]["bias"]
    )[..., 0]
    b, t = tokens.shape
    return {"logits": logits, "value": value, "state": tuple(state_out),
            "routes": jnp.zeros((1, b * t, 1), jnp.int32), "memory": memory}


# -- batches, loss, and the rest of PPO ---------------------------------------------


def make_state(rng: np.random.Generator, z: Dict, rows: int, fragment: int):
    """Seeded start states: streams somewhere inside an episode, EVERY
    slot of every cache filled with rows of order one rounded to
    bfloat16 (what earlier episodes leave behind: a row that must not be
    seen is there to be seen), the scan's matrices and convolution
    inputs what a few dozen writes leave."""
    pos0 = rng.integers(0, z["S"] - fragment + 1, rows).astype(np.int32)
    pos0[0] = 0  # one stream at its episode's start
    state = []
    for like in initial_state(z, rows)[:-1]:
        leaf = rng.standard_normal(like.shape, dtype=np.float32)
        if like.dtype == jnp.bfloat16:  # a cache
            leaf = leaf.astype(jnp.bfloat16)
        elif like.shape[1] == z["N"]:  # the matrices
            leaf = 0.3 * leaf
        state.append(leaf)
    state.append(pos0)
    return tuple(state)


def make_batch(rng: np.random.Generator, config: Dict, rows: int, num_actions: int):
    """A row is one token of a fragment; ``rows / T`` fragments, each
    with its start state in the ``__chunk__state_in_<k>`` columns (one
    row a fragment). The second fragment has an episode boundary inside
    it where there is room."""
    z = sizes(config, num_actions)
    t = z["T"]
    frags = rows // t
    prev = rng.normal(0.0, 1.0, (rows, num_actions)).astype(np.float32)
    actions = rng.integers(0, num_actions, rows).astype(np.int32)
    logp = prev - np.log(np.sum(np.exp(prev), axis=1, keepdims=True))
    resets = np.zeros((frags, t), np.float32)
    state = make_state(rng, z, frags, t)
    resets[0, 0] = 1.0 if state[-1][0] == 0 else 0.0
    if frags > 1 and t > 2:
        resets[1, t // 3] = 1.0
    batch = {
        "obs": rng.integers(0, num_actions, (rows, 1)).astype(np.int32),
        "actions": actions,
        "action_logp": logp[np.arange(rows), actions].astype(np.float32),
        "action_dist_inputs": prev,
        "advantages": rng.normal(0.0, 1.0, rows).astype(np.float32),
        "value_targets": rng.normal(0.0, 1.0, rows).astype(np.float32),
        "resets": resets.reshape(rows),
    }
    for k, leaf in enumerate(state):
        batch[f"__chunk__state_in_{k}"] = leaf
    return batch


def batch_state(batch):
    out, k = [], 0
    while f"__chunk__state_in_{k}" in batch:
        out.append(batch[f"__chunk__state_in_{k}"])
        k += 1
    return tuple(out)


def ppo_loss(logits, value, batch, algo: Dict):
    """Clipped surrogate + clipped value loss + KL penalty - entropy
    bonus, mean over the rows (Schulman et al. 2017)."""
    clip, vf_clip = float(algo["clip_param"]), float(algo["vf_clip_param"])
    logp_all = jax.nn.log_softmax(logits)
    prev_all = jax.nn.log_softmax(batch["action_dist_inputs"])
    logp = jnp.take_along_axis(
        logp_all, batch["actions"][:, None].astype(jnp.int32), axis=1
    )[:, 0]
    ratio = jnp.exp(logp - batch["action_logp"])
    adv = batch["advantages"]
    surrogate = jnp.minimum(adv * ratio, adv * jnp.clip(ratio, 1 - clip, 1 + clip))
    kl = jnp.sum(jnp.exp(prev_all) * (prev_all - logp_all), axis=1)
    entropy = -jnp.sum(jnp.exp(logp_all) * logp_all, axis=1)
    vf = jnp.clip(jnp.square(value - batch["value_targets"]), 0.0, vf_clip)
    return jnp.mean(
        -surrogate + float(algo.get("kl_coeff", 0.0)) * kl
        + float(algo.get("vf_loss_coeff", 1.0)) * vf
        - float(algo.get("entropy_coeff", 0.0)) * entropy
    )


def loss(params, batch, config: Dict, precision: str = "float32"):
    num_actions = batch["action_dist_inputs"].shape[-1]
    t = sizes(config, num_actions)["T"]
    rows = batch["actions"].shape[0]
    out = forward(
        params,
        batch["obs"].reshape(rows // t, t),
        tuple(jax.lax.stop_gradient(s) for s in batch_state(batch)),
        batch["resets"].reshape(rows // t, t) > 0.5,
        config, num_actions, precision,
    )
    return ppo_loss(
        out["logits"].reshape(rows, num_actions), out["value"].reshape(rows),
        batch, config["algo_config"],
    )


def gae(rewards, values, next_values, terminated, done, gamma: float, lam: float):
    """Generalised advantage estimation over ``(T, N)`` arrays, float64
    on the host, the plain backward loop: a bootstrap is zero across
    ``terminated``, and the running sum stops at ``done``."""
    rewards, values, next_values = (
        np.asarray(x, np.float64) for x in (rewards, values, next_values)
    )
    terminated, done = np.asarray(terminated, bool), np.asarray(done, bool)
    adv = np.zeros_like(rewards)
    running = np.zeros(rewards.shape[1])
    for t in range(rewards.shape[0] - 1, -1, -1):
        delta = rewards[t] + gamma * next_values[t] * (~terminated[t]) - values[t]
        running = delta + gamma * lam * running * (~done[t])
        adv[t] = running
    return adv, adv + values


def standardize(adv):
    adv = np.asarray(adv, np.float64)
    return (adv - adv.mean()) / max(1e-4, adv.std())


def adam_step(params, grads, mu, nu, count: int, lr: float, clip, eps=1e-8,
              b1=0.9, b2=0.999, xp=np):
    """One global-norm clip + Adam step on flat dicts of host arrays:
    ``(params, mu, nu)`` after it. ``xp=jnp`` (and no clip) takes the
    same lines over device arrays, inside a jitted comparison."""
    scale = 1.0
    if clip:
        norm = np.sqrt(sum(float(np.sum(np.square(g))) for g in grads.values()))
        scale = min(1.0, float(clip) / max(norm, 1e-30))
    out_p, out_mu, out_nu = {}, {}, {}
    for k, g in grads.items():
        g = g * scale
        out_mu[k] = b1 * mu[k] + (1 - b1) * g
        out_nu[k] = b2 * nu[k] + (1 - b2) * g * g
        m_hat = out_mu[k] / (1 - b1 ** count)
        v_hat = out_nu[k] / (1 - b2 ** count)
        out_p[k] = params[k] - lr * m_hat / (xp.sqrt(v_hat) + eps)
    return out_p, out_mu, out_nu
