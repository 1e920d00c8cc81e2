"""Plain reference of the Ling 3.0 block stack (``model_type:
bailing_hybrid``) as a token-level PPO policy: ``jax.numpy``, float32,
every product at precision "highest", nothing from ``ray_tpu``.

Written the long way where the system is clever. Kimi Delta Attention
and its three convolutions are ONE ``lax.scan`` over the tokens of a
fragment (the recurrence, state in and state out: no chunk, no
sub-block, no reference row). Latent attention is the EXPANDED form
only: the state holds one latent row a position, and the keys and
values of EVERY position (stored and the fragment's own) are rebuilt
from them through ``W_kvb`` under the full masked score matrix; the
query never absorbs ``W_kvb``. The group-limited router is written out
(a group's score, the groups kept, the experts of the others struck
out), and the routed experts are a loop over the HELD experts with a
dense 0/weight mask. The share (``experts_held``, the vocabulary rows)
is the policy's: what the absent experts would add is left out here as
there. Its own GAE, PPO loss, global-norm clip and Adam step are at the
end.

Layer equations (Kimi Linear, arXiv:2510.26692, over the gated delta
rule of arXiv:2412.06464; DeepSeek-V3, arXiv:2412.19437, for the latent
attention and the router; what the published config is silent on is
``assumed`` in the configuration file). ``d`` the hidden size, ``H``
heads of ``hd`` in both mixers. Every block is ``x <- x + F(rms(x))``
then ``x <- x + FFN(rms'(x))``; no bias anywhere.

- ``rms(x) = x * rsqrt(mean(x^2) + eps) * (1 + w)`` (DEPARTURE: the
  weight is stored zero-centred, as the policy stores every norm; with
  seeded weights a reparametrisation).
- the mixer of PUBLISHED layer ``i`` is latent attention where ``(i +
  1) % layer_group_size == 0``, else Kimi Delta Attention; ``layer_indices``
  names the published layers held.
- KDA (``h = rms(x)``): ``q~ = h W_q``, ``k~ = h W_k``, ``v~ = h W_v``,
  each through its own causal depthwise convolution (width
  ``short_conv_kernel_size``, no bias) then SiLU; per head ``q =
  l2norm(q~) hd^-1/2``, ``k = l2norm(k~)``, ``v = v~``. ``a = h W_f`` (a
  number a head and key channel), ``g = kda_lower_bound * sigmoid(
  exp(A_log_h) (a + dt_bias))``; ``beta = sigmoid(h W_b)``. Per head,
  ``S`` ``(hd, hd)``: ``S <- diag(exp(g_t)) S; d = beta_t (v_t - S^T
  k_t); S <- S + k_t d^T; o_t = S^T q_t``. ``o <- rms_plain(o) * w`` per
  head, times ``sigmoid(h W_g)_head``, then ``W_o``. No positions.
- latent attention: ``q = h W_q``, per head ``[q_nope | q_pe]`` (no
  query latent, no query norm); ``[c_kv | k_pe] = h W_kva``, ``c_kv =
  rms(c_kv)``; RoPE on ``q_pe`` and ``k_pe`` over ADJACENT pairs
  (``rope_interleave``), plain frequencies ``theta^(-2i/R)``; ``[k_nope
  | v]`` per head ``= c_kv W_kvb``; scores at ``(nope + rope)^-1/2``,
  causal softmax, times ``sigmoid(h W_g)_head``, ``W_o``.
- experts: ``s = sigmoid(h W_r)`` over all router outputs; the choice is
  on ``s + b``: a group's score is the sum of its two largest among its
  ``E / n_group`` experts, the ``topk_group`` best groups stay, the
  top-k of ``s + b`` among their experts are chosen; weights ``s`` (no
  bias) of the chosen over their sum, times ``routed_scaling_factor``;
  plus the shared expert, ungated. The first ``first_k_dense_replace``
  layers held are a dense SwiGLU.

``precision`` "int8" and "fp8" are the CONTROLS: inputs and weights of
every projection that takes bfloat16 operands in the system (the KDA
projections ``W_q W_k W_v W_f W_g W_o``, the latent ones and the
``W_kvb`` expansion, the dense layer, the expert products, the head)
rounded per tensor to 127 levels or to float8 e4m3, and their
cotangents likewise: one step below the bfloat16 operands the
configuration states. ``"bf16_state"`` is the control of the ONE float32
quantity this model adds: the KDA matrix ``S`` rounded to bfloat16 after
every token.

Parameters are two levels deep in the policy's own names and shapes, so
``to_policy_tree`` is the identity and a caller may hand the policy's
arrays in as views. ``init_params`` returns HOST arrays: beside 13 GB of
policy state the chip has no room for a second copy of the weights.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
KDA, LATENT = "kimi_delta_attention", "latent_attention"
# streams whose rebuilt keys, values and scores are alive at once
ATTENTION_STREAMS = 2
# tokens of the recurrence under one checkpoint
TOKENS_A_BLOCK = 16


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


_same = lambda v: v
# (what a product's operands go through, what the KDA matrix goes
# through after every token)
_QUANT = {
    "float32": (_same, _same),
    "int8": (_both_ways(_round_int8), _same),
    "fp8": (_both_ways(_round_fp8), _same),
    # ``reduce_precision``, not two casts: the TPU compiler may drop a
    # cast to bfloat16 and back as "excess precision"
    "bf16_state": (_same, _both_ways(
        lambda s: jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7))),
}


# -- sizes and weights ----------------------------------------------------------


def sizes(config: Dict, num_actions: int) -> Dict:
    c = config
    first, held = c["experts_held"]
    layers = int(c["num_hidden_layers"])
    indices = [int(i) for i in c.get("layer_indices", range(layers))]
    every, dense = int(c["layer_group_size"]), int(c["first_k_dense_replace"])
    z = {
        "D": int(c["hidden_size"]), "V": int(num_actions), "L": layers,
        "kinds": tuple(LATENT if (i + 1) % every == 0 else KDA for i in indices),
        "dense": tuple(n < dense for n in range(layers)),
        "eps": float(c["rms_norm_eps"]),
        "H": int(c["num_attention_heads"]), "hd": int(c["head_dim"]),
        "conv": int(c["short_conv_kernel_size"]), "lower": float(c["kda_lower_bound"]),
        "C": int(c["kv_lora_rank"]),
        "dn": int(c["qk_nope_head_dim"]), "R": int(c["qk_rope_head_dim"]),
        "dv": int(c["v_head_dim"]), "theta": float(c["rope_theta"]),
        "S": int(c["max_position_embeddings"]),
        "R_out": int(c["router_outputs"]), "first": int(first), "E": int(held),
        "groups": int(c["n_group"]), "groups_kept": int(c["topk_group"]),
        "top_k": int(c["num_experts_per_tok"]), "norm_topk": bool(c["norm_topk_prob"]),
        "route_scale": float(c["routed_scaling_factor"]),
        "F": int(c["moe_intermediate_size"]),
        "Fs": int(c["num_shared_experts"]) * int(c["moe_shared_expert_intermediate_size"]),
        "Fd": int(c["intermediate_size"]),
        "T": int(c["algo_config"]["model"]["max_seq_len"]),
    }
    z["row"] = z["C"] + z["R"]
    z["Kd"] = z["H"] * z["hd"]
    return z


def param_shapes(config: Dict, num_actions: int) -> Dict[str, Dict[str, tuple]]:
    z = sizes(config, num_actions)
    d, e, f, fs, h, kd = z["D"], z["E"], z["F"], z["Fs"], z["H"], z["Kd"]
    out = {
        "embed": {"embedding": (z["V"], d)},
        "final_norm": {"weight": (d,)},
        "head": {"kernel": (d, z["V"])},
        "value": {"kernel": (d, 1), "bias": (1,)},
    }
    for i, (kind, dense) in enumerate(zip(z["kinds"], z["dense"])):
        layer = {"input_norm": (d,), "post_norm": (d,), "g_proj": (d, h)}
        if kind == KDA:
            layer.update({
                "q_proj": (d, kd), "k_proj": (d, kd), "v_proj": (d, kd),
                "q_conv": (kd, z["conv"]), "k_conv": (kd, z["conv"]),
                "v_conv": (kd, z["conv"]),
                "f_proj": (d, kd), "A_log": (h,), "dt_bias": (kd,),
                "b_proj": (d, h), "kda_norm": (z["hd"],), "out_proj": (kd, d),
            })
        else:
            layer.update({
                "q_proj": (d, h * (z["dn"] + z["R"])),
                "kv_a": (d, z["row"]), "kv_a_norm": (z["C"],),
                "kv_b": (z["C"], h * (z["dn"] + z["dv"])),
                "o_proj": (h * z["dv"], d),
            })
        if dense:
            layer.update({"mlp_gate": (d, z["Fd"]), "mlp_up": (d, z["Fd"]),
                          "mlp_down": (z["Fd"], d)})
        else:
            layer.update({
                "router": (d, z["R_out"]), "select_bias": (z["R_out"],),
                "experts_gate": (e, d, f), "experts_up": (e, d, f),
                "experts_down": (e, f, d),
                "shared_gate": (d, fs), "shared_up": (d, fs), "shared_down": (fs, d),
            })
        out[f"layer_{i}"] = layer
    return out


def init_params(key, config: Dict, num_actions: int, host: bool = True):
    """Seeded weights, one jitted call a group, brought to the host
    (``host=False``: left on the device, for a check that puts the
    seeded weights back without a copy through the host): matrices
    normal with variance 1 / rows (the output head half the deviation,
    so that a random policy is not near-deterministic), norm weights
    and biases small and not zero (a weight the system dropped would
    otherwise go unseen). ASSUMED, the config states none of it: ``A``
    uniform in (1, 16) as Gated DeltaNet starts it, the convolutions
    0.5 x normal, the head norm's plain weight 1 + 0.1 x normal, the
    selection bias 0.02 x normal (small against scores in (0, 1), and
    enough to change which groups and experts some tokens get)."""
    shapes = param_shapes(config, num_actions)
    # XLA's own bit generator: a threefry stream for 822 M weights is a
    # minute of compiling on the chip, and this is a few seconds
    key = jax.random.wrap_key_data(
        jnp.tile(jax.random.key_data(key).astype(jnp.uint32).ravel(), 2)[:4],
        impl="rbg")

    def make(key, group):
        # ONE draw a group, cut into its leaves: a generator op a leaf
        # is a quarter of a second of compiling each
        leaves = sorted(shapes[group].items())
        sizes_ = [int(np.prod(shape)) for _, shape in leaves]
        draws = jax.random.normal(key, (sum(sizes_),), jnp.float32)
        out, at = {}, 0
        for (leaf, shape), size in zip(leaves, sizes_):
            x = draws[at : at + size]
            # a narrow leaf is cut the other way round and turned: the
            # compiler moves a reshape before the slice, and the whole
            # draw as rows of 4 or 32 numbers is 13 GB of padded tiles
            narrow = len(shape) == 2 and shape[-1] < 128
            x = x.reshape(shape[::-1]).T if narrow else x.reshape(shape)
            at += size
            if leaf == "A_log":
                # uniform in (1, 16) from the normal draw
                x = jnp.log(1.0 + 15.0 * jax.scipy.stats.norm.cdf(x))
            elif leaf == "kda_norm":
                x = 1.0 + 0.1 * x
            elif leaf == "select_bias":
                x = 0.02 * x
            elif len(shape) == 1:
                x = 0.1 * x
            elif leaf.endswith("_conv"):
                x = 0.5 * x
            elif leaf == "embedding":
                pass
            else:
                x = x / np.sqrt(shape[-2])
                if group == "head":
                    x = 0.5 * x
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


def _rms(x, w, eps, centred=True):
    y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return y * ((1.0 + w) if centred else w)


def _mm(x, w, q_):
    return jnp.dot(q_(x), q_(w), precision=HI)


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _rope(x, positions, theta: float):
    """``x`` ``(B, T, H, R)``; ``positions`` ``(B, T)``. Frequency ``i``
    turns the ADJACENT pair ``(x[2 i], x[2 i + 1])`` (the config's
    ``rope_interleave``); ``theta^(-2i/R)``, no scaling."""
    r = x.shape[-1]
    inv = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angle = positions.astype(jnp.float32)[..., None, None] * inv
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    pairs = x.reshape(x.shape[:-1] + (r // 2, 2))
    x1, x2 = pairs[..., 0], pairs[..., 1]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


def state_leaves(z: Dict, rows: int):
    """``[(shape, dtype)]`` of the policy's state tuple, the position
    last: a KDA layer its matrix and three convolution tails, a latent
    layer its rows."""
    out = []
    for kind in z["kinds"]:
        if kind == KDA:
            out.append(((rows, z["H"], z["hd"], z["hd"]), jnp.float32))
            out.extend([((rows, z["conv"] - 1, z["Kd"]), jnp.float32)] * 3)
        else:
            out.append(((rows, z["S"], z["row"]), jnp.bfloat16))
    return out + [((rows,), jnp.int32)]


def initial_state(z: Dict, rows: int):
    return tuple(jnp.zeros(shape, dtype) for shape, dtype in state_leaves(z, rows))


def _positions(pos0, fresh):
    """Each token's position in its episode: a fresh token is at 0."""
    def step(pos, f):
        pos = jnp.where(f, 0, pos)
        return pos + 1, pos

    end, positions = jax.lax.scan(step, pos0, fresh.T)
    return positions.T, end


def _kda(p, x, s0, tails0, fresh, z, q_, qs_):
    """Kimi Delta Attention over a fragment, one token at a time.
    ``tails0``: the last ``conv - 1`` inputs of the three convolutions."""
    b, t, _ = x.shape
    h, hd = z["H"], z["hd"]
    pre = jnp.stack([_mm(x, p[n + "_proj"], q_) for n in "qkv"], axis=2)  # (B, T, 3, Kd)
    kernels = jnp.stack([p[n + "_conv"] for n in "qkv"])  # (3, Kd, conv)
    a = (_mm(x, p["f_proj"], q_) + p["dt_bias"]).reshape(b, t, h, hd)
    g = z["lower"] * jax.nn.sigmoid(jnp.exp(p["A_log"])[:, None] * a)
    beta = jax.nn.sigmoid(jnp.dot(x, p["b_proj"], precision=HI))

    def token(carry, xs):
        s, tails = carry  # (B, H, hd, hd), (B, 3, conv - 1, Kd)
        m_t, g_t, beta_t, f_t = xs
        s = jnp.where(f_t[:, None, None, None], 0.0, s)
        tails = jnp.where(f_t[:, None, None, None], 0.0, tails)
        window = jnp.concatenate([tails, m_t[:, :, None]], axis=2)  # (B, 3, conv, Kd)
        conv = jax.nn.silu(jnp.sum(window * jnp.swapaxes(kernels, 1, 2)[None], axis=2))
        q = _l2norm(conv[:, 0].reshape(b, h, hd)) * (hd ** -0.5)
        k = _l2norm(conv[:, 1].reshape(b, h, hd))
        v = conv[:, 2].reshape(b, h, hd)
        s = s * jnp.exp(g_t)[..., None]  # a row of S a key channel
        read = jnp.einsum("bhkv,bhk->bhv", s, k, precision=HI)
        delta = beta_t[..., None] * (v - read)
        s = qs_(s + k[..., :, None] * delta[..., None, :])
        o = jnp.einsum("bhkv,bhk->bhv", s, q, precision=HI)
        return (s, window[:, :, 1:]), o

    every = TOKENS_A_BLOCK if t % TOKENS_A_BLOCK == 0 else 1

    def seg(x_):  # (B, T, ...) -> (T / every, every, B, ...)
        x_ = jnp.moveaxis(x_, 1, 0)
        return x_.reshape((t // every, every) + x_.shape[1:])

    (s1, tails1), o = jax.lax.scan(
        jax.checkpoint(lambda carry, xs: jax.lax.scan(token, carry, xs)),
        (s0, jnp.stack(tails0, axis=1)),
        (seg(pre), seg(g), seg(beta), seg(fresh)),
    )
    o = jnp.moveaxis(o.reshape((t,) + o.shape[2:]), 0, 1)  # (B, T, H, hd)
    o = _rms(o, p["kda_norm"], z["eps"], centred=False)
    o = o * jax.nn.sigmoid(_mm(x, p["g_proj"], q_))[..., None]
    return _mm(o.reshape(b, t, h * hd), p["out_proj"], q_), (
        s1, tails1[:, 0], tails1[:, 1], tails1[:, 2])


def _attention(p, x, cache, pos0, positions, fresh, z, q_):
    """Latent attention, expanded: every position's key and value are
    rebuilt from its latent row, the full masked score matrix over the
    stored positions and the fragment's own. Returns the output and the
    latent rows after the fragment (float32)."""
    b, t, _ = x.shape
    h, dn, r, dv, c, s_max = z["H"], z["dn"], z["R"], z["dv"], z["C"], z["S"]
    q = _mm(x, p["q_proj"], q_).reshape(b, t, h, dn + r)
    q_nope, q_pe = q[..., :dn], _rope(q[..., dn:], positions, z["theta"])
    kv = _mm(x, p["kv_a"], q_)
    rows = jnp.concatenate([
        _rms(kv[..., :c], p["kv_a_norm"], z["eps"]),
        _rope(kv[:, :, None, c:], positions, z["theta"])[:, :, 0],
    ], axis=-1)  # (B, T, C + R)
    episode = jnp.cumsum(fresh.astype(jnp.int32), axis=1)
    steps = jnp.arange(t)
    scale = (dn + r) ** -0.5

    def some_streams(xs):
        qn, qp, new, old, ep, p0 = xs
        every = jnp.concatenate([old.astype(jnp.float32), new], axis=1)  # (b, S+T, row)
        k_v = _mm(every[..., :c], p["kv_b"], q_).reshape(
            every.shape[:2] + (h, dn + dv))
        k_nope, v, k_pe = k_v[..., :dn], k_v[..., dn:], every[..., c:]
        scores = (
            jnp.einsum("bthd,bshd->bhts", qn, k_nope, precision=HI)
            + jnp.einsum("bthr,bsr->bhts", qp, k_pe, precision=HI)
        ) * scale
        stored = (ep == 0)[:, :, None] & (
            jnp.arange(s_max)[None, None] < p0[:, None, None])
        own = (steps[:, None] >= steps[None, :])[None] & (
            ep[:, :, None] == ep[:, None, :])
        mask = jnp.concatenate([stored, own], axis=-1)[:, None]
        w = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhts,bshv->bthv", w, v, precision=HI)

    # a few streams at a time, each recomputed in the backward pass
    k = ATTENTION_STREAMS if b % ATTENTION_STREAMS == 0 else 1
    args = (q_nope, q_pe, rows, cache, episode, pos0)
    o = jax.lax.map(
        jax.checkpoint(some_streams),
        tuple(a.reshape((b // k, k) + a.shape[1:]) for a in args),
    ).reshape(b, t, h, dv)
    o = o * jax.nn.sigmoid(_mm(x, p["g_proj"], q_))[..., None]

    # the rows after the fragment, written token by token
    def write(cache, xs):
        row_t, pos_t = xs
        return cache.at[jnp.arange(b), pos_t].set(row_t), None

    after, _ = jax.lax.scan(
        write, cache.astype(jnp.float32), (jnp.moveaxis(rows, 1, 0), positions.T))
    return _mm(o.reshape(b, t, h * dv), p["o_proj"], q_), after


def _swiglu(x, wg, wu, wd, q_):
    return _mm(jax.nn.silu(_mm(x, wg, q_)) * _mm(x, wu, q_), wd, q_)


def route(p, flat, z):
    """``(top-k ids (tokens, k), their weights, the groups kept (tokens,
    groups) bool)``: a sigmoid each; on ``s + b`` a group's score is the
    sum of its two largest, the best ``groups_kept`` groups stay and the
    experts of the others are struck out; the top-k of what is left; the
    weights are ``s`` without the bias."""
    scores = jax.nn.sigmoid(jnp.dot(flat, p["router"], precision=HI))
    pick = scores + p["select_bias"]
    groups = z["groups"]
    by_group = pick.reshape(pick.shape[0], groups, -1)
    two_best = jnp.sort(by_group, axis=-1)[..., -2:]
    group_score = jnp.sum(two_best, axis=-1)  # (tokens, groups)
    # the n-th largest group score of each token is the bar
    bar = jnp.sort(group_score, axis=-1)[:, groups - z["groups_kept"]][:, None]
    kept = group_score >= bar
    pick = jnp.where(jnp.repeat(kept, by_group.shape[-1], axis=-1), pick, -jnp.inf)
    _, top_i = jax.lax.top_k(pick, z["top_k"])
    top_w = jnp.take_along_axis(scores, top_i, axis=-1)
    if z["norm_topk"]:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    return top_i, top_w * z["route_scale"], kept


def _experts(p, x, z, q_):
    """The held experts one after another under a dense 0/weight mask;
    the shared expert. Returns the layer's output and each token's
    top-k set."""
    flat = x.reshape(-1, x.shape[-1])
    top_i, top_w, _ = route(p, flat, z)

    def one_expert(acc, xs):
        e, wg, wu, wd = xs
        weight = jnp.sum(jnp.where(top_i == e, top_w, 0.0), axis=-1)
        return acc + weight[:, None] * _swiglu(flat, wg, wu, wd, q_), None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(flat),
        (z["first"] + jnp.arange(z["E"]), p["experts_gate"], p["experts_up"],
         p["experts_down"]),
    )
    shared = _swiglu(flat, p["shared_gate"], p["shared_up"], p["shared_down"], q_)
    return (routed + shared).reshape(x.shape), top_i


def forward(params, tokens, state, fresh, config: Dict, num_actions: int,
            precision: str = "float32"):
    """A fragment from its start state. ``tokens`` ``(B, T)`` int;
    ``state`` as ``initial_state`` (latent rows in any float type);
    ``fresh`` ``(B, T)`` bool (the token opens an episode). Returns
    ``{"logits" (B, T, V), "value" (B, T), "state", "routes" (expert
    layers, B*T, k)}``."""
    z = sizes(config, num_actions)
    q_, qs_ = _QUANT[precision]
    fresh = fresh.astype(bool)
    pos0 = state[-1]
    positions, pos1 = _positions(pos0, fresh)
    x = params["embed"]["embedding"][tokens.astype(jnp.int32)]

    def block(x, p, mine, kind, dense):
        """One layer: ``(x, its state after, its tokens' top-k sets)``."""
        h = _rms(x, p["input_norm"], z["eps"])
        if kind == KDA:
            y, new = _kda(p, h, mine[0], mine[1:], fresh, z, q_, qs_)
        else:
            y, after = _attention(p, h, mine[0], pos0, positions, fresh, z, q_)
            new = (after,)
        x = x + y
        h = _rms(x, p["post_norm"], z["eps"])
        if dense:
            return x + _swiglu(h, p["mlp_gate"], p["mlp_up"], p["mlp_down"], q_), new, None
        y, top_i = _experts(p, h, z, q_)
        return x + y, new, top_i

    state_out, routes, at = [], [], 0
    for i, (kind, dense) in enumerate(zip(z["kinds"], z["dense"])):
        leaves = 4 if kind == KDA else 1
        # a layer's own activations are made again in the backward pass:
        # beside three copies of the weights the chip holds one layer's
        x, new, top_i = jax.checkpoint(block, static_argnums=(3, 4))(
            x, params[f"layer_{i}"], tuple(state[at : at + leaves]), kind, dense)
        at += leaves
        state_out.extend(new)
        if not dense:
            routes.append(top_i)
    state_out.append(pos1)
    feat = _rms(x, params["final_norm"]["weight"], z["eps"])
    logits = _mm(feat, params["head"]["kernel"], q_)
    value = (
        jnp.dot(feat, params["value"]["kernel"], precision=HI)
        + params["value"]["bias"]
    )[..., 0]
    return {"logits": logits, "value": value, "state": tuple(state_out),
            "routes": jnp.stack(routes)}


# -- batches, loss, and the rest of PPO ---------------------------------------------


def make_state(rng: np.random.Generator, z: Dict, rows: int, fragment: int):
    """Seeded start states: streams somewhere inside an episode, with
    the KDA matrix, the convolutions' inputs and the latent rows such an
    episode leaves behind (magnitudes of order one, latent rows rounded
    to bfloat16 as the policy stores them)."""
    pos0 = rng.integers(0, z["S"] - fragment + 1, rows).astype(np.int32)
    pos0[0] = 0  # one stream at its episode's start
    state = []
    for shape, dtype in state_leaves(z, rows)[:-1]:
        leaf = rng.standard_normal(shape, dtype=np.float32)
        state.append(0.1 * leaf if len(shape) == 4 else leaf.astype(dtype))
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
