"""Plain reference of the Xing4.0 block stack as a token-level PPO
policy: ``jax.numpy``, float32, every product at precision "highest",
nothing from ``ray_tpu``.

Written the long way where the system is clever. Latent attention is
the EXPANDED form only: the state holds one latent row a position, and
the keys and values of EVERY position (stored and the fragment's own)
are rebuilt from them through ``W_kvb``, a few streams at a time, under
the full masked score matrix; the query never absorbs ``W_kvb``. The
hyper-connection is one token at a time (``vmap`` of a function of one
``(lanes, hidden)`` stream) with the Sinkhorn rounds a plain loop. The
routed experts are a loop over the HELD experts with a dense 0/weight
mask. The share (``experts_held``, the vocabulary rows) is the
policy's: what the absent experts would add is left out here as there.
Its own GAE, PPO loss, global-norm clip and Adam step are at the end.

Layer equations (the published description; departures are comments
where they occur and ``assumed`` in the configuration file):

- ``rms(x) = x * rsqrt(mean(x^2) + eps) * (1 + w)`` (DEPARTURE: the
  weight is stored zero-centred, as the policy stores every norm; with
  seeded weights a reparametrisation).
- latent attention (DeepSeek-V3 with a query latent): ``c_q = rms(x
  W_qa)``; ``q = c_q W_qb``, per head ``[q_nope | q_pe]``; ``[c_kv |
  k_pe] = x W_kva``, ``c_kv = rms(c_kv)``, ``k_pe = rope(k_pe)`` shared
  by all heads; ``[k_nope | v]`` per head ``= c_kv W_kvb``; ``score =
  (q_nope . k_nope + rope(q_pe) . k_pe) * s``, causal softmax, ``o = P
  v``, ``W_o``. ``s = (nope + rope)^-1/2 * m^2``, ``m = 0.1 *
  mscale_all_dim * ln(factor) + 1``. YaRN: the inverse frequencies
  blend ``theta^(-2i/d)`` and that over ``factor`` by the linear ramp
  between the two correction dimensions; ``mscale = mscale_all_dim``,
  so cos and sin carry factor 1. RoPE on the ``[first half | second
  half]`` layout.
- residual (manifold-constrained hyper-connections, arXiv:2512.24880):
  ``x' = rms(vec(X))``; ``H~ = a (x' phi) + b`` for pre ``(1 x n)``,
  post ``(1 x n)`` and res ``(n x n)``; ``H_pre = sigmoid``, ``H_post =
  2 sigmoid``, ``H_res`` = ``exp(clamp(H~_res))`` through
  ``hc_sinkhorn_iters`` rounds of (each column over its sum + eps, each
  row over its sum + eps); ``X <- H_res X + H_post^T F(H_pre X)``, ``F``
  with its own RMSNorm. The embedding is copied into the lanes; the
  lanes are summed before the final norm.
- experts: ``s = sigmoid(x W_r)`` over all router outputs; the top-k of
  ``s + bias`` are chosen; weights ``s`` (no bias) over their sum times
  ``routed_scaling_factor``; plus the shared expert, ungated. The first
  ``first_k_dense_replace`` layers are a dense SwiGLU.

``precision`` "int8" and "fp8" are the CONTROLS: inputs and weights of
every projection (the ``W_kvb`` expansion among them), expert product,
the dense layer and the head rounded per tensor to 127 levels or to
float8 e4m3, and their cotangents likewise: one step below the bfloat16
operands the configuration states.

Parameters are two levels deep in the policy's own names and shapes
(the policy's stream is flat, lane ``i`` at ``[i D, (i + 1) D)``, so
``hc_*_norm`` and the rows of ``hc_*_phi`` are in that order), so
``to_policy_tree`` is the identity and a caller may hand the policy's
arrays in as views. ``init_params`` returns HOST arrays: beside 12 GB
of policy state the chip has no room for a second copy of the weights.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
# streams whose keys, values and scores are alive at once
ATTENTION_STREAMS = 2


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


def sizes(config: Dict, num_actions: int) -> Dict:
    c = config
    first, held = c["experts_held"]
    layers = int(c["num_hidden_layers"])
    dense = int(c["first_k_dense_replace"])
    z = {
        "D": int(c["hidden_size"]), "V": int(num_actions), "L": layers,
        "dense": tuple(i < dense for i in range(layers)),
        "eps": float(c["rms_norm_eps"]),
        "H": int(c["num_attention_heads"]),
        "Cq": int(c["q_lora_rank"]), "C": int(c["kv_lora_rank"]),
        "dn": int(c["qk_nope_head_dim"]), "R": int(c["qk_rope_head_dim"]),
        "dv": int(c["v_head_dim"]),
        "theta": float(c["rope_theta"]), "yarn": dict(c["rope_scaling"]),
        "S": int(c["max_position_embeddings"]),
        "n": int(c["hc_mult"]), "rounds": int(c["hc_sinkhorn_iters"]),
        "hc_eps": float(c["hc_eps"]),
        "lo": float(c["mhc_h_res_clamp_min"]), "hi": float(c["mhc_h_res_clamp_max"]),
        "R_out": int(c["router_outputs"]), "first": int(first), "E": int(held),
        "top_k": int(c["num_experts_per_tok"]), "norm_topk": bool(c["norm_topk_prob"]),
        "route_scale": float(c["routed_scaling_factor"]),
        "F": int(c["moe_intermediate_size"]),
        "Fs": int(c["n_shared_experts"]) * int(c["moe_intermediate_size"]),
        "Fd": int(c["intermediate_size"]),
        "T": int(c["algo_config"]["model"]["max_seq_len"]),
    }
    z["row"] = z["C"] + z["R"]
    return z


def param_shapes(config: Dict, num_actions: int) -> Dict[str, Dict[str, tuple]]:
    z = sizes(config, num_actions)
    d, e, f, fs, n, h = z["D"], z["E"], z["F"], z["Fs"], z["n"], z["H"]
    out = {
        "embed": {"embedding": (z["V"], d)},
        "final_norm": {"weight": (d,)},
        "head": {"kernel": (d, z["V"])},
        "value": {"kernel": (d, 1), "bias": (1,)},
    }
    for i, dense in enumerate(z["dense"]):
        layer = {
            "input_norm": (d,), "post_norm": (d,),
            "q_a": (d, z["Cq"]), "q_a_norm": (z["Cq"],),
            "q_b": (z["Cq"], h * (z["dn"] + z["R"])),
            "kv_a": (d, z["row"]), "kv_a_norm": (z["C"],),
            "kv_b": (z["C"], h * (z["dn"] + z["dv"])),
            "o_proj": (h * z["dv"], d),
        }
        for sub in ("mixer", "ffn"):
            layer.update({
                f"hc_{sub}_norm": (n * d,), f"hc_{sub}_phi": (n * d, 2 * n + n * n),
                f"hc_{sub}_a": (3,), f"hc_{sub}_b": (2 * n + n * n,),
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
    otherwise go unseen). ASSUMED, the config states none of it: the
    selection bias 0.02 x normal (small against scores in (0, 1), and
    enough to change which experts some tokens get); a hyper-connection's
    ``a`` uniform in (0.01, 0.1), ``b_pre`` and ``b_post`` 0.1 x normal,
    ``b_res`` the identity + 0.1 x normal, so that ``H_res`` is neither
    uniform nor a permutation (its diagonal comes out near 0.45)."""
    shapes = param_shapes(config, num_actions)
    n = int(config["hc_mult"])
    # XLA's own bit generator: a threefry stream for 759 M weights is a
    # minute of compiling on the chip, and this is a few seconds
    key = jax.random.wrap_key_data(
        jnp.tile(jax.random.key_data(key).astype(jnp.uint32).ravel(), 2)[:4],
        impl="rbg")

    def make(key, group):
        # ONE draw a group, cut into its leaves: a generator op a leaf
        # (37 in a layer) is a quarter of a second of compiling each
        leaves = sorted(shapes[group].items())
        sizes_ = [int(np.prod(shape)) for _, shape in leaves]
        draws = jax.random.normal(key, (sum(sizes_),), jnp.float32)
        out, at = {}, 0
        for (leaf, shape), size in zip(leaves, sizes_):
            x = draws[at : at + size].reshape(shape)
            at += size
            if leaf.startswith("hc_") and leaf.endswith("_a"):
                # uniform in (0.01, 0.1) from the normal draw
                x = 0.01 + 0.09 * jax.scipy.stats.norm.cdf(x)
            elif leaf.startswith("hc_") and leaf.endswith("_b"):
                x = 0.1 * x + jnp.concatenate(
                    [jnp.zeros((2 * n,)), jnp.eye(n).ravel()])
            elif leaf == "select_bias":
                x = 0.02 * x
            elif len(shape) == 1:
                x = 0.1 * x
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


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * (
        1.0 + w)


def _mm(x, w, q_):
    return jnp.dot(q_(x), q_(w), precision=HI)


def yarn_inv_freq(dim: int, theta: float, yarn: Dict):
    """``theta^(-2i/dim)`` blended with that over ``factor``: below the
    correction dimension of ``beta_fast`` rotations the plain frequency,
    above that of ``beta_slow`` the interpolated one, a linear ramp
    between (DeepSeek-V3's ``DeepseekV3YarnRotaryEmbedding``)."""
    factor = float(yarn["factor"])
    original = float(yarn["original_max_position_embeddings"])
    out = []
    for i in range(dim // 2):
        plain = theta ** (-2.0 * i / dim)

        def correction(rotations):
            return dim * math.log(original / (rotations * 2 * math.pi)) / (
                2 * math.log(theta))

        low = max(math.floor(correction(float(yarn["beta_fast"]))), 0)
        high = min(math.ceil(correction(float(yarn["beta_slow"]))), dim - 1)
        if low == high:
            high += 0.001
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(plain / factor * ramp + plain * (1.0 - ramp))
    return np.asarray(out, np.float32)


def softmax_scale(z: Dict) -> float:
    yarn = z["yarn"]
    m = 0.1 * float(yarn["mscale_all_dim"]) * math.log(float(yarn["factor"])) + 1.0
    return (z["dn"] + z["R"]) ** -0.5 * m * m


def _rope(x, positions, inv_freq):
    """``x`` ``(B, T, H, R)``; ``positions`` ``(B, T)``; the ``[first
    half | second half]`` layout (the config has no ``rope_interleave``)."""
    half = x.shape[-1] // 2
    angle = positions.astype(jnp.float32)[..., None, None] * inv_freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def initial_state(z: Dict, rows: int):
    """One leaf of latent rows a layer (float32 here) and the position."""
    state = [jnp.zeros((rows, z["S"], z["row"]), jnp.float32) for _ in range(z["L"])]
    state.append(jnp.zeros((rows,), jnp.int32))
    return tuple(state)


def _positions(pos0, fresh):
    """Each token's position in its episode: a fresh token is at 0."""
    def step(pos, f):
        pos = jnp.where(f, 0, pos)
        return pos + 1, pos

    end, positions = jax.lax.scan(step, pos0, fresh.T)
    return positions.T, end


def _attention(p, x, cache, pos0, positions, fresh, z, q_):
    """Latent attention, expanded: every position's key and value are
    rebuilt from its latent row, the full masked score matrix over the
    stored positions and the fragment's own. Returns the output and the
    latent rows after the fragment (float32)."""
    b, t, _ = x.shape
    h, dn, r, dv, c, s_max = z["H"], z["dn"], z["R"], z["dv"], z["C"], z["S"]
    inv = yarn_inv_freq(r, z["theta"], z["yarn"])
    c_q = _rms(_mm(x, p["q_a"], q_), p["q_a_norm"], z["eps"])
    q = _mm(c_q, p["q_b"], q_).reshape(b, t, h, dn + r)
    q_nope, q_pe = q[..., :dn], _rope(q[..., dn:], positions, inv)
    kv = _mm(x, p["kv_a"], q_)
    rows = jnp.concatenate([
        _rms(kv[..., :c], p["kv_a_norm"], z["eps"]),
        _rope(kv[:, :, None, c:], positions, inv)[:, :, 0],
    ], axis=-1)  # (B, T, C + R)
    episode = jnp.cumsum(fresh.astype(jnp.int32), axis=1)
    steps = jnp.arange(t)
    scale = softmax_scale(z)

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

    # a few streams at a time, each recomputed in the backward pass:
    # 2,176 rebuilt keys and values of 32 heads are 71 MB a stream
    k = ATTENTION_STREAMS if b % ATTENTION_STREAMS == 0 else 1
    args = (q_nope, q_pe, rows, cache, episode, pos0)
    o = jax.lax.map(
        jax.checkpoint(some_streams),
        tuple(a.reshape((b // k, k) + a.shape[1:]) for a in args),
    ).reshape(b, t, h * dv)

    # the rows after the fragment, written token by token
    def write(cache, xs):
        row_t, pos_t = xs
        return cache.at[jnp.arange(b), pos_t].set(row_t), None

    after, _ = jax.lax.scan(
        write, cache.astype(jnp.float32), (jnp.moveaxis(rows, 1, 0), positions.T))
    return _mm(o, p["o_proj"], q_), after


def sinkhorn(logits, z: Dict):
    """``(n, n)``: the rounds as a plain loop (``lax.scan``, so that it
    can be differentiated; nothing is unrolled)."""

    def one_round(m, _):
        m = m / (jnp.sum(m, axis=0, keepdims=True) + z["hc_eps"])  # each column
        return m / (jnp.sum(m, axis=1, keepdims=True) + z["hc_eps"]), None  # each row

    m, _ = jax.lax.scan(
        one_round, jnp.exp(jnp.clip(logits, z["lo"], z["hi"])), None,
        length=z["rounds"])
    return m


def hyper_maps(p, sub: str, stream, z: Dict):
    """One token's ``(H_pre (n,), H_post (n,), H_res (n, n))`` from its
    stream ``(n, D)``."""
    n = z["n"]
    flat = _rms(stream.reshape(-1), p[f"hc_{sub}_norm"], z["eps"])
    h = jnp.dot(flat, p[f"hc_{sub}_phi"], precision=HI)
    a, b = p[f"hc_{sub}_a"], p[f"hc_{sub}_b"]
    pre = jax.nn.sigmoid(a[0] * h[:n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * h[n : 2 * n] + b[n : 2 * n])
    res = sinkhorn(a[2] * h[2 * n :].reshape(n, n) + b[2 * n :].reshape(n, n), z)
    return pre, post, res


def _hyper(p, sub: str, streams, f, z: Dict):
    """``X <- H_res X + H_post^T F(H_pre X)`` for every token of
    ``streams`` ``(B, T, n, D)``; ``f`` maps ``(B, T, D)`` and may
    return more beside its output."""
    per_token = jax.vmap(jax.vmap(lambda s: hyper_maps(p, sub, s, z)))
    pre, post, res = per_token(streams)
    out = f(jnp.einsum("btn,btnd->btd", pre, streams, precision=HI))
    y = out[0]
    mixed = jnp.einsum("btnm,btmd->btnd", res, streams, precision=HI)
    return mixed + post[..., None] * y[:, :, None, :], out[1]


def _swiglu(x, wg, wu, wd, q_):
    return _mm(jax.nn.silu(_mm(x, wg, q_)) * _mm(x, wu, q_), wd, q_)


def _experts(p, x, z, q_):
    """Router over all outputs (a sigmoid each; the bias picks, the
    scores weigh); the held experts one after another under a dense
    0/weight mask; the shared expert. Returns the layer's output and
    each token's top-k set."""
    flat = x.reshape(-1, x.shape[-1])
    scores = jax.nn.sigmoid(jnp.dot(flat, p["router"], precision=HI))
    _, top_i = jax.lax.top_k(scores + p["select_bias"], z["top_k"])
    top_w = jnp.take_along_axis(scores, top_i, axis=-1)
    if z["norm_topk"]:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    top_w = top_w * z["route_scale"]

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
    z, q_ = sizes(config, num_actions), _QUANT[precision]
    fresh = fresh.astype(bool)
    pos0 = state[-1]
    positions, pos1 = _positions(pos0, fresh)
    x = params["embed"]["embedding"][tokens.astype(jnp.int32)]
    streams = jnp.repeat(x[:, :, None], z["n"], axis=2)  # copied into the lanes

    def layer(streams, p, cache, dense):
        def mixer(h):
            return _attention(
                p, _rms(h, p["input_norm"], z["eps"]), cache, pos0, positions,
                fresh, z, q_)

        def feed_forward(h):
            h = _rms(h, p["post_norm"], z["eps"])
            if dense:
                return _swiglu(h, p["mlp_gate"], p["mlp_up"], p["mlp_down"], q_), None
            return _experts(p, h, z, q_)

        streams, after = _hyper(p, "mixer", streams, mixer, z)
        streams, top_i = _hyper(p, "ffn", streams, feed_forward, z)
        return streams, after, top_i

    state_out, routes = [], []
    for i, dense in enumerate(z["dense"]):
        streams, after, top_i = layer(streams, params[f"layer_{i}"], state[i], dense)
        state_out.append(after)
        if not dense:
            routes.append(top_i)
    state_out.append(pos1)
    feat = _rms(jnp.sum(streams, axis=2), params["final_norm"]["weight"], z["eps"])
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
    the latent rows such an episode leaves behind (magnitudes of order
    one, rounded to bfloat16 as the policy stores them)."""
    pos0 = rng.integers(0, z["S"] - fragment + 1, rows).astype(np.int32)
    pos0[0] = 0  # one stream at its episode's start
    state = [
        rng.standard_normal((rows, z["S"], z["row"]), dtype=np.float32)
        .astype(jnp.bfloat16)
        for _ in range(z["L"])
    ]
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
