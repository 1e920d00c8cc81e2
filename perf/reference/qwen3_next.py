"""Plain reference of the Qwen3-Next block stack as a token-level PPO
policy: ``jax.numpy``, float32, every product at precision "highest",
nothing from ``ray_tpu``.

Written token by token where the system is clever: Gated DeltaNet and
its convolution are ONE ``lax.scan`` over the tokens of a fragment (the
recurrence of the model card, state in and state out), attention is
the full masked score matrix over the stored keys and the fragment's
own, the routed experts are a loop over the HELD experts with a dense
0/weight mask. The share (``experts_held``, the vocabulary rows) is the
policy's: what the absent experts would add is left out here as there.
Its own GAE, PPO loss, global-norm clip and Adam step are at the end.

Layer equations (Hugging Face ``qwen3_next``; departures are listed in
the configuration file's ``assumed``):

- ``rms(x) = x * rsqrt(mean(x^2) + eps) * (1 + w)``; block ``h = x +
  mixer(rms(x))``, ``y = h + moe(rms(h))``; layer ``i`` is full
  attention when ``(i + 1) % full_attention_interval == 0``.
- gated attention: ``q, gate`` per head from one projection, ``k, v``
  for the KV heads; ``rms`` of ``q`` and ``k`` over the head; RoPE
  (rotate-half) on the first ``partial_rotary_factor`` of the head;
  causal softmax at ``head_dim^-1/2``; ``(attn * sigmoid(gate)) Wo``.
- Gated DeltaNet: ``q, k, v, z`` and ``b, a`` from two projections; a
  causal depthwise convolution over ``(q, k, v)`` then SiLU; ``beta =
  sigmoid(b)``, ``g = -exp(A_log) * softplus(a + dt_bias)``; ``q, k``
  L2-normalised, ``q`` scaled by ``dk^-1/2``, each key head serving
  ``Hv / Hk`` value heads; ``S <- exp(g) S``, ``d = beta (v - S^T k)``,
  ``S <- S + k d^T``, ``o = S^T q``; ``rms_plain(o) * w * silu(z)``.
- experts: softmax over all router outputs, top-k, renormalised; the
  held experts' ``(silu(x Wg) * (x Wu)) Wd`` under those weights, plus
  the shared expert times ``sigmoid(x w_s)``.

``precision`` "int8" and "fp8" are the CONTROLS: inputs and weights of
every projection, expert product and the head rounded per tensor to
127 levels or to float8 e4m3, and their cotangents likewise: one step
below the bfloat16 operands the configuration states.

Parameters are two levels deep, ``{"layer_0": {"in_proj_qkvz": ...}}``,
in the policy's own names and shapes, so ``to_policy_tree`` is the
identity and a caller may hand the policy's arrays in as views.
``init_params`` returns HOST arrays: beside 10 GB of policy state the
chip has no room for a second copy of the weights.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
LINEAR, FULL = "linear_attention", "full_attention"


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
    every = int(c["full_attention_interval"])
    layers = int(c["num_hidden_layers"])
    first, held = c["experts_held"]
    z = {
        "D": int(c["hidden_size"]), "V": int(num_actions),
        "kinds": tuple(
            FULL if (i + 1) % every == 0 else LINEAR for i in range(layers)
        ),
        "eps": float(c["rms_norm_eps"]),
        "H": int(c["num_attention_heads"]), "Hkv": int(c["num_key_value_heads"]),
        "hd": int(c["head_dim"]),
        "theta": float(c["rope_theta"]),
        "S": int(c["max_position_embeddings"]),
        "Hk": int(c["linear_num_key_heads"]), "Hv": int(c["linear_num_value_heads"]),
        "dk": int(c["linear_key_head_dim"]), "dv": int(c["linear_value_head_dim"]),
        "conv": int(c["linear_conv_kernel_dim"]),
        "R": int(c["router_outputs"]), "first": int(first), "E": int(held),
        "top_k": int(c["num_experts_per_tok"]), "norm_topk": bool(c["norm_topk_prob"]),
        "F": int(c["moe_intermediate_size"]),
        "Fs": int(c["shared_expert_intermediate_size"]),
        "T": int(c["algo_config"]["model"]["max_seq_len"]),
    }
    z["rotary"] = int(z["hd"] * float(c["partial_rotary_factor"]))
    z["Kd"], z["Vd"] = z["Hk"] * z["dk"], z["Hv"] * z["dv"]
    z["C"] = 2 * z["Kd"] + z["Vd"]
    return z


def param_shapes(config: Dict, num_actions: int) -> Dict[str, Dict[str, tuple]]:
    z = sizes(config, num_actions)
    d, e, f, fs = z["D"], z["E"], z["F"], z["Fs"]
    out = {
        "embed": {"embedding": (z["V"], d)},
        "final_norm": {"weight": (d,)},
        "head": {"kernel": (d, z["V"])},
        "value": {"kernel": (d, 1), "bias": (1,)},
    }
    for i, kind in enumerate(z["kinds"]):
        layer = {
            "input_norm": (d,), "post_norm": (d,),
            "router": (d, z["R"]),
            "experts_gate": (e, d, f), "experts_up": (e, d, f),
            "experts_down": (e, f, d),
            "shared_gate": (d, fs), "shared_up": (d, fs), "shared_down": (fs, d),
            "shared_expert_gate": (d, 1),
        }
        if kind == LINEAR:
            layer.update({
                "in_proj_qkvz": (d, 2 * z["Kd"] + 2 * z["Vd"]),
                "in_proj_ba": (d, 2 * z["Hv"]),
                "conv": (z["C"], z["conv"]),
                "A_log": (z["Hv"],), "dt_bias": (z["Hv"],),
                "gdn_norm": (z["dv"],),
                "out_proj": (z["Vd"], d),
            })
        else:
            layer.update({
                "q_proj": (d, z["H"] * z["hd"] * 2),
                "k_proj": (d, z["Hkv"] * z["hd"]),
                "v_proj": (d, z["Hkv"] * z["hd"]),
                "o_proj": (z["H"] * z["hd"], d),
                "q_norm": (z["hd"],), "k_norm": (z["hd"],),
            })
        out[f"layer_{i}"] = layer
    return out


def init_params(key, config: Dict, num_actions: int, host: bool = True):
    """Seeded weights, one jitted call a group, brought to the host
    (``host=False``: left on the device, for a check that puts the
    seeded weights back without a copy through the host):
    matrices normal with variance 1 / rows (the output head a quarter
    of that, so that a random policy is not near-deterministic), norm
    weights and biases small and not zero (a weight the system dropped
    would otherwise go unseen), ``A`` uniform in (1, 16), ``dt_bias``
    near one."""
    shapes = param_shapes(config, num_actions)
    # XLA's own bit generator: a threefry stream for 626 M weights is a
    # minute of compiling on the chip, and this is a few seconds
    key = jax.random.wrap_key_data(
        jnp.tile(jax.random.key_data(key).astype(jnp.uint32).ravel(), 2)[:4],
        impl="rbg")

    def make(key, group):
        out = {}
        for n, (leaf, shape) in enumerate(sorted(shapes[group].items())):
            k = jax.random.fold_in(key, n)
            x = jax.random.normal(k, shape, jnp.float32)
            if leaf == "A_log":
                x = jnp.log(jax.random.uniform(k, shape, minval=1.0, maxval=16.0))
            elif leaf == "dt_bias":
                x = 1.0 + 0.1 * x
            elif leaf == "gdn_norm":
                x = 1.0 + 0.1 * x
            elif len(shape) == 1:
                x = 0.1 * x
            elif leaf == "embedding":
                pass
            elif leaf == "conv":
                x = x * 0.5
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


def _rope(x, positions, rotary, theta):
    """``x`` ``(B, T, H, D)``; ``positions`` ``(B, T)``."""
    half = rotary // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rotary)
    angle = positions.astype(jnp.float32)[..., None, None] * inv
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half], x[..., half:rotary]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rotary:]], axis=-1
    )


def initial_state(z: Dict, rows: int):
    """The policy's state layout, float32 where the policy's is."""
    state = []
    for kind in z["kinds"]:
        if kind == LINEAR:
            state.append(jnp.zeros((rows, z["Hv"], z["dk"], z["dv"]), jnp.float32))
            state.append(jnp.zeros((rows, z["conv"] - 1, z["C"]), jnp.float32))
        else:
            shape = (rows, z["S"], z["Hkv"] * z["hd"])
            state.append(jnp.zeros(shape, jnp.bfloat16))
            state.append(jnp.zeros(shape, jnp.bfloat16))
    state.append(jnp.zeros((rows,), jnp.int32))
    return tuple(state)


def _positions(pos0, fresh):
    """Each token's position in its episode: a fresh token is at 0."""
    def step(pos, f):
        pos = jnp.where(f, 0, pos)
        return pos + 1, pos

    end, positions = jax.lax.scan(step, pos0, fresh.T)
    return positions.T, end


def _delta_net(p, x, s0, tail0, fresh, z, q_):
    """Gated DeltaNet over a fragment, one token at a time."""
    b, t, _ = x.shape
    kd, vd, hv, hk = z["Kd"], z["Vd"], z["Hv"], z["Hk"]
    qkvz = _mm(x, p["in_proj_qkvz"], q_)
    mixed, gate_z = qkvz[..., : 2 * kd + vd], qkvz[..., 2 * kd + vd :]
    ba = jnp.dot(x, p["in_proj_ba"], precision=HI)
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., hv:] + p["dt_bias"])
    rep = hv // hk

    def token(carry, xs):
        s, tail = carry
        m_t, g_t, beta_t, f_t = xs
        s = jnp.where(f_t[:, None, None, None], 0.0, s)
        tail = jnp.where(f_t[:, None, None], 0.0, tail)
        window = jnp.concatenate([tail, m_t[:, None]], axis=1)  # (B, conv, C)
        conv = jax.nn.silu(jnp.sum(window * p["conv"].T[None], axis=1))
        q = conv[:, :kd].reshape(b, hk, z["dk"])
        k = conv[:, kd : 2 * kd].reshape(b, hk, z["dk"])
        v = conv[:, 2 * kd :].reshape(b, hv, z["dv"])
        q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6)
        k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
        q = jnp.repeat(q, rep, axis=1) * (z["dk"] ** -0.5)
        k = jnp.repeat(k, rep, axis=1)
        s = s * jnp.exp(g_t)[..., None, None]
        read = jnp.einsum("bhkv,bhk->bhv", s, k, precision=HI)
        delta = beta_t[..., None] * (v - read)
        s = s + k[..., :, None] * delta[..., None, :]
        o = jnp.einsum("bhkv,bhk->bhv", s, q, precision=HI)
        return (s, window[:, 1:]), o

    every = 16 if t % 16 == 0 else 1

    def some_tokens(carry, xs):
        return jax.lax.scan(token, carry, xs)

    def seg(x_):  # (B, T, ...) -> (T / every, every, B, ...)
        x_ = jnp.moveaxis(x_, 1, 0)
        return x_.reshape((t // every, every) + x_.shape[1:])

    (s1, tail1), o = jax.lax.scan(
        jax.checkpoint(some_tokens), (s0, tail0),
        (seg(mixed), seg(g), seg(beta), seg(fresh)),
    )
    o = jnp.moveaxis(o.reshape((t,) + o.shape[2:]), 0, 1)  # (B, T, Hv, dv)
    o = _rms(o, p["gdn_norm"], z["eps"], centred=False)
    o = o * jax.nn.silu(gate_z.reshape(b, t, hv, z["dv"]))
    return _mm(o.reshape(b, t, vd), p["out_proj"], q_), (s1, tail1)


def _attention(p, x, k_cache, v_cache, pos0, positions, fresh, z, q_):
    """Gated attention over the stored keys and the fragment's own:
    the full masked score matrix."""
    b, t, _ = x.shape
    h, hkv, d, s_max = z["H"], z["Hkv"], z["hd"], z["S"]
    qg = _mm(x, p["q_proj"], q_).reshape(b, t, h, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = _mm(x, p["k_proj"], q_).reshape(b, t, hkv, d)
    v = _mm(x, p["v_proj"], q_).reshape(b, t, hkv, d)
    q = _rope(_rms(q, p["q_norm"], z["eps"]), positions, z["rotary"], z["theta"])
    k = _rope(_rms(k, p["k_norm"], z["eps"]), positions, z["rotary"], z["theta"])
    episode = jnp.cumsum(fresh.astype(jnp.int32), axis=1)  # (B, T)
    stored_shape = (b, s_max, hkv, d)  # a cache row is kv heads x head, flat
    keys = jnp.concatenate(
        [k_cache.astype(jnp.float32).reshape(stored_shape), k], axis=1)
    values = jnp.concatenate(
        [v_cache.astype(jnp.float32).reshape(stored_shape), v], axis=1)
    keys = jnp.repeat(keys, h // hkv, axis=2)
    values = jnp.repeat(values, h // hkv, axis=2)
    scores = jnp.einsum("bthd,bshd->bhts", q, keys, precision=HI) * (d ** -0.5)
    steps = jnp.arange(t)
    stored = (episode == 0)[:, :, None] & (
        jnp.arange(s_max)[None, None] < pos0[:, None, None]
    )
    own = (steps[:, None] >= steps[None, :])[None] & (
        episode[:, :, None] == episode[:, None, :]
    )
    mask = jnp.concatenate([stored, own], axis=-1)[:, None]  # (B, 1, T, S+T)
    w = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("bhts,bshd->bthd", w, values, precision=HI)
    o = o * jax.nn.sigmoid(gate)

    # the cache after the fragment, written token by token
    def write(caches, xs):
        kc, vc = caches
        k_t, v_t, pos_t = xs
        rows = jnp.arange(b)
        return (
            kc.at[rows, pos_t].set(k_t.reshape(b, -1).astype(kc.dtype)),
            vc.at[rows, pos_t].set(v_t.reshape(b, -1).astype(vc.dtype)),
        ), None

    (k1, v1), _ = jax.lax.scan(
        write, (k_cache, v_cache),
        (jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0), positions.T),
    )
    return _mm(o.reshape(b, t, h * d), p["o_proj"], q_), (k1, v1)


def _experts(p, x, z, q_):
    """Router over all outputs; the held experts one after another
    under a dense 0/weight mask; the shared expert. Returns the
    layer's output and each token's top-k set."""
    flat = x.reshape(-1, x.shape[-1])
    logits = jnp.dot(flat, p["router"], precision=HI)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, z["top_k"])
    if z["norm_topk"]:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)

    def one_expert(acc, xs):
        e, wg, wu, wd = xs
        weight = jnp.sum(jnp.where(top_i == e, top_w, 0.0), axis=-1)
        hidden = jax.nn.silu(_mm(flat, wg, q_)) * _mm(flat, wu, q_)
        return acc + weight[:, None] * _mm(hidden, wd, q_), None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(flat),
        (z["first"] + jnp.arange(z["E"]), p["experts_gate"], p["experts_up"],
         p["experts_down"]),
    )
    hidden = jax.nn.silu(_mm(flat, p["shared_gate"], q_)) * _mm(flat, p["shared_up"], q_)
    shared = _mm(hidden, p["shared_down"], q_) * jax.nn.sigmoid(
        jnp.dot(flat, p["shared_expert_gate"], precision=HI)
    )
    return (routed + shared).reshape(x.shape), top_i


def forward(params, tokens, state, fresh, config: Dict, num_actions: int,
            precision: str = "float32"):
    """A fragment from its start state. ``tokens`` ``(B, T)`` int;
    ``state`` as ``initial_state``; ``fresh`` ``(B, T)`` bool (the token
    opens an episode). Returns ``{"logits" (B, T, V), "value" (B, T),
    "state", "routes" (layers, B*T, k)}``."""
    z, q_ = sizes(config, num_actions), _QUANT[precision]
    fresh = fresh.astype(bool)
    pos0 = state[-1]
    positions, pos1 = _positions(pos0, fresh)
    x = params["embed"]["embedding"][tokens.astype(jnp.int32)]
    state_out, routes = [], []
    for i, kind in enumerate(z["kinds"]):
        p = params[f"layer_{i}"]
        xn = _rms(x, p["input_norm"], z["eps"])
        a, b_ = state[2 * i], state[2 * i + 1]
        if kind == LINEAR:
            y, new = _delta_net(p, xn, a, b_, fresh, z, q_)
        else:
            y, new = _attention(p, xn, a, b_, pos0, positions, fresh, z, q_)
        state_out.extend(new)
        x = x + y
        y, top_i = _experts(p, _rms(x, p["post_norm"], z["eps"]), z, q_)
        routes.append(top_i)
        x = x + y
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
    the DeltaNet matrix, the convolution's inputs and the stored keys
    and values such an episode leaves behind (magnitudes of order one,
    cache entries rounded to bfloat16 as the policy stores them)."""
    state = []
    pos0 = rng.integers(0, z["S"] - fragment + 1, rows).astype(np.int32)
    pos0[0] = 0  # one stream at its episode's start
    for kind in z["kinds"]:
        if kind == LINEAR:
            state.append(
                (0.1 * rng.standard_normal((rows, z["Hv"], z["dk"], z["dv"])))
                .astype(np.float32)
            )
            state.append(
                rng.standard_normal((rows, z["conv"] - 1, z["C"])).astype(np.float32)
            )
        else:
            shape = (rows, z["S"], z["Hkv"] * z["hd"])
            for _ in range(2):
                state.append(
                    rng.standard_normal(shape, dtype=np.float32).astype(jnp.bfloat16)
                )
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
