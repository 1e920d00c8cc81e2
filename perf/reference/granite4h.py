"""Plain reference of the Granite 4.0-H block stack (Hugging Face
``granitemoehybrid`` with no expert layer) as a token-level PPO policy:
``jax.numpy``, float32, every product at precision "highest", nothing
from ``ray_tpu``.

Written the long way where the system is clever. The state-space layer
is the recurrence ONE TOKEN AT A TIME under ``lax.scan`` (convolution
window, decay, rank-one write, read), never in chunks; a new episode
zeroes the matrix and the window before its first token. Attention is
the full masked score matrix over every stored position and the
fragment's own, a few streams at a time; there is no cache logic beyond
"the rows below the position are the episode so far". The layers of a
run are a ``lax.scan`` over the stacked leaves (nine layers written out
compile for minutes). The output head is the embedding itself. Its own GAE, PPO loss, global-norm clip and Adam
step are at the end.

Layer equations (the published description; departures are comments
where they occur and ``assumed`` in the configuration file):

- ``x0 = embedding_multiplier * E[token]``; a layer is ``x <- x +
  residual_multiplier * mixer(rms(x))`` then ``x <- x +
  residual_multiplier * mlp(rms(x))`` with ``mlp(h) = (silu(h W_g) * (h
  W_u)) W_d`` of width ``shared_intermediate_size``; logits ``= rms(x)
  E^T / logits_scaling``.
- ``rms(x) = x * rsqrt(mean(x^2) + eps) * (1 + w)`` (DEPARTURE: the
  weight is stored zero-centred, as the policy stores every norm; with
  seeded weights a reparametrisation).
- ``mamba`` (Mamba-2, arXiv:2405.21060): ``[z | x | B | C | dt] = h
  W_in``; ``(x, B, C) <- silu(conv(x, B, C) + b)``, causal, depthwise,
  width ``mamba_d_conv``; ``dt = softplus(dt + dt_bias)``, ``A =
  -exp(A_log)``; per head ``S <- exp(dt A) S + dt x B^T``, ``y = S C + D
  x``; ``y <- rms(y * silu(z))`` over the whole inner width; ``y W_out``.
- ``attention``: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` key/value heads, no biases, NO positions,
  ``softmax(q k^T * attention_multiplier)`` causal, ``o W_o``.

``precision`` "int8" and "fp8" are the CONTROLS: inputs and weights of
every projection (``W_in``, ``W_out``, q/k/v/o), the feed-forward and
the head rounded per tensor to 127 levels or to float8 e4m3, and their
cotangents likewise: one step below the bfloat16 operands the
configuration states.

Parameters are two levels deep in the policy's own names and shapes (a
run of state-space layers is one group ``layers_<first>_<last>`` with a
leading layer axis), so ``to_policy_tree`` is the identity and a caller
may hand the policy's arrays in as views. ``init_params`` returns HOST
arrays: beside 12 GB of policy state the chip has no room for a second
copy of the weights. The gradient of the recurrence keeps every token's
matrix of the streams it runs, 0.54 GB a stream and layer at 256
tokens, so a layer is recomputed in the backward pass
(``jax.checkpoint``) ``STREAMS`` streams at a time.
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
MAMBA, ATTENTION = "mamba", "attention"


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
    layers = int(c["num_hidden_layers"])
    heads = int(c["num_attention_heads"])
    z = {
        "D": int(c["hidden_size"]), "V": int(num_actions), "L": layers,
        # the published pattern's first ``num_hidden_layers`` entries
        "kinds": tuple(c["layer_types"][:layers]),
        "eps": float(c["rms_norm_eps"]),
        "H": heads, "Hkv": int(c["num_key_value_heads"]),
        "dh": int(c.get("head_dim") or int(c["hidden_size"]) // heads),
        "attn_scale": float(c["attention_multiplier"]),
        "S": int(c["max_position_embeddings"]),
        "F": int(c["shared_intermediate_size"]),
        "Hs": int(c["mamba_n_heads"]), "P": int(c["mamba_d_head"]),
        "N": int(c["mamba_d_state"]), "K": int(c["mamba_d_conv"]),
        "embed_scale": float(c["embedding_multiplier"]),
        "residual_scale": float(c["residual_multiplier"]),
        "logits_scale": float(c["logits_scaling"]),
        "T": int(c["algo_config"]["model"]["max_seq_len"]),
    }
    z["I"] = z["Hs"] * z["P"]
    z["C"] = z["I"] + 2 * z["N"]
    return z


def runs(z: Dict):
    """``[(group name, kind, layers)]``: consecutive state-space layers
    are one group."""
    out = []
    for i, kind in enumerate(z["kinds"]):
        if kind == MAMBA and out and out[-1][1] == MAMBA and out[-1][0] + out[-1][2] == i:
            out[-1][2] += 1
        else:
            out.append([i, kind, 1])
    return [
        (f"layers_{i}_{i + n - 1}" if kind == MAMBA else f"layer_{i}", kind, n)
        for i, kind, n in out
    ]


def param_shapes(config: Dict, num_actions: int) -> Dict[str, Dict[str, tuple]]:
    z = sizes(config, num_actions)
    d, f = z["D"], z["F"]
    out = {
        "embed": {"embedding": (z["V"], d)},  # the output head too
        "final_norm": {"weight": (d,)},
        "value": {"kernel": (d, 1), "bias": (1,)},
    }
    for name, kind, n in runs(z):
        layer = {
            "input_norm": (d,), "post_norm": (d,),
            "mlp_gate": (d, f), "mlp_up": (d, f), "mlp_down": (f, d),
        }
        if kind == MAMBA:
            layer.update({
                "in_proj": (d, 2 * z["I"] + 2 * z["N"] + z["Hs"]),
                "conv": (z["C"], z["K"]), "conv_bias": (z["C"],),
                "dt_bias": (z["Hs"],), "A_log": (z["Hs"],), "D": (z["Hs"],),
                "ssm_norm": (z["I"],), "out_proj": (z["I"], d),
            })
            layer = {k: (n,) + shape for k, shape in layer.items()}
        else:
            layer.update({
                "q_proj": (d, z["H"] * z["dh"]), "k_proj": (d, z["Hkv"] * z["dh"]),
                "v_proj": (d, z["Hkv"] * z["dh"]), "o_proj": (z["H"] * z["dh"], d),
            })
        out[name] = layer
    return out


def init_params(key, config: Dict, num_actions: int, host: bool = True):
    """Seeded weights, one jitted call a group, brought to the host
    (``host=False``: left on the device). ASSUMED, the config states
    none of it: matrices normal with variance 1 / rows (the convolution
    1 / width); the tied table 0.125 x normal, so that ``12 E`` is of
    order one and the logits ``rms(x) E^T / 8`` have deviation 0.7 (a
    random policy that is not near-deterministic); norm weights and
    biases 0.1 x normal, small and not zero (a weight the system dropped
    would otherwise go unseen); the family's own initialisation for the
    recurrence: ``A_log = log(1..heads)``, ``D = 1``, ``dt_bias`` the
    inverse softplus of a log-uniform step in (0.001, 0.1)."""
    shapes = param_shapes(config, num_actions)
    # XLA's own bit generator: a threefry stream for 772 M weights is a
    # minute of compiling on the chip, and this is a few seconds
    key = jax.random.wrap_key_data(
        jnp.tile(jax.random.key_data(key).astype(jnp.uint32).ravel(), 2)[:4],
        impl="rbg")

    def make(key, group):
        # ONE draw a group, cut into its leaves
        stacked = group.startswith("layers_")
        leaves = sorted(shapes[group].items())
        counts = [int(np.prod(shape)) for _, shape in leaves]
        draws = jax.random.normal(key, (sum(counts),), jnp.float32)
        out, at = {}, 0
        for (leaf, shape), count in zip(leaves, counts):
            x = draws[at : at + count].reshape(shape)
            at += count
            one = shape[1:] if stacked else shape  # a layer's own shape
            if leaf == "A_log":
                x = jnp.broadcast_to(
                    jnp.log(jnp.arange(1, shape[-1] + 1, dtype=jnp.float32)), shape)
            elif leaf == "D":
                x = jnp.ones(shape, jnp.float32)
            elif leaf == "dt_bias":
                # log-uniform in (0.001, 0.1) from the normal draw
                dt = jnp.exp(np.log(1e-3) + jax.scipy.stats.norm.cdf(x) * np.log(100.0))
                x = dt + jnp.log(-jnp.expm1(-dt))
            elif len(one) == 1:
                x = 0.1 * x
            elif leaf == "embedding":
                x = 0.125 * x
            elif leaf == "conv":
                x = x / np.sqrt(one[-1])
            else:
                x = x / np.sqrt(one[-2])
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


def _swiglu(x, wg, wu, wd, q_):
    return _mm(jax.nn.silu(_mm(x, wg, q_)) * _mm(x, wu, q_), wd, q_)


def initial_state(z: Dict, rows: int):
    """As the policy lays it out: a run of state-space layers holds its
    matrices ``(rows, layers, heads, head, state)`` and the last ``conv
    - 1`` inputs of each convolution ``(rows, layers, conv - 1,
    channels)``; the attention layer its keys and values (float32
    here); last the position."""
    state = []
    for _, kind, n in runs(z):
        if kind == MAMBA:
            state.append(jnp.zeros((rows, n, z["Hs"], z["P"], z["N"]), jnp.float32))
            state.append(jnp.zeros((rows, n, z["K"] - 1, z["C"]), jnp.float32))
        else:
            for _ in range(2):
                state.append(jnp.zeros((rows, z["S"], z["Hkv"] * z["dh"]), jnp.float32))
    state.append(jnp.zeros((rows,), jnp.int32))
    return tuple(state)


def _positions(pos0, fresh):
    """Each token's position in its episode: a fresh token is at 0."""
    def step(pos, f):
        pos = jnp.where(f, 0, pos)
        return pos + 1, pos

    end, positions = jax.lax.scan(step, pos0, fresh.T)
    return positions.T, end


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


def _mamba(p, x, matrix, window, fresh, z, q_):
    """One state-space layer over a fragment, token by token. ``matrix``
    ``(B, heads, head, state)``, ``window`` ``(B, conv - 1, channels)``
    the last inputs of the convolution. Returns the output and both
    after the fragment."""
    b, t, _ = x.shape
    i, n, hs, ph = z["I"], z["N"], z["Hs"], z["P"]
    zxbcdt = _mm(x, p["in_proj"], q_)
    gate, u, dt_raw = zxbcdt[..., :i], zxbcdt[..., i : i + z["C"]], zxbcdt[..., i + z["C"] :]
    a = -jnp.exp(p["A_log"])

    def some_streams(xs):
        u, dt_raw, matrix, window, fresh = xs

        def token(carry, xs):
            s, w = carry
            u_t, dt_t, f_t = xs
            # a new episode starts from nothing
            s = jnp.where(f_t[:, None, None, None], 0.0, s)
            w = jnp.where(f_t[:, None, None], 0.0, w)
            w = jnp.concatenate([w, u_t[:, None]], axis=1)  # (b, conv, channels)
            mixed = jax.nn.silu(
                jnp.sum(w * p["conv"].T[None], axis=1) + p["conv_bias"])
            x_t = mixed[:, :i].reshape(-1, hs, ph)
            b_t, c_t = mixed[:, i : i + n], mixed[:, i + n :]
            dt = jax.nn.softplus(dt_t + p["dt_bias"])  # (b, heads)
            s = jnp.exp(dt * a)[..., None, None] * s + (
                dt[..., None, None] * x_t[..., None] * b_t[:, None, None, :])
            y = jnp.einsum("bhpn,bn->bhp", s, c_t, precision=HI) + (
                p["D"][:, None] * x_t)
            return (s, w[:, 1:]), y.reshape(-1, i)

        (s, w), ys = jax.lax.scan(
            token, (matrix, window),
            (jnp.moveaxis(u, 1, 0), jnp.moveaxis(dt_raw, 1, 0), fresh.T))
        return jnp.moveaxis(ys, 0, 1), s, w

    y, matrix, window = _in_groups(some_streams, (u, dt_raw, matrix, window, fresh))
    y = _rms(y * jax.nn.silu(gate), p["ssm_norm"], z["eps"])
    return _mm(y, p["out_proj"], q_), matrix, window


def _attention(p, x, k_cache, v_cache, pos0, positions, fresh, z, q_):
    """Causal softmax attention with no positions, over every stored
    row of the episode so far and the fragment's own. Returns the
    output and the keys and values after the fragment (float32)."""
    b, t, _ = x.shape
    h, hkv, dh, s_max = z["H"], z["Hkv"], z["dh"], z["S"]
    q = _mm(x, p["q_proj"], q_).reshape(b, t, h, dh)
    k = _mm(x, p["k_proj"], q_)
    v = _mm(x, p["v_proj"], q_)
    episode = jnp.cumsum(fresh.astype(jnp.int32), axis=1)
    steps = jnp.arange(t)

    def some_streams(xs):
        q, k, v, kc, vc, ep, p0 = xs
        keys = jnp.concatenate([kc.astype(jnp.float32), k], axis=1)
        values = jnp.concatenate([vc.astype(jnp.float32), v], axis=1)
        keys = jnp.repeat(keys.reshape(keys.shape[:2] + (hkv, dh)), h // hkv, axis=2)
        values = jnp.repeat(
            values.reshape(values.shape[:2] + (hkv, dh)), h // hkv, axis=2)
        scores = jnp.einsum("bthd,bshd->bhts", q, keys, precision=HI) * z["attn_scale"]
        stored = (ep == 0)[:, :, None] & (
            jnp.arange(s_max)[None, None] < p0[:, None, None])
        own = (steps[:, None] >= steps[None, :])[None] & (
            ep[:, :, None] == ep[:, None, :])
        mask = jnp.concatenate([stored, own], axis=-1)[:, None]
        w = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhts,bshd->bthd", w, values, precision=HI)

    o = _in_groups(
        some_streams, (q, k, v, k_cache, v_cache, episode, pos0)).reshape(b, t, h * dh)

    # the rows after the fragment, written token by token
    def write(caches, xs):
        k_t, v_t, pos_t = xs
        kc, vc = caches
        rows = jnp.arange(b)
        return (kc.at[rows, pos_t].set(k_t), vc.at[rows, pos_t].set(v_t)), None

    (k_after, v_after), _ = jax.lax.scan(
        write, (k_cache.astype(jnp.float32), v_cache.astype(jnp.float32)),
        (jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0), positions.T))
    return _mm(o, p["o_proj"], q_), k_after, v_after


def forward(params, tokens, state, fresh, config: Dict, num_actions: int,
            precision: str = "float32"):
    """A fragment from its start state. ``tokens`` ``(B, T)`` int;
    ``state`` as ``initial_state`` (caches in any float type); ``fresh``
    ``(B, T)`` bool (the token opens an episode). Returns ``{"logits"
    (B, T, V), "value" (B, T), "state", "routes"}``; ``routes`` is one
    row of zeros ``(1, B*T, 1)``: there is no router, every token takes
    the one feed-forward there is."""
    z, q_ = sizes(config, num_actions), _QUANT[precision]
    fresh = fresh.astype(bool)
    pos0 = state[-1]
    positions, pos1 = _positions(pos0, fresh)
    table = params["embed"]["embedding"]
    x = z["embed_scale"] * table[tokens.astype(jnp.int32)]
    scale = z["residual_scale"]

    @jax.checkpoint
    def mamba_layer(x, p, matrix, window):
        y, matrix, window = _mamba(
            p, _rms(x, p["input_norm"], z["eps"]), matrix, window, fresh, z, q_)
        x = x + scale * y
        h = _rms(x, p["post_norm"], z["eps"])
        y = _swiglu(h, p["mlp_gate"], p["mlp_up"], p["mlp_down"], q_)
        return x + scale * y, matrix, window

    state_out, at = [], 0
    for name, kind, n in runs(z):
        group = params[name]
        if kind == MAMBA:
            def one_layer(x, xs):
                x, matrix, window = mamba_layer(x, *xs)
                return x, (matrix, window)

            x, after = jax.lax.scan(
                one_layer, x,
                (group, jnp.moveaxis(state[at], 1, 0), jnp.moveaxis(state[at + 1], 1, 0)))
            state_out.extend(jnp.moveaxis(leaf, 0, 1) for leaf in after)
        else:
            y, k_after, v_after = _attention(
                group, _rms(x, group["input_norm"], z["eps"]), state[at],
                state[at + 1], pos0, positions, fresh, z, q_)
            x = x + scale * y
            h = _rms(x, group["post_norm"], z["eps"])
            x = x + scale * _swiglu(
                h, group["mlp_gate"], group["mlp_up"], group["mlp_down"], q_)
            state_out.extend([k_after, v_after])
        at += 2
    state_out.append(pos1)
    feat = _rms(x, params["final_norm"]["weight"], z["eps"])
    logits = _mm(feat, table.T, q_) / z["logits_scale"]  # the tied head
    value = (
        jnp.dot(feat, params["value"]["kernel"], precision=HI)
        + params["value"]["bias"]
    )[..., 0]
    b, t = tokens.shape
    return {"logits": logits, "value": value, "state": tuple(state_out),
            "routes": jnp.zeros((1, b * t, 1), jnp.int32)}


# -- batches, loss, and the rest of PPO ---------------------------------------------


def make_state(rng: np.random.Generator, z: Dict, rows: int, fragment: int):
    """Seeded start states: streams somewhere inside an episode, with
    the matrices, convolution inputs, keys and values such an episode
    leaves behind (magnitudes of order one; the caches rounded to
    bfloat16 as the policy stores them)."""
    pos0 = rng.integers(0, z["S"] - fragment + 1, rows).astype(np.int32)
    pos0[0] = 0  # one stream at its episode's start
    state = []
    for like in initial_state(z, rows)[:-1]:
        leaf = rng.standard_normal(like.shape, dtype=np.float32)
        if like.ndim == 3:  # a cache
            leaf = leaf.astype(jnp.bfloat16)
        elif like.ndim == 5:  # the matrices: what a few dozen writes of dt x B^T leave
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
