"""Plain reference of the Laguna block stack (``model_type: laguna``,
poolside's Laguna-XS.2) as a token-level PPO policy: ``jax.numpy``,
float32, every product at precision "highest", nothing from ``ray_tpu``.

Written the long way where the system is clever. Attention, of either
kind, is the full masked score matrix over every stored row and the
fragment's own, a few streams at a time, and both masks are written in
POSITIONS: a key is seen if it is of the query's episode, not after the
query and, in a window layer, less than ``sliding_window`` behind it
(``0 <= p_q - p_k < 512``). YaRN's frequencies are written out here as
Hugging Face's ``_compute_yarn_parameters`` computes them. The experts
run one after another under a dense 0/weight mask. Its own GAE, PPO
loss, global-norm clip and Adam step are at the end.

Only the two ends of ``forward`` know that a window layer's state is a
ring, because the policy's carry is the state it is handed and the
state it is compared with (``perf/checks/rollout_fragment.py``, slot by
slot): ``_stored_rows`` reads the rows out in order of position, and
``_write`` puts each token's row at ``position mod rows``, token by
token. Between the two there are rows with positions and nothing else.

Layer equations (the published config and the catalog's description;
what neither states is a comment where it occurs and ``assumed`` in the
configuration file). ``x`` is the stream, layer ``l``, no bias anywhere
(``attention_bias: false``), ``rms(x) = x * rsqrt(mean(x^2) + eps) * (1
+ w)`` with ``rms_norm_eps`` 1e-6 (DEPARTURE: norm weights stored
zero-centred, as the policy stores every norm; with seeded weights a
reparametrisation):

- attention, ``H_l = num_attention_heads_per_layer[l]`` query heads (48
  on a full layer, 64 on a window layer), ``num_key_value_heads`` 8,
  ``D = head_dim`` 128: ``h = rms(x)``; ``q = h W_q`` as ``(H_l, D)``,
  ``k = h W_k``, ``v = h W_v`` as ``(8, D)``; ``q`` and ``k`` RMS-normed
  over the head with a learned weight (ASSUMED: the ``qwen3`` convention
  whose key names the config carries); RoPE by the layer's kind; scores
  ``q . k / sqrt(D)``, causal, and on a window layer a query at ``p``
  sees the keys at ``p - 511 .. p`` of its episode; softmax; ``o = P
  v``; the GATE ``g = sigmoid(h W_g)``, ``W_g: (hidden, H_l)``, one
  number a head and token, ``o_head <- g_head o_head`` (ASSUMED:
  ``gating: true`` names no form; head-wise reproduces the published
  33.4 B); ``x <- x + o W_o``;
- RoPE, full layers (``rope_parameters.full_attention``): YaRN over the
  FIRST ``partial_rotary_factor x D`` = 64 dimensions of the head
  (rotate-half within them): with ``dim`` 64, base ``rope_theta``
  500,000, ``factor`` 64, ``original_max_position_embeddings`` 4,096,
  ``beta_fast`` 64, ``beta_slow`` 1, the inverse frequencies are
  ``base^(-2i/dim)`` (extrapolated) below the correction dimension of
  ``beta_fast`` rotations, those over ``factor`` (interpolated) above
  that of ``beta_slow``, a linear ramp between the two; ``cos`` and
  ``sin`` both times ``attention_factor`` 1.4158883, so the rotated
  half of a score carries the factor squared; the other 64 dimensions
  pass unturned and unscaled. Window layers
  (``rope_parameters.sliding_attention``): plain RoPE, theta 10,000,
  rotate-half over all 128 dimensions;
- feed-forward where ``mlp_layer_types[l]`` is ``dense`` (layer 0):
  ``x <- x + (silu(g W_gate) * (g W_up)) W_down``, ``g = rms(x)``, width
  ``intermediate_size`` 8,192;
- where it is ``sparse``: ``s = sigmoid(g W_r)`` over ALL
  ``router_outputs`` 256; ``idx`` the ``num_experts_per_tok`` 8 largest
  (no selection bias: no key for one); ``w_i = s_i / sum of the eight x
  moe_routed_scaling_factor`` 2.5 (ASSUMED: sigmoid and renormalise;
  the config states no scoring function); ``y = sum over i in idx, held
  here, of w_i E_i(g) + E_shared(g)``, every expert a SwiGLU of width
  512, the shared expert UNGATED (ASSUMED); ``x <- x + y``. What the
  experts that are not held would add is left out, as in the policy;
- ``logits = rms(x_L) W_head`` (untied), a value head beside it.

``precision`` "int8" and "fp8" are the CONTROLS: inputs and weights of
q/k/v/g/o, the dense feed-forward's, the experts' and the shared
expert's three products and the head rounded per tensor to 127 levels
or to float8 e4m3, and their cotangents likewise: one step below the
bfloat16 operands the configuration states. The router is float32 in
the policy and stays so here.

Parameters are two levels deep in the policy's own names and shapes, so
``to_policy_tree`` is the identity and a caller may hand the policy's
arrays in as views. ``init_params`` returns HOST arrays.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
# streams whose keys, values and scores are alive at once
STREAMS = 2
FULL, WINDOW = "full_attention", "sliding_attention"


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
    first, held = c.get("experts_held") or (0, int(c["num_experts"]))
    return {
        "D": int(c["hidden_size"]), "V": int(num_actions), "L": layers,
        # the published lists' first ``num_hidden_layers`` entries
        "kind": tuple(c["layer_types"][:layers]),
        "H": tuple(int(h) for h in c["num_attention_heads_per_layer"][:layers]),
        "sparse": tuple(m != "dense" for m in c["mlp_layer_types"][:layers]),
        "rope": {k: dict(c["rope_parameters"][k]) for k in (FULL, WINDOW)},
        "eps": float(c["rms_norm_eps"]),
        "Hkv": int(c["num_key_value_heads"]), "dh": int(c["head_dim"]),
        "S": int(c["max_position_embeddings"]), "W": int(c["sliding_window"]),
        "Fd": int(c["intermediate_size"]),
        "E": int(held), "first": int(first),
        "R": int(c.get("router_outputs", c["num_experts"])),
        "top_k": int(c["num_experts_per_tok"]),
        "F": int(c["moe_intermediate_size"]),
        "Fs": int(c["shared_expert_intermediate_size"]),
        "route_scale": float(c["moe_routed_scaling_factor"]),
        "T": int(c["algo_config"]["model"]["max_seq_len"]),
    }


def param_shapes(config: Dict, num_actions: int) -> Dict[str, Dict[str, tuple]]:
    z = sizes(config, num_actions)
    d, e, f, fs, dh = z["D"], z["E"], z["F"], z["Fs"], z["dh"]
    out = {
        "embed": {"embedding": (z["V"], d)},
        "final_norm": {"weight": (d,)},
        "head": {"kernel": (d, z["V"])},
        "value": {"kernel": (d, 1), "bias": (1,)},
    }
    for i in range(z["L"]):
        h = z["H"][i]
        layer = {
            "input_norm": (d,), "post_norm": (d,),
            "q_proj": (d, h * dh), "k_proj": (d, z["Hkv"] * dh),
            "v_proj": (d, z["Hkv"] * dh), "o_proj": (h * dh, d),
            "q_norm": (dh,), "k_norm": (dh,), "g_proj": (d, h),
        }
        if z["sparse"][i]:
            layer.update({
                "router": (d, z["R"]),
                "experts_gate": (e, d, f), "experts_up": (e, d, f),
                "experts_down": (e, f, d),
                "shared_gate": (d, fs), "shared_up": (d, fs), "shared_down": (fs, d),
            })
        else:
            layer.update({
                "mlp_gate": (d, z["Fd"]), "mlp_up": (d, z["Fd"]),
                "mlp_down": (z["Fd"], d),
            })
        out[f"layer_{i}"] = layer
    return out


def init_params(key, config: Dict, num_actions: int, host: bool = True):
    """Seeded weights, one jitted call a group, brought to the host
    (``host=False``: left on the device). ASSUMED, the config states
    none of it, as the other references draw them: matrices normal with
    variance 1 / rows (the output head a quarter of that, so that a
    random policy is not near-deterministic), the embedding normal, norm
    weights and the value bias 0.1 x normal, small and not zero (a
    weight the system dropped would otherwise go unseen)."""
    shapes = param_shapes(config, num_actions)
    # XLA's own bit generator: a threefry stream for 690 M weights
    # compiles for most of a minute on the chip
    key = jax.random.wrap_key_data(
        jnp.tile(jax.random.key_data(key).astype(jnp.uint32).ravel(), 2)[:4],
        impl="rbg")

    def make(key, group):
        out = {}
        for n, (leaf, shape) in enumerate(sorted(shapes[group].items())):
            x = jax.random.normal(jax.random.fold_in(key, n), shape, jnp.float32)
            if len(shape) == 1:
                x = 0.1 * x
            elif leaf != "embedding":
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


def rope_frequencies(rope: Dict, head_dim: int):
    """``(inverse frequencies (dim / 2,), factor on cos and sin)`` of one
    ``rope_parameters`` block, ``dim = partial_rotary_factor x
    head_dim``: plain RoPE, or YaRN as Hugging Face's
    ``_compute_yarn_parameters`` writes it (float64 here, float32 out)."""
    dim = int(head_dim * float(rope.get("partial_rotary_factor", 1.0)))
    base = float(rope["rope_theta"])
    extrapolated = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rope.get("rope_type", "default") == "default":
        return extrapolated.astype(np.float32), 1.0
    factor = float(rope["factor"])
    original = float(rope["original_max_position_embeddings"])

    def correction_dim(rotations):
        # the dimension whose wavelength makes ``rotations`` turns over
        # the original context
        return dim * math.log(original / (rotations * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(correction_dim(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(rope["beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    inv = extrapolated / factor * ramp + extrapolated * (1.0 - ramp)
    attention_factor = rope.get("attention_factor")
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0
    return inv.astype(np.float32), float(attention_factor)


def _rope(x, positions, rope: Dict):
    """Rotate-half over the head's first ``dim`` dimensions; the rest
    pass. ``x`` ``(B, T, H, D)``; ``positions`` ``(B, T)``."""
    inv, factor = rope_frequencies(rope, x.shape[-1])
    half = inv.shape[0]
    angle = positions.astype(jnp.float32)[..., None, None] * jnp.asarray(inv)
    cos, sin = jnp.cos(angle) * factor, jnp.sin(angle) * factor
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def cache_rows(z: Dict, layer: int) -> int:
    """Rows a stream's cache of ``layer`` holds: the window's in a
    window layer, the episode's otherwise."""
    return min(z["W"], z["S"]) if z["kind"][layer] == WINDOW else z["S"]


def initial_state(z: Dict, rows: int):
    """As the policy lays it out: keys and values a layer (bfloat16),
    last the position."""
    state = []
    for i in range(z["L"]):
        for _ in range(2):
            state.append(jnp.zeros(
                (rows, cache_rows(z, i), z["Hkv"] * z["dh"]), jnp.bfloat16))
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
    """The rows a stream holds, in order of position, newest first:
    ``(rows (B, n, row), their positions (B, n))`` for the ``n`` slots
    of ``cache``, a position below zero where the episode has no such
    row yet. The row of position ``p`` lies in slot ``p mod n`` (which
    is ``p`` itself while the cache is as deep as the episode)."""
    n = cache.shape[1]
    at = pos0[:, None] - 1 - jnp.arange(n)[None]  # (B, n)
    rows = jnp.take_along_axis(cache, (at % n)[..., None], axis=1)
    return rows.astype(jnp.float32), at


def _write(cache, rows, positions):
    """``cache`` after the fragment's ``rows`` ``(B, T, row)``, token by
    token, each at its position mod the cache's depth, in the type the
    cache came in (the policy's carry is bfloat16)."""
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


def _attention(p, x, k_cache, v_cache, pos0, positions, fresh, z, q_, layer):
    """Causal softmax attention of layer ``layer`` over every stored row
    of the episode so far and the fragment's own; in a window layer only
    over the rows less than ``W`` positions behind the query; the output
    gated a head. Returns the output and the keys and values after the
    fragment."""
    b, t, _ = x.shape
    h, hkv, dh = z["H"][layer], z["Hkv"], z["dh"]
    window = z["kind"][layer] == WINDOW
    rope = z["rope"][z["kind"][layer]]
    q = _mm(x, p["q_proj"], q_).reshape(b, t, h, dh)
    k = _mm(x, p["k_proj"], q_).reshape(b, t, hkv, dh)
    v = _mm(x, p["v_proj"], q_)
    # ASSUMED: q and k normed over the head, before RoPE
    q = _rope(_rms(q, p["q_norm"], z["eps"]), positions, rope)
    k = _rope(_rms(k, p["k_norm"], z["eps"]), positions, rope)
    k = k.reshape(b, t, hkv * dh)
    episode = jnp.cumsum(fresh.astype(jnp.int32), axis=1)
    k_old, at = _stored_rows(k_cache, pos0)
    v_old, _ = _stored_rows(v_cache, pos0)

    def some_streams(xs):
        q, k, v, k_old, v_old, at, ep, pos = xs
        heads = lambda a: jnp.repeat(
            a.reshape(a.shape[:2] + (hkv, dh)), h // hkv, axis=2)
        keys = heads(jnp.concatenate([k_old, k], axis=1))
        values = heads(jnp.concatenate([v_old, v], axis=1))
        scores = jnp.einsum("bthd,bshd->bhts", q, keys, precision=HI) * (dh ** -0.5)
        # every key's position and episode (a stored row is of the
        # episode the fragment starts in: number 0)
        key_pos = jnp.concatenate([at, pos], axis=1)  # (b, n + t)
        key_ep = jnp.concatenate([jnp.zeros_like(at), ep], axis=1)
        behind = pos[:, :, None] - key_pos[:, None, :]
        mask = (key_pos >= 0)[:, None] & (key_ep[:, None] == ep[:, :, None]) & (
            behind >= 0)
        if window:
            mask = mask & (behind < z["W"])
        w = jax.nn.softmax(jnp.where(mask[:, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhts,bshd->bthd", w, values, precision=HI)

    o = _in_groups(some_streams, (q, k, v, k_old, v_old, at, episode, positions))
    # ASSUMED: one gate a head and token
    gate = jax.nn.sigmoid(_mm(x, p["g_proj"], q_))  # (b, t, h)
    o = (o * gate[..., None]).reshape(b, t, h * dh)
    return (_mm(o, p["o_proj"], q_),
            _write(k_cache, k, positions), _write(v_cache, v, positions))


def _swiglu(x, w_gate, w_up, w_down, q_):
    return _mm(jax.nn.silu(_mm(x, w_gate, q_)) * _mm(x, w_up, q_), w_down, q_)


def _route(p, x, z):
    """A sigmoid for every router output, the largest ``top_k``, those
    over their sum times the scaling factor (ASSUMED). ``(indices,
    weights)`` ``(B*T, top_k)``."""
    scores = jax.nn.sigmoid(
        jnp.dot(x.reshape(-1, x.shape[-1]), p["router"], precision=HI))
    top, idx = jax.lax.top_k(scores, z["top_k"])
    return idx, top / jnp.sum(top, axis=-1, keepdims=True) * z["route_scale"]


def _experts(p, x, idx, w, z, q_):
    """The held experts one after another under a dense 0/weight mask."""
    flat = x.reshape(-1, x.shape[-1])

    def one_expert(acc, xs):
        e, wg, wu, wd = xs
        weight = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
        return acc + weight[:, None] * _swiglu(flat, wg, wu, wd, q_), None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(flat),
        (z["first"] + jnp.arange(z["E"]), p["experts_gate"], p["experts_up"],
         p["experts_down"]),
    )
    return routed.reshape(x.shape)


def _feed_forward(p, g, z, q_, sparse):
    """``(output, top-k indices or None)`` from the normed stream."""
    if not sparse:
        return _swiglu(g, p["mlp_gate"], p["mlp_up"], p["mlp_down"], q_), None
    idx, w = _route(p, g, z)
    # ASSUMED: the shared expert is not gated
    shared = _swiglu(g, p["shared_gate"], p["shared_up"], p["shared_down"], q_)
    return _experts(p, g, idx, w, z, q_) + shared, idx


def forward(params, tokens, state, fresh, config: Dict, num_actions: int,
            precision: str = "float32"):
    """A fragment from its start state. ``tokens`` ``(B, T)`` int;
    ``state`` as ``initial_state`` (caches in any float type); ``fresh``
    ``(B, T)`` bool (the token opens an episode). Returns ``{"logits"
    (B, T, V), "value" (B, T), "state", "routes" (expert layers, B*T,
    k)}``."""
    z, q_ = sizes(config, num_actions), _QUANT[precision]
    fresh = fresh.astype(bool)
    pos0 = state[-1]
    positions, pos1 = _positions(pos0, fresh)
    x = params["embed"]["embedding"][tokens.astype(jnp.int32)]
    state_out, routes = [], []
    for i in range(z["L"]):
        p = params[f"layer_{i}"]

        @jax.checkpoint
        def layer(x, p, k_cache, v_cache, i=i):
            y, k_after, v_after = _attention(
                p, _rms(x, p["input_norm"], z["eps"]), k_cache, v_cache, pos0,
                positions, fresh, z, q_, i)
            x = x + y
            y, idx = _feed_forward(
                p, _rms(x, p["post_norm"], z["eps"]), z, q_, z["sparse"][i])
            return x + y, k_after, v_after, idx

        x, k_after, v_after, idx = layer(x, p, state[2 * i], state[2 * i + 1])
        state_out.extend([k_after, v_after])
        if idx is not None:
            routes.append(idx)
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
    """Seeded start states: streams somewhere inside an episode, EVERY
    slot of every cache filled with rows of order one rounded to
    bfloat16 (what earlier episodes leave behind: a row that must not be
    seen is there to be seen)."""
    pos0 = rng.integers(0, z["S"] - fragment + 1, rows).astype(np.int32)
    pos0[0] = 0  # one stream at its episode's start
    state = [
        rng.standard_normal(like.shape, dtype=np.float32).astype(jnp.bfloat16)
        for like in initial_state(z, rows)[:-1]
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
