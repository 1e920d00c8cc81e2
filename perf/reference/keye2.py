"""Plain reference of Keye-VL-2.0-30B-A3B's decoder (``model_type:
KeyeVL2``: the ``qwen3_moe`` layer with the lightning indexer of
DeepSeek Sparse Attention on every layer, ``sa_config``) as a
token-level PPO policy: ``jax.numpy``, float32, every product at
precision "highest", nothing from ``ray_tpu``, no kernel and no cache
that is read by slot number.

Written the long way where the system is clever. A layer's attention is
the full masked score matrix over every stored row and the fragment's
own, ONE stream at a time, and the index is the full matrix too: ``I``
for every (query, row) pair, a stable SORT of each query's row of it (a
rank a row: ``argsort`` of ``argsort``), a boolean mask ``rank < topk``
and dense masked attention under it. The system never ranks: it takes
the ``topk``-th largest score as a threshold (one token: ``lax.top_k``'s
slot numbers and a gather). The experts run one after another under a
dense 0/weight mask. Its own GAE, PPO loss, global-norm clip and Adam
step are at the end.

Layer equations (the published config and the catalog's description;
what neither states is a comment where it occurs and ``assumed`` in the
configuration file). ``x`` the stream, ``rms(x) = x * rsqrt(mean(x^2) +
eps) * (1 + w)`` (DEPARTURE: norm weights stored zero-centred, as the
policy stores every norm; with seeded weights a reparametrisation), no
bias but LN's:

- ``h = rms(x)``; ``q = h W_q`` as (32, 128), ``k = h W_k``, ``v = h
  W_v`` as (4, 128); ``q`` and ``k`` RMS-normed over the head with a
  learned weight (ASSUMED: the ``qwen3_moe`` convention, whose key names
  the config carries); RoPE theta 1e7, rotate-half over all 128
  (M-RoPE with sections [16, 24, 24] over TEXT tokens, whose three
  components are equal, is exactly this; no vision tower here);
- the index, from the same ``h``: ``qI_j = RoPE(h W_qI)[j]`` for ``j =
  1..16``, each of 64; ``kI = RoPE(LN(h W_kI))``, ONE key of 64 a row
  (``indexer_num_kv_heads`` 1; ASSUMED: LN a LayerNorm with weight and
  bias as DSA's release, the weight zero-centred like the other norms;
  RoPE over the whole 64 at the attention's theta); ``w = h W_w``, 16
  numbers; ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`` for every
  row ``s <= t`` of the episode (no factor ``64^-1/2 16^-1/2``: positive,
  it changes no choice; an ``I`` of -0.0 counts as 0.0); ``S_t`` the
  ``min(t + 1, topk)`` rows of the largest ``I``, ties to the lower
  position, EXACT (``q_chunk_size`` / ``kv_chunk_size`` tile the release's
  score product and are no pooling: not read); the index takes no
  gradient and hands none on (``stop_gradient``; ASSUMED: no alignment
  loss, the config states no coefficient);
- ``o_t = softmax over s in S_t of (q_t . k_s / sqrt(128)) v_s``;
  ``x <- x + o W_o``;
- ``h = rms(x)``; ``s = softmax(h W_r)`` over all 128 in float32; top-8;
  weights ``s_i / sum of the eight`` (``norm_topk_prob``); ``y = sum_i
  w_i E_i(h)`` over the experts held here, ``E_i`` a SwiGLU of width 768;
  no shared expert; ``x <- x + y``;
- final norm, untied head over the rows held, a value head beside it.

``precision`` "int8" and "fp8" are the CONTROLS: inputs and weights of
q/k/v/o, the experts' three products, the head AND the index's two
projections and its score product ``qI . kI`` rounded per tensor to 127
levels or to float8 e4m3, and their cotangents likewise: one step below
the bfloat16 operands the configuration states. The router, ``W_w``,
LN and the top-k are float32 in the policy and stay so here.

The state is the policy's: keys, values and INDEX KEYS a layer, ``(rows,
positions, row)``, position ``p`` in slot ``p``, last the position.
Parameters are two levels deep in the policy's own names and shapes, so
``to_policy_tree`` is the identity and a caller may hand the policy's
arrays in as views. ``init_params`` returns HOST arrays.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
# streams whose keys, values and scores are alive at once (one stream's
# float32 scores of 32 heads x 256 queries over 16,640 rows are 0.55 GB)
STREAMS = 1


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
    first, held = c.get("experts_held") or (0, int(c["num_experts"]))
    sa = c["sa_config"]
    return {
        "D": int(c["hidden_size"]), "V": int(num_actions),
        "L": int(c["num_hidden_layers"]), "eps": float(c["rms_norm_eps"]),
        "H": int(c["num_attention_heads"]), "Hkv": int(c["num_key_value_heads"]),
        "dh": int(c["head_dim"]), "theta": float(c["rope_theta"]),
        "S": int(c["max_position_embeddings"]),
        "Hi": int(sa["indexer_num_heads"]), "di": int(sa["indexer_head_dim"]),
        "topk": int(sa["topk"]),
        "E": int(held), "first": int(first),
        "R": int(c.get("router_outputs", c["num_experts"])),
        "top_k": int(c["num_experts_per_tok"]),
        "F": int(c["moe_intermediate_size"]),
        "T": int(c["algo_config"]["model"]["max_seq_len"]),
    }


def param_shapes(config: Dict, num_actions: int) -> Dict[str, Dict[str, tuple]]:
    z = sizes(config, num_actions)
    d, e, f = z["D"], z["E"], z["F"]
    out = {
        "embed": {"embedding": (z["V"], d)},
        "final_norm": {"weight": (d,)},
        "head": {"kernel": (d, z["V"])},
        "value": {"kernel": (d, 1), "bias": (1,)},
    }
    for i in range(z["L"]):
        out[f"layer_{i}"] = {
            "input_norm": (d,), "post_norm": (d,),
            "q_proj": (d, z["H"] * z["dh"]), "k_proj": (d, z["Hkv"] * z["dh"]),
            "v_proj": (d, z["Hkv"] * z["dh"]), "o_proj": (z["H"] * z["dh"], d),
            "q_norm": (z["dh"],), "k_norm": (z["dh"],),
            "index_q_proj": (d, z["Hi"] * z["di"]), "index_k_proj": (d, z["di"]),
            "index_w_proj": (d, z["Hi"]),
            "index_k_norm": (z["di"],), "index_k_norm_bias": (z["di"],),
            "router": (d, z["R"]),
            "experts_gate": (e, d, f), "experts_up": (e, d, f),
            "experts_down": (e, f, d),
        }
    return out


def init_params(key, config: Dict, num_actions: int, host: bool = True):
    """Seeded weights, one jitted call a group, brought to the host
    (``host=False``: left on the device). ASSUMED, the config states
    none of it, as the other references draw them: matrices normal with
    variance 1 / rows (the output head a quarter of that, so that a
    random policy is not near-deterministic), the embedding normal, norm
    weights (q/k norms and the index's LN, weight and bias, included)
    and the value bias 0.1 x normal, small and not zero (a weight the
    system dropped would otherwise go unseen)."""
    shapes = param_shapes(config, num_actions)
    # XLA's own bit generator: a threefry stream for 314 M weights
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


def _rope(x, positions, theta):
    """Rotate-half over the whole head. ``x`` ``(B, T, H, D)``;
    ``positions`` ``(B, T)``."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[..., None, None] * inv
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _layer_norm(x, w, b, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * (
        1.0 + w) + b


def initial_state(z: Dict, rows: int):
    """As the policy lays it out: keys, values and index keys a layer
    (bfloat16), last the position."""
    state = []
    for _ in range(z["L"]):
        for row in (z["Hkv"] * z["dh"], z["Hkv"] * z["dh"], z["di"]):
            state.append(jnp.zeros((rows, z["S"], row), jnp.bfloat16))
    state.append(jnp.zeros((rows,), jnp.int32))
    return tuple(state)


def _positions(pos0, fresh):
    """Each token's position in its episode: a fresh token is at 0."""
    def step(pos, f):
        pos = jnp.where(f, 0, pos)
        return pos + 1, pos

    end, positions = jax.lax.scan(step, pos0, fresh.T)
    return positions.T, end


def _write(cache, rows, positions):
    """``cache`` after the fragment's ``rows`` ``(B, T, row)``, token by
    token, each at the slot of its position, in the type the cache came
    in (the policy's carry is bfloat16)."""
    b = cache.shape[0]

    def one(c, xs):
        row_t, pos_t = xs
        return c.at[jnp.arange(b), pos_t].set(row_t.astype(c.dtype)), None

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


def _attention(p, x, caches, pos0, positions, fresh, z, q_):
    """Softmax attention of every query over the ``min(seen, topk)``
    rows its index ranks first among the stored rows of the episode so
    far and the fragment's own up to itself. Returns the output, the
    three caches after the fragment and the choice ``(B, T, S + T)``
    bool over the slots and then the fragment's own rows."""
    b, t, _ = x.shape
    h, hkv, dh, hi, di = z["H"], z["Hkv"], z["dh"], z["Hi"], z["di"]
    k_cache, v_cache, i_cache = caches
    q = _rms(_mm(x, p["q_proj"], q_).reshape(b, t, h, dh), p["q_norm"], z["eps"])
    k = _rms(_mm(x, p["k_proj"], q_).reshape(b, t, hkv, dh), p["k_norm"], z["eps"])
    v = _mm(x, p["v_proj"], q_)
    q, k = _rope(q, positions, z["theta"]), _rope(k, positions, z["theta"])
    k = k.reshape(b, t, hkv * dh)
    # the index reads the layer's input and hands no gradient back
    xi = jax.lax.stop_gradient(x)
    pi = {leaf: jax.lax.stop_gradient(p[leaf])
          for leaf in p if leaf.startswith("index_")}
    qi = _rope(_mm(xi, pi["index_q_proj"], q_).reshape(b, t, hi, di), positions,
               z["theta"])
    ki = _layer_norm(_mm(xi, pi["index_k_proj"], q_), pi["index_k_norm"],
                     pi["index_k_norm_bias"], z["eps"])
    ki = _rope(ki[:, :, None], positions, z["theta"])[:, :, 0]
    wi = jnp.dot(xi, pi["index_w_proj"], precision=HI)
    episode = jnp.cumsum(fresh.astype(jnp.int32), axis=1)
    n = k_cache.shape[1]

    def some_streams(xs):
        q, k, v, k_old, v_old, qi, ki, wi, i_old, start, ep, pos = xs
        f32 = lambda a: a.astype(jnp.float32)
        heads = lambda a: jnp.repeat(
            a.reshape(a.shape[:2] + (hkv, dh)), h // hkv, axis=2)
        # every key's position and episode: the row in slot s is of
        # position s, of the episode the fragment starts in (number 0),
        # and there is one below the stream's start position only
        slot = jnp.broadcast_to(jnp.arange(n)[None], (pos.shape[0], n))
        key_pos = jnp.concatenate([jnp.where(slot < start[:, None], slot, -1), pos], 1)
        key_ep = jnp.concatenate([jnp.zeros_like(slot), ep], axis=1)
        seen = (key_pos >= 0)[:, None] & (key_ep[:, None] == ep[:, :, None]) & (
            pos[:, :, None] - key_pos[:, None, :] >= 0)
        # the index: I for every pair, a rank a row by a stable sort
        # (ties keep the order of the rows, which is that of position)
        index_keys = jnp.concatenate([f32(i_old), ki], axis=1)
        each = jnp.einsum("bthd,bsd->bths", q_(qi), q_(index_keys), precision=HI)
        index = jnp.sum(jax.nn.relu(each) * wi[..., None], axis=2)
        index = jnp.where(seen, jnp.where(index == 0.0, 0.0, index), -jnp.inf)
        order = jnp.argsort(-index, axis=-1, stable=True)
        rank = jnp.argsort(order, axis=-1)
        chosen = seen & (rank < z["topk"])
        keys = heads(jnp.concatenate([f32(k_old), k], axis=1))
        values = heads(jnp.concatenate([f32(v_old), v], axis=1))
        scores = jnp.einsum("bthd,bshd->bhts", q, keys, precision=HI) * (dh ** -0.5)
        w = jax.nn.softmax(jnp.where(chosen[:, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhts,bshd->bthd", w, values, precision=HI), chosen

    o, chosen = _in_groups(
        some_streams,
        (q, k, v, k_cache, v_cache, qi, ki, wi, i_cache, pos0, episode, positions))
    after = tuple(_write(c, rows, positions)
                  for c, rows in zip(caches, (k, v, ki)))
    return _mm(o.reshape(b, t, h * dh), p["o_proj"], q_), after, chosen


def _route(p, x, z):
    """``softmax(h W_r)`` over all router outputs in float32, the
    ``top_k`` largest, renormalised. ``(indices, weights)`` ``(rows,
    top_k)``."""
    scores = jax.nn.softmax(
        jnp.dot(x.reshape(-1, x.shape[-1]), p["router"], precision=HI), axis=-1)
    top, idx = jax.lax.top_k(scores, z["top_k"])
    return idx, top / jnp.sum(top, axis=-1, keepdims=True)


def _experts(p, x, idx, w, z, q_):
    """The held experts one after another under a dense 0/weight mask,
    each a SwiGLU."""
    flat = x.reshape(-1, x.shape[-1])

    def one_expert(acc, xs):
        e, wg, wu, wd = xs
        weight = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
        hidden = jax.nn.silu(_mm(flat, wg, q_)) * _mm(flat, wu, q_)
        return acc + weight[:, None] * _mm(hidden, wd, q_), None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(flat),
        (z["first"] + jnp.arange(z["E"]), p["experts_gate"], p["experts_up"],
         p["experts_down"]),
    )
    return routed.reshape(x.shape)


def forward(params, tokens, state, fresh, config: Dict, num_actions: int,
            precision: str = "float32"):
    """A fragment from its start state. ``tokens`` ``(B, T)`` int;
    ``state`` as ``initial_state`` (caches in any float type); ``fresh``
    ``(B, T)`` bool (the token opens an episode). Returns ``{"logits"
    (B, T, V), "value" (B, T), "state", "routes" (layers, B*T, k),
    "selected" (layers, B, T, S + T) bool}``: each query's chosen rows,
    the cache's slots and then the fragment's own."""
    z, q_ = sizes(config, num_actions), _QUANT[precision]
    fresh = fresh.astype(bool)
    pos0 = state[-1]
    positions, pos1 = _positions(pos0, fresh)
    x = params["embed"]["embedding"][tokens.astype(jnp.int32)]
    state_out, routes, selected = [], [], []
    for i in range(z["L"]):
        p = params[f"layer_{i}"]

        @jax.checkpoint
        def layer(x, p, caches):
            y, after, chosen = _attention(
                p, _rms(x, p["input_norm"], z["eps"]), caches, pos0, positions,
                fresh, z, q_)
            x = x + y
            g = _rms(x, p["post_norm"], z["eps"])
            idx, w = _route(p, g, z)
            return x + _experts(p, g, idx, w, z, q_), after, idx, chosen

        x, after, idx, chosen = layer(x, p, tuple(state[3 * i:3 * i + 3]))
        state_out.extend(after)
        routes.append(idx)
        selected.append(chosen)
    state_out.append(pos1)
    feat = _rms(x, params["final_norm"]["weight"], z["eps"])
    logits = _mm(feat, params["head"]["kernel"], q_)
    value = (
        jnp.dot(feat, params["value"]["kernel"], precision=HI)
        + params["value"]["bias"]
    )[..., 0]
    return {"logits": logits, "value": value, "state": tuple(state_out),
            "routes": jnp.stack(routes), "selected": jnp.stack(selected)}


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
