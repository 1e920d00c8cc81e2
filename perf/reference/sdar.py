"""Plain reference of SDAR-30B-A3B-Chat (``model_type: sdar_moe``;
SDAR, arXiv:2510.06303) as a block-diffusion PPO policy: ``jax.numpy``,
float32, every product at precision "highest", nothing from ``ray_tpu``,
no cache that is generated into and no kernel.

Written the long way where the system is clever. The system generates a
block in ``S`` denoise forwards and a commit forward against a cache and
replays a fragment in a clean and ``S`` noisy passes; here ONE forward a
denoise step runs ONE sequence of ``2T`` rows a stream, the ``T`` noisy
copies and the ``T`` clean tokens (BD3-LM's training layout,
arXiv:2503.09573), over the rows the stream had stored before the
fragment, under that paper's three masks written in POSITIONS (``blk(p)
= p // B``; a stored row is a clean row of an earlier block):

- a noisy query sees the noisy keys of its OWN block (block-diagonal);
- a noisy query sees the clean keys of STRICTLY EARLIER blocks;
- a clean query sees the clean keys of its own and earlier blocks
  (block-causal);

each within the query's episode. The experts run one after another over
the 16 held. Its own float64 GAE, PPO loss, global-norm clip and Adam
step are at the end.

Layer equations (the published config; what it does not state is
``assumed`` in the configuration file with the other reading named).
``x`` the stream, ``rms(x) = x * rsqrt(mean(x^2) + eps) * (1 + w)`` (norm
weights stored zero-centred, as the policy stores every norm; with seeded
weights a reparametrisation), ``eps`` 1e-6, no bias anywhere. ``B`` =
``block_length``, ``S`` = ``denoising_steps``, ``n = B / S``.

- ``h = rms(x)``; ``q = h W_q`` as (32, 128), ``k = h W_k``, ``v = h
  W_v`` as (4, 128); ``q`` and ``k`` RMS-normed over the head with a
  learned weight (the ``qwen3_moe`` convention); RoPE theta 1e6,
  rotate-half over all 128; scores ``q . k / sqrt(128)``; a key at
  ``p_k`` is seen from ``p_q`` of the same episode iff ``p_k // B <= p_q
  // B`` (block-causal: inside a block attention goes both ways);
  softmax in float32; ``x <- x + (P v) W_o``;
- ``h = rms(x)``; ``s = softmax(h W_r)`` over all 128 in float32; top-8;
  weights ``s_i / sum of the eight`` (``norm_topk_prob``); ``y = sum_i
  w_i E_i(h)`` over the experts held here, ``E_i`` a SwiGLU of width 768;
  no shared expert; every layer sparse; ``x <- x + y``;
- final norm, untied head over the rows held, a value head beside it.
- ``[MASK]`` is a row of the embedding (``mask_token_id``); its logit is
  left as it is, and whether a position is masked is read from the trace
  ``u``, never from the id.
- Generation of block ``b`` from the blocks before it: ``z_0 =
  [MASK]^B``; for ``s = 0 .. S-1`` one forward of ``z_s``; at every
  still-masked ``i`` a candidate ``c_i ~ softmax(logits_s[i])`` with
  confidence ``softmax(logits_s[i])[c_i]``; the ``n`` masked positions of
  highest confidence (ties to the lower) are committed, ``u_i = s``.
  (SDAR's ``block_diffusion_generate``, ``low_confidence_static``.) The
  reference does not sample: it is handed the tokens and ``u`` and
  recomputes what pass ``u_i`` gave at ``i``.
- PPO over the trace (TraceRL's token-level form, arXiv:2509.06949):
  token ``i``'s action is ``c_i``; its old and new log-probability and
  its value are those of the forward that committed it, ``(u_i, i)``;
  GAE runs over tokens in position order; a fragment's tail bootstraps
  from the value at position 0 of the next block's first, all-mask
  forward (:func:`first_value`).

``precision`` "int8" and "fp8" are the CONTROLS: inputs and weights of
q/k/v/o, the experts' three products and the head rounded per tensor to
127 levels or to float8 e4m3, and their cotangents likewise: one step
below the bfloat16 operands the configuration states. The router is
float32 in the policy and stays so here.

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
# streams whose keys, values and scores are alive at once
STREAMS = 2


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
    gen = c["algo_config"]["model"]["sequence_lm"]
    return {
        "D": int(c["hidden_size"]), "V": int(num_actions),
        "L": int(c["num_hidden_layers"]), "eps": float(c["rms_norm_eps"]),
        "H": int(c["num_attention_heads"]), "Hkv": int(c["num_key_value_heads"]),
        "dh": int(c["head_dim"]), "theta": float(c["rope_theta"]),
        "S": int(c["max_position_embeddings"]),
        "E": int(held), "first": int(first),
        "R": int(c.get("router_outputs", c["num_experts"])),
        "top_k": int(c["num_experts_per_tok"]),
        "F": int(c["moe_intermediate_size"]),
        "T": int(c["algo_config"]["model"]["max_seq_len"]),
        "block": int(gen["block_length"]), "steps": int(gen["denoising_steps"]),
        "mask": int(gen["mask_token_id"]),
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
    weights (the q/k norms' too) and the value bias 0.1 x normal, small and not zero (a
    weight the system dropped would otherwise go unseen)."""
    shapes = param_shapes(config, num_actions)
    # XLA's own bit generator: a threefry stream for 370 M weights
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


def initial_state(z: Dict, rows: int):
    """As the policy lays it out: keys and values a layer (bfloat16),
    last the position."""
    state = []
    for _ in range(2 * z["L"]):
        state.append(jnp.zeros((rows, z["S"], z["Hkv"] * z["dh"]), jnp.bfloat16))
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
    cache came in (the policy's carry is bfloat16, and 32 streams' caches
    in float32 beside a control's do not fit the chip)."""
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


def _attention(p, x, k_cache, v_cache, pos0, positions, episode, z, q_):
    """``x`` ``(B, 2T, D)``: the noisy copies, then the clean tokens,
    both at ``positions`` ``(B, T)``. Every query over every stored row
    and all ``2T`` own rows in one softmax under the three masks.
    Returns the output and the clean rows' keys and values ``(B, T, Hkv
    x dh)``."""
    b, t2, _ = x.shape
    t = t2 // 2
    h, hkv, dh, blk = z["H"], z["Hkv"], z["dh"], z["block"]
    both = jnp.concatenate([positions, positions], axis=1)
    q = _rms(_mm(x, p["q_proj"], q_).reshape(b, t2, h, dh), p["q_norm"], z["eps"])
    k = _rms(_mm(x, p["k_proj"], q_).reshape(b, t2, hkv, dh), p["k_norm"], z["eps"])
    v = _mm(x, p["v_proj"], q_)
    q, k = _rope(q, both, z["theta"]), _rope(k, both, z["theta"])
    k = k.reshape(b, t2, hkv * dh)
    k_old, at = _stored_rows(k_cache, pos0)
    v_old, _ = _stored_rows(v_cache, pos0)
    noisy = jnp.arange(t2) < t  # which of the own rows are noisy copies

    def some_streams(xs):
        q, k, v, k_old, v_old, at, ep, pos = xs
        heads = lambda a: jnp.repeat(
            a.reshape(a.shape[:2] + (hkv, dh)), h // hkv, axis=2)
        keys = heads(jnp.concatenate([k_old, k], axis=1))
        values = heads(jnp.concatenate([v_old, v], axis=1))
        scores = jnp.einsum("bthd,bshd->bhts", q, keys, precision=HI) * (dh ** -0.5)
        # every key's position, episode and kind (a stored row is a
        # clean row of the episode the fragment starts in: number 0)
        n = at.shape[1]
        key_pos = jnp.concatenate([at, pos, pos], axis=1)  # (b, n + 2t)
        key_ep = jnp.concatenate([jnp.zeros_like(at), ep, ep], axis=1)
        key_noisy = jnp.concatenate([jnp.zeros((n,), bool), noisy])
        q_pos, q_ep = jnp.concatenate([pos, pos], 1), jnp.concatenate([ep, ep], 1)
        q_blk, k_blk = q_pos[:, :, None] // blk, key_pos[:, None, :] // blk
        qn, kn = noisy[None, :, None], key_noisy[None, None, :]
        diagonal = qn & kn & (q_blk == k_blk)
        earlier = qn & ~kn & (q_blk > k_blk)
        causal = ~qn & ~kn & (q_blk >= k_blk)
        mask = (diagonal | earlier | causal) & (key_pos >= 0)[:, None] & (
            key_ep[:, None] == q_ep[:, :, None])
        w = jax.nn.softmax(jnp.where(mask[:, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhts,bshd->bthd", w, values, precision=HI)

    o = _in_groups(
        some_streams, (q, k, v, k_old, v_old, at, episode, positions)
    ).reshape(b, t2, h * dh)
    return _mm(o, p["o_proj"], q_), k[:, t:], v[:, t:]


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


def _denoise_forward(params, noisy, tokens, state, pos0, positions, episode, z, q_):
    """ONE forward of the ``2T``-row sequence ``[noisy | tokens]``:
    ``(logits (B, T, V), value (B, T))`` of the noisy rows, the clean
    rows' keys and values a layer, the clean rows' routes a layer."""
    t = tokens.shape[1]
    x = params["embed"]["embedding"][jnp.concatenate([noisy, tokens], axis=1)]
    rows, routes = [], []
    for i in range(z["L"]):
        p = params[f"layer_{i}"]

        @jax.checkpoint
        def layer(x, p, k_cache, v_cache):
            y, k_clean, v_clean = _attention(
                p, _rms(x, p["input_norm"], z["eps"]), k_cache, v_cache, pos0,
                positions, episode, z, q_)
            x = x + y
            g = _rms(x, p["post_norm"], z["eps"])
            idx, w = _route(p, g, z)
            return x + _experts(p, g, idx, w, z, q_), k_clean, v_clean, idx

        x, k_clean, v_clean, idx = layer(x, p, state[2 * i], state[2 * i + 1])
        rows.extend([k_clean, v_clean])
        routes.append(idx.reshape(x.shape[0], 2 * t, -1)[:, t:].reshape(
            -1, idx.shape[-1]))
    feat = _rms(x[:, :t], params["final_norm"]["weight"], z["eps"])
    logits = _mm(feat, params["head"]["kernel"], q_)
    value = (
        jnp.dot(feat, params["value"]["kernel"], precision=HI)
        + params["value"]["bias"]
    )[..., 0]
    return logits, value, rows, jnp.stack(routes)


def forward(params, tokens, trace, state, fresh, config: Dict, num_actions: int,
            precision: str = "float32"):
    """A fragment from its start state. ``tokens`` ``(B, T)`` the
    committed tokens, ``trace`` ``(B, T)`` the pass that committed each;
    ``state`` as ``initial_state`` (caches in any float type); ``fresh``
    ``(B, T)`` bool (the token opens an episode). One ``2T``-row forward
    a denoise step; token ``i``'s logits and value are pass
    ``trace[i]``'s at its noisy row. Returns ``{"logits" (B, T, V),
    "value" (B, T), "state", "routes" (layers, B*T, k) of the clean
    rows}``."""
    z, q_ = sizes(config, num_actions), _QUANT[precision]
    fresh = fresh.astype(bool)
    pos0 = state[-1]
    positions, pos1 = _positions(pos0, fresh)
    episode = jnp.cumsum(fresh.astype(jnp.int32), axis=1)
    tokens, trace = tokens.astype(jnp.int32), trace.astype(jnp.int32)
    logits = value = None
    for s in range(z["steps"]):
        noisy = jnp.where(trace < s, tokens, z["mask"])
        lg, vl, rows, routes = _denoise_forward(
            params, noisy, tokens, state, pos0, positions, episode, z, q_)
        mine = trace == s
        logits = lg if logits is None else jnp.where(mine[..., None], lg, logits)
        value = vl if value is None else jnp.where(mine, vl, value)
    state_out = [
        _write(cache, row, positions) for cache, row in zip(state[:-1], rows)]
    state_out.append(pos1)
    return {"logits": logits, "value": value, "state": tuple(state_out),
            "routes": routes}


def first_value(params, state, fresh, config: Dict, num_actions: int,
                precision: str = "float32"):
    """``(B,)``: the value at position 0 of the first, all-mask forward
    of each stream's NEXT block from ``state`` (``fresh`` ``(B,)``: the
    block opens an episode): what a fragment's tail bootstraps from."""
    z = sizes(config, num_actions)
    b = state[-1].shape[0]
    block = jnp.full((b, z["block"]), z["mask"], jnp.int32)
    opens = jnp.zeros((b, z["block"]), bool).at[:, 0].set(fresh.reshape(b))
    out = forward(params, block, jnp.zeros_like(block), state, opens, config,
                  num_actions, precision)
    return out["value"][:, 0]


# -- batches, loss, and the rest of PPO ---------------------------------------------


def make_trace(rng: np.random.Generator, z: Dict, shape):
    """A trace a sampler could have left: in every block of ``B``
    positions exactly ``B / S`` committed in each pass."""
    blocks = int(np.prod(shape)) // z["block"]
    order = np.argsort(rng.random((blocks, z["block"])), axis=1)
    return (order // (z["block"] // z["steps"])).reshape(shape).astype(np.int32)


def make_state(rng: np.random.Generator, z: Dict, rows: int, fragment: int):
    """Seeded start states: streams on a block's first position
    somewhere inside an episode, EVERY slot of every cache filled with
    rows of order one rounded to bfloat16 (what earlier episodes leave
    behind: a row that must not be seen is there to be seen)."""
    blk = z["block"]
    pos0 = (rng.integers(0, (z["S"] - fragment) // blk + 1, rows) * blk).astype(
        np.int32)
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
    row a fragment). The tokens are the ``actions`` column and their
    trace ``unmask_step``; the model reads no observation. The second
    fragment has an episode boundary inside it, on a block's first
    token, where there is room."""
    z = sizes(config, num_actions)
    t, blk = z["T"], z["block"]
    frags = rows // t
    prev = rng.normal(0.0, 1.0, (rows, num_actions)).astype(np.float32)
    actions = rng.integers(0, num_actions, rows).astype(np.int32)
    logp = prev - np.log(np.sum(np.exp(prev), axis=1, keepdims=True))
    resets = np.zeros((frags, t), np.float32)
    state = make_state(rng, z, frags, t)
    resets[0, 0] = 1.0 if state[-1][0] == 0 else 0.0
    if frags > 1 and t > 2 * blk:
        resets[1, (t // 3) // blk * blk] = 1.0
    batch = {
        "obs": rng.integers(0, num_actions, (rows, 1)).astype(np.int32),
        "actions": actions,
        "unmask_step": make_trace(rng, z, (rows,)),
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
        batch["actions"].reshape(rows // t, t),
        batch["unmask_step"].reshape(rows // t, t),
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
