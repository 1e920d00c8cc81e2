"""Plain reference of the EvaByte block stack (``model_type: evabyte``,
``attention_class: "eva"``) as a byte-level PPO policy: ``jax.numpy``,
float32, every product at precision "highest", nothing from ``ray_tpu``.

Written the long way where the system is clever: no kernel, no blocks
skipped, and between the two ends of ``forward`` no store, only ROWS
WITH POSITIONS. Every exact row (a stored one or one of the fragment's
own) has a position and an episode; EVERY token of the fragment gets a
summary, pooled over all the exact rows under a dense mask ("of the
token's episode, in the token's chunk"), and the summary counts only
where the token is its chunk's last; the attention is one dense masked
score matrix over the exact rows, the stored summaries and those made
here, a few streams at a time. Only ``_stored`` and ``_write`` know how
the policy lays its state out, because the policy's carry is the state
this is handed and the state it is compared with
(``perf/checks/rollout_fragment.py``, slot by slot).

The layer (Zheng, Yuan, Wang, Kong, "Efficient Attention via Control
Variates", ICLR 2023, arXiv:2302.04542, in the causal, learned-proposal
parameterisation of the EvaByte release; what the catalogue's row does
not state is ``assumed`` in the configuration file). Stream ``x_t`` in
float32, layer by layer:

- ``h = rms(x)(1 + w)`` (``norm_add_unit_offset``), eps ``rms_norm_eps``;
  ``q, k, v = W_q h, W_k h, W_v h``, heads of ``head_dim``, no bias; RoPE
  (rotate-half, ``rope_theta``, the whole head) on ``q`` and ``k`` at the
  token's position in its episode; ``s = head_dim^-1/2``, ``W =
  window_size``, ``c = chunk_size``; per head learned ``phi``, ``mu``;
- chunk ``j`` = positions ``c j .. c j + c - 1``; its summary, made when
  its last token is written: ``kbar_j = sum_i softmax_i(phi . k_i) k_i``,
  ``vbar_j = sum_i softmax_i(mu . k_i) v_i`` (``k_i`` after RoPE; no
  ``-|k|^2 / 2`` term in the pooling logits);
- ``S_t = {i : i // W == t // W, i <= t}`` (the query's own window,
  exact); ``R_t = {j : c j + c - 1 < W (t // W)}`` (every chunk of every
  EARLIER window);
- ``o_t = [sum_S e^{s q.k_i} v_i + sum_R e^{s q.kbar_j} vbar_j] / [sum_S
  e^{s q.k_i} + sum_R e^{s q.kbar_j}]``: ONE softmax over both sets;
- ``x += W_o o``; ``x += W_down(silu(W_gate h') * W_up h')``, ``h'`` by
  its own norm. Final norm, head, logits float32.

The chip holds ``num_attention_heads`` of the layer's heads
(``heads_held``): what the absent heads would add to ``W_o o`` is left
out, here as in the policy.

``precision`` "int8" and "fp8" are the CONTROLS: inputs and weights of
q/k/v/o, the three feed-forward products and the head rounded per tensor
to 127 levels or to float8 e4m3, and their cotangents likewise: one step
below the bfloat16 operands the configuration states.

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
# streams whose rows, pooling weights and scores are alive at once
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
    heads = int(c["num_attention_heads"])
    return {
        "D": int(c["hidden_size"]), "V": int(num_actions),
        "L": int(c["num_hidden_layers"]), "eps": float(c["rms_norm_eps"]),
        "H": heads, "dh": int(c.get("head_dim") or int(c["hidden_size"]) // heads),
        "theta": float(c["rope_theta"]), "F": int(c["intermediate_size"]),
        "S": int(c["max_position_embeddings"]),
        "W": int(c["window_size"]), "c": int(c["chunk_size"]),
        # layers of the published model: the scale of the projections
        # that write the stream
        "depth": int((c.get("published") or {}).get(
            "num_hidden_layers", c["num_hidden_layers"])),
        "T": int(c["algo_config"]["model"]["max_seq_len"]),
    }


def param_shapes(config: Dict, num_actions: int) -> Dict[str, Dict[str, tuple]]:
    z = sizes(config, num_actions)
    d, wide, f = z["D"], z["H"] * z["dh"], z["F"]
    out = {
        "embed": {"embedding": (z["V"], d)},
        "final_norm": {"weight": (d,)},
        "head": {"kernel": (d, z["V"])},
        "value": {"kernel": (d, 1), "bias": (1,)},
    }
    for i in range(z["L"]):
        out[f"layer_{i}"] = {
            "input_norm": (d,), "post_norm": (d,),
            "q_proj": (d, wide), "k_proj": (d, wide), "v_proj": (d, wide),
            "o_proj": (wide, d),
            "eva_mu": (z["H"], z["dh"]), "eva_phi": (z["H"], z["dh"]),
            "mlp_gate": (d, f), "mlp_up": (d, f), "mlp_down": (f, d),
        }
    return out


def init_params(key, config: Dict, num_actions: int, host: bool = True):
    """Seeded weights, one jitted call a group, brought to the host
    (``host=False``: left on the device). ASSUMED, as the other
    references draw them: matrices normal with variance 1 / rows (the
    output head a quarter of that), the embedding normal, norm weights
    and the value bias 0.1 x normal; and of this family: the projections
    that WRITE the stream (``o_proj``, ``mlp_down``) over ``sqrt(2 x
    layers of the published model)`` (the release's scaled
    initialisation), ``mu`` and ``phi`` ``clip(normal, -1, 1) x
    head_dim^-1/2``."""
    shapes = param_shapes(config, num_actions)
    depth = sizes(config, num_actions)["depth"]
    # XLA's own bit generator: a threefry stream for 610 M weights
    # compiles for most of a minute on the chip
    key = jax.random.wrap_key_data(
        jnp.tile(jax.random.key_data(key).astype(jnp.uint32).ravel(), 2)[:4],
        impl="rbg")

    def make(key, group):
        out = {}
        for n, (leaf, shape) in enumerate(sorted(shapes[group].items())):
            x = jax.random.normal(jax.random.fold_in(key, n), shape, jnp.float32)
            if leaf in ("eva_mu", "eva_phi"):
                x = jnp.clip(x, -1.0, 1.0) * shape[-1] ** -0.5
            elif len(shape) == 1:
                x = 0.1 * x
            elif leaf != "embedding":
                x = x / np.sqrt(shape[-2])
                if group == "head":
                    x = 0.5 * x
                if leaf in ("o_proj", "mlp_down"):
                    x = x / np.sqrt(2.0 * depth)
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


def _rope(x, positions, theta):
    """Rotate-half over the whole head. ``x`` ``(B, T, H, D)``;
    ``positions`` ``(B, T)``."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[..., None, None] * inv
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def store_rows(z: Dict):
    """Rows of a stream's two stores: a window's, and a chunk's row for
    every chunk of an episode."""
    return min(z["W"], z["S"]), -(-z["S"] // z["c"])


def initial_state(z: Dict, rows: int):
    """As the policy lays it out: a layer's window keys, window values,
    summary keys, summary values (bfloat16), last the position."""
    exact, pooled = store_rows(z)
    state = []
    for _ in range(z["L"]):
        for n in (exact, exact, pooled, pooled):
            state.append(jnp.zeros((rows, n, z["H"] * z["dh"]), jnp.bfloat16))
    state.append(jnp.zeros((rows,), jnp.int32))
    return tuple(state)


def _positions(pos0, fresh):
    """Each token's position in its episode: a fresh token is at 0."""
    def step(pos, f):
        pos = jnp.where(f, 0, pos)
        return pos + 1, pos

    end, positions = jax.lax.scan(step, pos0, fresh.T)
    return positions.T, end


def _stored(window_store, pos0, z):
    """The exact rows a stream holds, as rows with positions: ``(rows
    (B, n, row), positions (B, n))`` for the ``n`` slots, the position
    below zero where the slot holds no row of the stream's CURRENT
    window (position ``p`` lies in slot ``p mod n``; a slot at or past
    ``pos0 mod n`` holds the window before's row, or none)."""
    n = window_store.shape[1]
    slot = jnp.arange(n)[None]
    opened = pos0[:, None] - pos0[:, None] % n  # the current window's first position
    at = jnp.where(slot < pos0[:, None] % n, opened + slot, -1)
    return window_store.astype(jnp.float32), at


def _write(store, rows, slots, keep):
    """``store`` after the fragment's ``rows`` ``(B, T, row)``, token by
    token, each in its slot where ``keep`` says so, in the type the
    store came in."""
    b = store.shape[0]

    def one(c, xs):
        row_t, slot_t, keep_t = xs
        slot_t = jnp.where(keep_t, slot_t, c.shape[1])
        return c.at[jnp.arange(b), slot_t].set(row_t.astype(c.dtype), mode="drop"), None

    out, _ = jax.lax.scan(
        one, store, (jnp.moveaxis(rows, 1, 0), slots.T, keep.T))
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


def _attention(p, x, stores, pos0, positions, fresh, z, q_):
    """EVA attention by the definition. Returns the output and the four
    stores after the fragment."""
    b, t, _ = x.shape
    h, dh, w, c = z["H"], z["dh"], z["W"], z["c"]
    win_k, win_v, sum_k, sum_v = stores
    q = _rope(_mm(x, p["q_proj"], q_).reshape(b, t, h, dh), positions, z["theta"])
    k = _rope(_mm(x, p["k_proj"], q_).reshape(b, t, h, dh), positions, z["theta"])
    v = _mm(x, p["v_proj"], q_).reshape(b, t, h, dh)
    episode = jnp.cumsum(fresh.astype(jnp.int32), axis=1)
    k_old, at = _stored(win_k, pos0, z)
    v_old, _ = _stored(win_v, pos0, z)
    # the stored summaries: row j is chunk j's, there below the start
    chunks = jnp.arange(sum_k.shape[1])[None]
    stored_end = jnp.where(chunks < pos0[:, None] // c, c * chunks + c - 1, -1)

    def some_streams(xs):
        (q, k, v, k_old, v_old, at, kbar_old, vbar_old, stored_end, ep, pos) = xs
        heads = lambda a: a.reshape(a.shape[:2] + (h, dh))
        # every exact row: its position (below zero: none) and episode
        # (a stored row is of the episode the fragment starts in: 0)
        keys = jnp.concatenate([heads(k_old), k], axis=1)
        values = jnp.concatenate([heads(v_old), v], axis=1)
        key_pos = jnp.concatenate([at, pos], axis=1)  # (b, n + t)
        key_ep = jnp.concatenate([jnp.zeros_like(at), ep], axis=1)
        # a summary for every token, over the rows of its episode in its
        # chunk up to itself; it counts where the token ends its chunk
        mine = (key_pos >= 0)[:, None] & (key_ep[:, None] == ep[:, :, None]) & (
            key_pos[:, None] // c == pos[:, :, None] // c) & (
            key_pos[:, None] <= pos[:, :, None])  # (b, t, n + t)
        pool = lambda vec: jax.nn.softmax(jnp.where(
            mine[:, None], jnp.einsum("bshd,hd->bhs", keys, vec, precision=HI)[:, :, None],
            -jnp.inf), axis=-1)  # (b, h, t, n + t)
        kbar = jnp.einsum("bhts,bshd->bthd", pool(p["eva_phi"]), keys, precision=HI)
        vbar = jnp.einsum("bhts,bshd->bthd", pool(p["eva_mu"]), values, precision=HI)
        made_end = jnp.where(pos % c == c - 1, pos, -1)  # (b, t)
        # one softmax over the exact rows and the summaries
        all_k = jnp.concatenate([keys, heads(kbar_old), kbar], axis=1)
        all_v = jnp.concatenate([values, heads(vbar_old), vbar], axis=1)
        scores = jnp.einsum("bthd,bshd->bhts", q, all_k, precision=HI) * (dh ** -0.5)
        query = pos[:, :, None]
        exact = (key_pos >= 0)[:, None] & (key_ep[:, None] == ep[:, :, None]) & (
            key_pos[:, None] <= query) & (key_pos[:, None] // w == query // w)
        ends = jnp.concatenate([stored_end, made_end], axis=1)
        ends_ep = jnp.concatenate([jnp.zeros_like(stored_end), ep], axis=1)
        pooled = (ends >= 0)[:, None] & (ends_ep[:, None] == ep[:, :, None]) & (
            ends[:, None] < w * (query // w))
        mask = jnp.concatenate([exact, pooled], axis=-1)
        weights = jax.nn.softmax(jnp.where(mask[:, None], scores, -jnp.inf), axis=-1)
        return (jnp.einsum("bhts,bshd->bthd", weights, all_v, precision=HI),
                kbar, vbar)

    o, kbar, vbar = _in_groups(some_streams, (
        q, k, v, k_old, v_old, at, sum_k.astype(jnp.float32),
        sum_v.astype(jnp.float32), stored_end, episode, positions))
    # the state after, token by token as a rollout would leave it: a
    # row a token in the window store, a row a completed chunk in the
    # summary store
    flat = lambda a: a.reshape(b, t, h * dh)
    exact_rows = win_k.shape[1]
    every = jnp.ones_like(fresh)
    ends_chunk = positions % c == c - 1
    return (
        _mm(o.reshape(b, t, h * dh), p["o_proj"], q_),
        (_write(win_k, flat(k), positions % exact_rows, every),
         _write(win_v, flat(v), positions % exact_rows, every),
         _write(sum_k, flat(kbar), positions // c, ends_chunk),
         _write(sum_v, flat(vbar), positions // c, ends_chunk)),
    )


def forward(params, tokens, state, fresh, config: Dict, num_actions: int,
            precision: str = "float32"):
    """A fragment from its start state. ``tokens`` ``(B, T)`` int;
    ``state`` as ``initial_state`` (stores in any float type); ``fresh``
    ``(B, T)`` bool (the token opens an episode). Returns ``{"logits"
    (B, T, V), "value" (B, T), "state", "routes"}``; ``routes`` is one
    layer's, of zeros: no layer routes."""
    z, q_ = sizes(config, num_actions), _QUANT[precision]
    fresh = fresh.astype(bool)
    pos0 = state[-1]
    positions, pos1 = _positions(pos0, fresh)
    x = params["embed"]["embedding"][tokens.astype(jnp.int32)]
    b, t = tokens.shape
    state_out = []
    for i in range(z["L"]):
        @jax.checkpoint
        def layer(x, p, stores):
            y, after = _attention(
                p, _rms(x, p["input_norm"], z["eps"]), stores, pos0, positions,
                fresh, z, q_)
            x = x + y
            x = x + _swiglu(_rms(x, p["post_norm"], z["eps"]), p["mlp_gate"],
                            p["mlp_up"], p["mlp_down"], q_)
            return x, after

        x, after = layer(x, params[f"layer_{i}"], tuple(state[4 * i:4 * i + 4]))
        state_out.extend(after)
    state_out.append(pos1)
    feat = _rms(x, params["final_norm"]["weight"], z["eps"])
    logits = _mm(feat, params["head"]["kernel"], q_)
    value = (
        jnp.dot(feat, params["value"]["kernel"], precision=HI)
        + params["value"]["bias"]
    )[..., 0]
    return {"logits": logits, "value": value, "state": tuple(state_out),
            "routes": jnp.zeros((1, b * t, 1), jnp.int32)}


# -- batches, loss, and the rest of PPO ---------------------------------------------


def make_state(rng: np.random.Generator, z: Dict, rows: int, fragment: int):
    """Seeded start states: streams somewhere inside an episode, EVERY
    slot of every store filled with rows of order one rounded to
    bfloat16 (what earlier windows and episodes leave behind: a row
    that must not be seen is there to be seen)."""
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
