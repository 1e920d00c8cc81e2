"""Plain reference of the Nemotron-H block stack (Hugging Face
``nemotron_h``; Nemotron-H, arXiv:2504.03624; NVIDIA-Nemotron-3-Nano-30B-A3B)
as a token-level PPO policy: ``jax.numpy``, float32, every product at
precision "highest", nothing from ``ray_tpu``.

Written the long way where the system is clever. A state-space block is
the recurrence ONE TOKEN AT A TIME under ``lax.scan`` (convolution
window, decay, rank-one write, read), never in chunks, each head taking
its group's ``B`` and ``C`` rows by an index; a new episode zeroes the
matrix and the window before its first token. The experts run one after
another under a dense 0/weight mask. Attention is the full masked score
matrix over every stored position and the fragment's own, a few streams
at a time. Its own GAE, PPO loss, global-norm clip and Adam step are at
the end.

The model (the published ``config.json`` and the family's modeling
file; what neither states is ``assumed`` in the configuration file).
``h = E[token]``, no multiplier. For each character of
``hybrid_override_pattern`` (its first ``num_hidden_layers``) ONE block
of ONE sublayer under ONE norm, ``h <- h + f(rms(h; w_l))``, ``rms(x) =
x * rsqrt(mean(x^2) + eps) * (1 + w)`` with ``eps`` 1e-5 (DEPARTURE: the
weight is stored zero-centred, as the policy stores every norm; with
seeded weights a reparametrisation). After the last block ``rms(h;
w_f)``, then the UNTIED head ``logits = h W_head`` and a value head
beside it. No positions anywhere.

- ``M``, Mamba-2 (arXiv:2405.21060), ``H = mamba_num_heads`` heads of
  ``P = mamba_head_dim``, state ``N = ssm_state_size``, ``G = n_groups``:
  ``[z | xBC | dt] = u W_in`` of widths ``H P | H P + 2 G N | H``; ``xBC
  <- silu(conv1d(xBC) + b_conv)``, causal, depthwise, width
  ``conv_kernel``; split into ``x`` ``(H, P)``, ``B`` ``(G, N)``, ``C``
  ``(G, N)``; ``dt = softplus(dt + dt_bias)`` (not clamped), ``A =
  -exp(A_log)``; head ``h`` with ``g = h // (H / G)``: ``S_h <- exp(dt_h
  A_h) S_h + dt_h x_h B_g^T``, ``y_h = S_h C_g + D_h x_h``; the gated
  norm BY GROUP: ``v = y * silu(z)``, each group's ``H P / G`` numbers
  over their own root mean square, times ``(1 + w_norm)``; ``v W_out``.
- ``E``, experts: ``s = sigmoid(u W_r)`` over ALL ``router_outputs``;
  chosen = the ``num_experts_per_tok`` largest of ``s + b_select``
  (``e_score_correction_bias``, a buffer: no gradient; ``n_group`` 1, so
  the group-limited choice is the plain one); weights ``s_chosen /
  sum(s_chosen) x routed_scaling_factor``; expert ``e``: ``relu(u
  W_up,e)^2 W_down,e``, NO gate matrix; the shared expert the same at
  width ``moe_shared_expert_intermediate_size``, added ungated. What
  the experts that are not held here would add is left out, as in the
  policy (``experts_held``).
- ``*``, attention: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` key/value heads of ``head_dim``, no biases, NO
  positions, no gate, no q/k norm, ``softmax(q k^T / sqrt(head_dim))``
  causal, ``o W_o``.

``precision`` "int8" and "fp8" are the CONTROLS: inputs and weights of
``W_in``, ``W_out``, q/k/v/o, the experts' and the shared expert's two
products and the head rounded per tensor to 127 levels or to float8
e4m3, and their cotangents likewise: one step below the bfloat16
operands the configuration states. The router is float32 in the policy
and stays so here.

Parameters are two levels deep in the policy's own names and shapes (a
state-space block is a stacked group of ONE layer,
``layers_<n>_<n>``, with a leading layer axis of 1; every other block
is ``layer_<n>``), so ``to_policy_tree`` is the identity and a caller
may hand the policy's arrays in as views. ``init_params`` returns HOST
arrays: beside 10.7 GB of policy state the chip has no room for a
second copy of the weights. The gradient of the recurrence keeps every
token's matrix of the streams it runs, 0.54 GB a stream and block at 256
tokens, so a block is recomputed in the backward pass
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
MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
# the projections whose output is added to the residual stream
_WRITES_THE_STREAM = ("out_proj", "o_proj", "experts_down", "shared_down")


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
    first, held = c.get("experts_held") or (0, int(c["n_routed_experts"]))
    if (int(c.get("n_group", 1)), int(c.get("topk_group", 1))) != (1, 1):
        raise ValueError("the reference chooses among all experts: n_group 1")
    z = {
        "D": int(c["hidden_size"]), "V": int(num_actions), "L": layers,
        # the published pattern's first ``num_hidden_layers`` characters
        "kinds": tuple(str(c["hybrid_override_pattern"])[:layers]),
        "eps": float(c["layer_norm_epsilon"]),
        "H": int(c["num_attention_heads"]), "Hkv": int(c["num_key_value_heads"]),
        "dh": int(c["head_dim"]), "S": int(c["max_position_embeddings"]),
        "Hs": int(c["mamba_num_heads"]), "P": int(c["mamba_head_dim"]),
        "N": int(c["ssm_state_size"]), "G": int(c["n_groups"]),
        "K": int(c["conv_kernel"]),
        "first": int(first), "E": int(held),
        "R_out": int(c.get("router_outputs", c["n_routed_experts"])),
        "top_k": int(c["num_experts_per_tok"]),
        "norm_topk": bool(c["norm_topk_prob"]),
        "route_scale": float(c["routed_scaling_factor"]),
        "F": int(c["moe_intermediate_size"]),
        "Fs": int(c["moe_shared_expert_intermediate_size"]),
        "T": int(c["algo_config"]["model"]["max_seq_len"]),
    }
    unknown = sorted(set(z["kinds"]) - {MAMBA, EXPERTS, ATTENTION})
    if unknown or str(c["mlp_hidden_act"]) != "relu2":
        raise ValueError(f"blocks {unknown} / {c['mlp_hidden_act']!r} are not written here")
    z["I"] = z["Hs"] * z["P"]
    z["C"] = z["I"] + 2 * z["G"] * z["N"]
    return z


def groups_of(z: Dict):
    """``[(group name, kind)]``, a block each: a state-space block is a
    stacked group of one layer."""
    return [(f"layers_{i}_{i}" if kind == MAMBA else f"layer_{i}", kind)
            for i, kind in enumerate(z["kinds"])]


def param_shapes(config: Dict, num_actions: int) -> Dict[str, Dict[str, tuple]]:
    z = sizes(config, num_actions)
    d, f, fs, e = z["D"], z["F"], z["Fs"], z["E"]
    out = {
        "embed": {"embedding": (z["V"], d)},
        "final_norm": {"weight": (d,)},
        "head": {"kernel": (d, z["V"])},
        "value": {"kernel": (d, 1), "bias": (1,)},
    }
    for name, kind in groups_of(z):
        if kind == MAMBA:
            layer = {
                "input_norm": (d,),
                "in_proj": (d, z["I"] + z["C"] + z["Hs"]),
                "conv": (z["C"], z["K"]), "conv_bias": (z["C"],),
                "dt_bias": (z["Hs"],), "A_log": (z["Hs"],), "D": (z["Hs"],),
                "ssm_norm": (z["I"],), "out_proj": (z["I"], d),
            }
            layer = {k: (1,) + shape for k, shape in layer.items()}
        elif kind == EXPERTS:
            layer = {
                "post_norm": (d,),
                "router": (d, z["R_out"]), "select_bias": (z["R_out"],),
                "experts_up": (e, d, f), "experts_down": (e, f, d),
                "shared_up": (d, fs), "shared_down": (fs, d),
            }
        else:
            layer = {
                "input_norm": (d,),
                "q_proj": (d, z["H"] * z["dh"]), "k_proj": (d, z["Hkv"] * z["dh"]),
                "v_proj": (d, z["Hkv"] * z["dh"]), "o_proj": (z["H"] * z["dh"], d),
            }
        out[name] = layer
    return out


def init_params(key, config: Dict, num_actions: int, host: bool = True):
    """Seeded weights, one jitted call a group, brought to the host
    (``host=False``: left on the device). ASSUMED, the config states
    none of it: matrices normal with variance 1 / rows (the convolution
    1 / width), the head half of that deviation (logits of deviation
    0.5: a random policy that is not near-deterministic); every
    projection that writes into the residual stream (``W_out``, ``W_o``,
    the experts' and the shared expert's ``W_down``) divided by ``sqrt(2
    x 52)``, the published depth: the scaled initialisation of a
    pre-norm stack that ``rescale_prenorm_residual`` names, without
    which ``relu(.)^2``, whose hidden activations are all positive, adds
    one token-independent vector a block to the stream and a seeded
    model's later routers send every token to the same experts (a held
    expert's load 3.7 times the mean by the fourth expert block where a
    trained model's selection bias holds it near 1; 1.3-1.5 with the
    scaling); the embedding
    normal; norm weights and biases 0.1 x normal, small and not zero (a
    weight the system dropped would otherwise go unseen); the selection
    bias 0.02 x normal (the scores it is added to lie in (0, 1) around a
    half: it changes some tokens' expert sets); the family's own
    initialisation for the recurrence: ``A_log = log(1..heads)``, ``D =
    1``, ``dt_bias`` the inverse softplus of a log-uniform step in
    (0.001, 0.1)."""
    shapes = param_shapes(config, num_actions)
    # XLA's own bit generator: a threefry stream for 667 M weights is a
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
            elif leaf == "select_bias":
                x = 0.02 * x
            elif len(one) == 1:
                x = 0.1 * x
            elif leaf == "embedding":
                pass
            elif leaf == "conv":
                x = x / np.sqrt(one[-1])
            else:
                x = x / np.sqrt(one[-2])
                if group == "head":
                    x = 0.5 * x
                elif leaf in _WRITES_THE_STREAM:
                    x = x / np.sqrt(2.0 * len(str(config["hybrid_override_pattern"])))
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


def _relu2_mlp(x, w_up, w_down, q_):
    """``relu(x W_up)^2 W_down``: two matrices, no gate."""
    return _mm(jnp.square(jax.nn.relu(_mm(x, w_up, q_))), w_down, q_)


def initial_state(z: Dict, rows: int):
    """As the policy lays it out, a block after another: a state-space
    block holds its matrix ``(rows, 1, heads, head, state)`` and the last
    ``conv - 1`` inputs of its convolution ``(rows, 1, conv - 1,
    channels)`` (a stacked run of ONE layer); the attention block its
    keys and values (float32 here); an expert block nothing; last the
    position."""
    state = []
    for kind in z["kinds"]:
        if kind == MAMBA:
            state.append(jnp.zeros((rows, 1, z["Hs"], z["P"], z["N"]), jnp.float32))
            state.append(jnp.zeros((rows, 1, z["K"] - 1, z["C"]), jnp.float32))
        elif kind == ATTENTION:
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
    """One state-space block's mixer over a fragment, token by token.
    ``matrix`` ``(B, heads, head, state)``, ``window`` ``(B, conv - 1,
    channels)`` the last inputs of the convolution. Returns the output
    and both after the fragment."""
    i, n, g, hs, ph = z["I"], z["N"], z["G"], z["Hs"], z["P"]
    zxbcdt = _mm(x, p["in_proj"], q_)
    gate, u, dt_raw = zxbcdt[..., :i], zxbcdt[..., i : i + z["C"]], zxbcdt[..., i + z["C"] :]
    a = -jnp.exp(p["A_log"])
    group_of_head = jnp.arange(hs) // (hs // g)

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
            # every head its group's rows: (b, G, N) -> (b, heads, N)
            b_t = mixed[:, i : i + g * n].reshape(-1, g, n)[:, group_of_head]
            c_t = mixed[:, i + g * n :].reshape(-1, g, n)[:, group_of_head]
            dt = jax.nn.softplus(dt_t + p["dt_bias"])  # (b, heads)
            s = jnp.exp(dt * a)[..., None, None] * s + (
                dt[..., None, None] * x_t[..., None] * b_t[:, :, None, :])
            y = jnp.einsum("bhpn,bhn->bhp", s, c_t, precision=HI) + (
                p["D"][:, None] * x_t)
            return (s, w[:, 1:]), y.reshape(-1, i)

        (s, w), ys = jax.lax.scan(
            token, (matrix, window),
            (jnp.moveaxis(u, 1, 0), jnp.moveaxis(dt_raw, 1, 0), fresh.T))
        return jnp.moveaxis(ys, 0, 1), s, w

    y, matrix, window = _in_groups(some_streams, (u, dt_raw, matrix, window, fresh))
    # the gated norm, a group at a time
    v = (y * jax.nn.silu(gate)).reshape(y.shape[:-1] + (g, i // g))
    v = v * jax.lax.rsqrt(jnp.mean(jnp.square(v), axis=-1, keepdims=True) + z["eps"])
    v = v.reshape(y.shape) * (1.0 + p["ssm_norm"])
    return _mm(v, p["out_proj"], q_), matrix, window


def _route(p, x, z):
    """A sigmoid for every router output; the ``top_k`` largest of score
    + selection bias are chosen, weighted by their scores WITHOUT the
    bias over their sum, times the scaling factor. ``(indices, weights)``
    ``(B*T, top_k)``."""
    scores = jax.nn.sigmoid(
        jnp.dot(x.reshape(-1, x.shape[-1]), p["router"], precision=HI))
    _, idx = jax.lax.top_k(scores + jax.lax.stop_gradient(p["select_bias"]), z["top_k"])
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if z["norm_topk"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx, w * z["route_scale"]


def _experts(p, x, idx, w, z, q_):
    """The held experts one after another under a dense 0/weight mask,
    and the shared expert once."""
    flat = x.reshape(-1, x.shape[-1])

    def one_expert(acc, xs):
        e, w_up, w_down = xs
        weight = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
        return acc + weight[:, None] * _relu2_mlp(flat, w_up, w_down, q_), None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(flat),
        (z["first"] + jnp.arange(z["E"]), p["experts_up"], p["experts_down"]),
    )
    shared = _relu2_mlp(flat, p["shared_up"], p["shared_down"], q_)
    return (routed + shared).reshape(x.shape)


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
        scores = jnp.einsum("bthd,bshd->bhts", q, keys, precision=HI) / np.sqrt(dh)
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
    (B, T, V), "value" (B, T), "state", "routes" (expert blocks, B*T,
    k)}``."""
    z, q_ = sizes(config, num_actions), _QUANT[precision]
    fresh = fresh.astype(bool)
    pos0 = state[-1]
    positions, pos1 = _positions(pos0, fresh)
    x = params["embed"]["embedding"][tokens.astype(jnp.int32)]

    @jax.checkpoint
    def mamba_block(x, p, matrix, window):
        p = {k: v[0] for k, v in p.items()}  # the run's one layer
        y, matrix, window = _mamba(
            p, _rms(x, p["input_norm"], z["eps"]), matrix[:, 0], window[:, 0],
            fresh, z, q_)
        return x + y, matrix[:, None], window[:, None]

    @jax.checkpoint
    def expert_block(x, p):
        u = _rms(x, p["post_norm"], z["eps"])
        idx, w = _route(p, u, z)
        return x + _experts(p, u, idx, w, z, q_), idx

    @jax.checkpoint
    def attention_block(x, p, k_cache, v_cache):
        y, k_after, v_after = _attention(
            p, _rms(x, p["input_norm"], z["eps"]), k_cache, v_cache, pos0,
            positions, fresh, z, q_)
        return x + y, k_after, v_after

    state_out, routes, at = [], [], 0
    for name, kind in groups_of(z):
        p = params[name]
        if kind == MAMBA:
            x, matrix, window = mamba_block(x, p, state[at], state[at + 1])
            state_out.extend([matrix, window])
            at += 2
        elif kind == EXPERTS:
            x, idx = expert_block(x, p)
            routes.append(idx)
        else:
            x, k_after, v_after = attention_block(x, p, state[at], state[at + 1])
            state_out.extend([k_after, v_after])
            at += 2
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
