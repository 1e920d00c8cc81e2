"""TEST DATA. Plain reference of a small pre-LN causal decoder policy
(the shapes of ray_tpu's ``use_transformer`` torso, written out with a
materialized softmax, no kernel and nothing from ``ray_tpu``) under
the clipped PPO objective (Schulman et al. 2017): float32, matmuls at
precision "highest". ``precision`` "bf16" and "int8" are the controls:
every matmul operand rounded one and two steps below the float32 the
configuration states.

Parameters in the reference's names are two levels deep,
``{"layer_0.attn": {"wq": ...}, ...}``, which is what
``perf.correct.compare_grads`` walks."""

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def _round_int8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.clip(jnp.round(x / scale), -127.0, 127.0) * scale


@jax.custom_vjp
def _fake_int8(x):
    return _round_int8(x)


_fake_int8.defvjp(lambda x: (_round_int8(x), None), lambda _, g: (_round_int8(g),))

_QUANT = {
    "float32": lambda v: v,
    "bf16": lambda v: v.astype(jnp.bfloat16).astype(jnp.float32),
    "int8": _fake_int8,
}


def _sizes(config: Dict):
    m = config["model"]
    d, h = int(m["transformer_dim"]), int(m["transformer_num_heads"])
    return {
        "obs": int(config["obs_dim"]), "S": int(m["transformer_seq_len"]),
        "D": d, "H": h, "Dh": d // h, "FF": int(m["transformer_ff_dim"]),
        "L": int(m["transformer_num_layers"]),
    }


def _shapes(config: Dict, num_actions: int) -> Dict[str, Dict[str, tuple]]:
    z = _sizes(config)
    tok = -(-z["obs"] // z["S"])
    d, h, dh, ff = z["D"], z["H"], z["Dh"], z["FF"]
    ln = {"scale": (d,), "bias": (d,)}
    out = {
        "in_proj": {"kernel": (tok, d), "bias": (d,)},
        "pos": {"table": (z["S"], d)},
        "ln_f": ln,
        "logits": {"kernel": (d, num_actions), "bias": (num_actions,)},
        "value": {"kernel": (d, 1), "bias": (1,)},
    }
    for i in range(z["L"]):
        out[f"layer_{i}.ln1"] = ln
        out[f"layer_{i}.ln2"] = ln
        out[f"layer_{i}.attn"] = {
            "wq": (d, h, dh), "wk": (d, h, dh), "wv": (d, h, dh),
            "bq": (h, dh), "bk": (h, dh), "bv": (h, dh),
            "wo": (h, dh, d), "bo": (d,),
        }
        out[f"layer_{i}.mlp"] = {
            "w_up": (d, ff), "b_up": (ff,), "w_down": (ff, d), "b_down": (d,),
        }
    return out


def init_params(key, config: Dict, num_actions: int):
    """Seeded weights in one jitted call: matrices normal with
    variance 1 / rows, biases small and not zero (a bias the system
    dropped would otherwise go unseen), layer-norm scales near 1."""
    shapes = _shapes(config, num_actions)

    @jax.jit
    def make(key):
        out, n = {}, 0
        for group in sorted(shapes):
            out[group] = {}
            for leaf, shape in sorted(shapes[group].items()):
                k = jax.random.fold_in(key, n)
                n += 1
                x = jax.random.normal(k, shape, jnp.float32)
                if leaf == "scale":
                    x = 1.0 + 0.1 * x
                elif len(shape) == 1 or leaf in ("bq", "bk", "bv"):
                    x = 0.1 * x
                else:
                    x = x / np.sqrt(shape[0] if leaf != "wo" else shape[0] * shape[1])
                out[group][leaf] = x
        return out

    return make(key)


def to_policy_tree(params, config: Dict):
    """Reference names -> the plain nested dict of ``TransformerPolicyNet``."""
    out = {}
    for group, leaves in params.items():
        if group == "pos":
            out["pos"] = leaves["table"]
        elif "." in group:
            layer, part = group.split(".")
            out.setdefault(layer, {})[part] = dict(leaves)
        else:
            out[group] = dict(leaves)
    return out


def from_policy_tree(tree, config: Dict):
    out = {}
    for name, sub in tree.items():
        if name == "pos":
            out["pos"] = {"table": sub}
        elif name.startswith("layer_"):
            for part, leaves in sub.items():
                out[f"{name}.{part}"] = dict(leaves)
        else:
            out[name] = dict(sub)
    return out


def make_batch(rng: np.random.Generator, config: Dict, rows: int, num_actions: int):
    """A row is one timestep of a rollout: the observation, the action
    taken, what the behaviour policy thought of it, its advantage and
    its value target."""
    z = _sizes(config)
    prev = rng.normal(0.0, 1.0, (rows, num_actions)).astype(np.float32)
    actions = rng.integers(0, num_actions, rows).astype(np.int64)
    logp = prev - np.log(np.sum(np.exp(prev), axis=1, keepdims=True))
    return {
        "obs": rng.normal(0.0, 1.0, (rows, z["obs"])).astype(np.float32),
        "actions": actions,
        "action_logp": logp[np.arange(rows), actions].astype(np.float32),
        "action_dist_inputs": prev,
        "advantages": rng.normal(0.0, 1.0, rows).astype(np.float32),
        "value_targets": rng.normal(0.0, 1.0, rows).astype(np.float32),
    }


def _layer_norm(x, p, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def attention_probs(q, k):
    """Causal softmax over keys, ``(B, H, S, S)``."""
    s = q.shape[2]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=HI) / jnp.sqrt(
        jnp.float32(q.shape[-1])
    )
    mask = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    return jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)


def forward(params, obs, config: Dict, precision: str = "float32"):
    """``(logits, value, attention probabilities of every layer)``."""
    z, q_ = _sizes(config), _QUANT[precision]
    b = obs.shape[0]
    tok = -(-z["obs"] // z["S"])
    x = jnp.pad(obs.astype(jnp.float32), ((0, 0), (0, z["S"] * tok - z["obs"])))
    t = x.reshape(b, z["S"], tok)
    h = (
        jnp.einsum("bst,td->bsd", q_(t), q_(params["in_proj"]["kernel"]), precision=HI)
        + params["in_proj"]["bias"] + params["pos"]["table"]
    )
    probs = []
    for i in range(z["L"]):
        ap, mp = params[f"layer_{i}.attn"], params[f"layer_{i}.mlp"]
        a = q_(_layer_norm(h, params[f"layer_{i}.ln1"]))
        q, k, v = (
            jnp.einsum("bsd,dhk->bhsk", a, q_(ap["w" + n]), precision=HI)
            + ap["b" + n][None, :, None, :]
            for n in "qkv"
        )
        p = attention_probs(q_(q), q_(k))
        probs.append(p)
        o = jnp.einsum("bhqk,bhkd->bhqd", q_(p), q_(v), precision=HI)
        h = h + jnp.einsum("bhsk,hkd->bsd", q_(o), q_(ap["wo"]), precision=HI) + ap["bo"]
        m = q_(_layer_norm(h, params[f"layer_{i}.ln2"]))
        up = jax.nn.gelu(
            jnp.einsum("bsd,df->bsf", m, q_(mp["w_up"]), precision=HI) + mp["b_up"]
        )
        h = h + jnp.einsum("bsf,fd->bsd", q_(up), q_(mp["w_down"]), precision=HI) + mp["b_down"]
    feat = _layer_norm(h, params["ln_f"])[:, -1]
    logits = jnp.dot(feat, params["logits"]["kernel"], precision=HI) + params["logits"]["bias"]
    value = (jnp.dot(feat, params["value"]["kernel"], precision=HI) + params["value"]["bias"])[:, 0]
    return logits, value, jnp.stack(probs)


def loss(params, batch, config: Dict, precision: str = "float32"):
    """Clipped surrogate + clipped value loss - entropy bonus + KL
    penalty, mean over the rows, with the configuration's coefficients."""
    algo = config["algo_config"]
    clip, vf_clip = float(algo["clip_param"]), float(algo["vf_clip_param"])
    logits, value, _ = forward(params, batch["obs"], config, precision)
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
        -surrogate + float(algo["kl_coeff"]) * kl
        + float(algo.get("vf_loss_coeff", 1.0)) * vf
        - float(algo["entropy_coeff"]) * entropy
    )
