"""What the readers, the FLOP rule and the tests of a cell of Kimi Delta
Attention beside latent attention share: the kinds of its layers, the
parameters by part, the bytes a stream carries, and the bytes one decode
step and one call of the one-token delta-rule kernel must move, from the
configuration's shapes alone (``model_type: bailing_hybrid``:
``perf/configs/ling_3_0_flash_125b_a5b_ppo.json``). Device time by the
model's named scopes is ``perf/sequence_model.seconds_under`` and
``perf/ssm_moe_model.act_seconds_under``.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional

KDA, LATENT = "kimi_delta_attention", "latent_attention"


def is_kda_latent(config: Dict) -> bool:
    return config.get("model_type") == "bailing_hybrid"


def kinds(config: Dict, indices=None) -> List[str]:
    """The mixers of the published layers ``indices`` (default: those
    held, ``layer_indices``): the LAST of every ``layer_group_size`` is
    latent attention, the others Kimi Delta Attention."""
    c = config
    every = int(c["layer_group_size"])
    if indices is None:
        indices = c.get("layer_indices") or range(int(c["num_hidden_layers"]))
    return [LATENT if (int(i) + 1) % every == 0 else KDA for i in indices]


def sizes(config: Dict) -> Dict[str, int]:
    c = config
    return {
        "d": int(c["hidden_size"]), "heads": int(c["num_attention_heads"]),
        "head": int(c["head_dim"]), "conv": int(c["short_conv_kernel_size"]),
        "latent": int(c["kv_lora_rank"]), "nope": int(c["qk_nope_head_dim"]),
        "rope": int(c["qk_rope_head_dim"]), "v_head": int(c["v_head_dim"]),
        "ffn": int(c["intermediate_size"]), "expert": int(c["moe_intermediate_size"]),
        "shared": int(c["num_shared_experts"])
        * int(c["moe_shared_expert_intermediate_size"]),
        "outputs": int(c.get("router_outputs", c["num_experts"])),
        "held": int((c.get("experts_held") or (0, c["num_experts"]))[1]),
        "top_k": int(c["num_experts_per_tok"]),
        "positions": int(c["max_position_embeddings"]),
    }


def layer_param_counts(config: Dict, num_actions: int) -> Dict[str, Dict[str, int]]:
    """``{part: {"products": n, "others": n}}``: the parameters of one
    layer's mixer of each kind, of each kind of feed-forward, of a
    block's two norms and of the model's ends, split into those that
    enter a bfloat16 product and the rest (``W_b``, the convolutions,
    ``A_log``, ``dt_bias``, norms, the router with its selection bias,
    the value head), which are used in float32."""
    z = sizes(config)
    d, h, hd = z["d"], z["heads"], z["head"]
    wide = h * hd
    return {
        # W_q, W_k, W_v, W_f, W_g, W_o | three convolutions, A_log,
        # dt_bias, W_b, the head norm
        KDA: {"products": 4 * d * wide + d * h + wide * d,
              "others": 3 * wide * z["conv"] + h + wide + d * h + hd},
        # W_q, W_kva, W_kvb, W_g, W_o | the latent norm
        LATENT: {"products": d * h * (z["nope"] + z["rope"])
                 + d * (z["latent"] + z["rope"])
                 + z["latent"] * h * (z["nope"] + z["v_head"]) + d * h
                 + h * z["v_head"] * d,
                 "others": z["latent"]},
        "dense": {"products": 3 * d * z["ffn"], "others": 0},
        "experts": {"products": 3 * d * (z["held"] * z["expert"] + z["shared"]),
                    "others": d * z["outputs"] + z["outputs"]},
        "one_expert": {"products": 3 * d * z["expert"], "others": 0},
        "norms": {"products": 0, "others": 2 * d},
        # the untied head | the final norm and the value head (the
        # embedding is counted apart: a lookup multiplies nothing)
        "ends": {"products": d * num_actions, "others": d + d + 1},
    }


def _layers(config: Dict, indices=None, dense: Optional[int] = None):
    """``[(mixer kind, "dense" | "experts")]`` of the layers held (or of
    the published layers ``indices`` with ``dense`` leading dense ones)."""
    ks = kinds(config, indices)
    if dense is None:
        dense = int(config["first_k_dense_replace"])
    return [(k, "dense" if n < dense else "experts") for n, k in enumerate(ks)]


def _sum(config: Dict, num_actions: int, which: str, layers=None) -> int:
    p = layer_param_counts(config, num_actions)
    total = p["ends"][which]
    for mixer, ffn in layers or _layers(config):
        total += p[mixer][which] + p[ffn][which] + p["norms"][which]
    return total


def param_count(config: Dict, num_actions: int, layers=None) -> int:
    """Every parameter, the embedding among them."""
    return num_actions * int(config["hidden_size"]) + sum(
        _sum(config, num_actions, which, layers) for which in ("products", "others"))


def product_weight_count(config: Dict, num_actions: int) -> int:
    return _sum(config, num_actions, "products")


def published_param_count(config: Dict) -> int:
    """The uncut model's, by the same parts: every layer of the published
    depth, every expert and the whole vocabulary; no value head (the
    published model has none) and no multi-token-prediction layer."""
    pub = config["published"]
    whole = dict(config, experts_held=[0, int(pub["num_experts"])],
                 router_outputs=int(pub["num_experts"]))
    layers = _layers(whole, range(int(pub["num_hidden_layers"])),
                     int(pub["first_k_dense_replace"]))
    return param_count(whole, int(pub["vocab_size"]), layers) - (
        int(config["hidden_size"]) + 1)


def kda_state_bytes(config: Dict) -> float:
    """A KDA layer's float32 matrix and its three convolutions' inputs, a
    stream."""
    z = sizes(config)
    wide = z["heads"] * z["head"]
    return 4.0 * (z["heads"] * z["head"] * z["head"] + 3 * (z["conv"] - 1) * wide)


def latent_row_bytes(config: Dict) -> float:
    z = sizes(config)
    return 2.0 * (z["latent"] + z["rope"])


def state_bytes(config: Dict) -> Dict[str, float]:
    """Bytes ONE stream carries, by what holds them."""
    ks = kinds(config)
    return {
        "kda": ks.count(KDA) * kda_state_bytes(config),
        "latent": ks.count(LATENT) * latent_row_bytes(config)
        * sizes(config)["positions"],
    }


def carried_kda_bytes_per_stream(state_leaves) -> Optional[float]:
    """Bytes of delta-rule state a stream carries, from a live carry's
    own leaves: the float32 matrices ``(streams, heads, dk, dv)`` and the
    convolutions' inputs ``(streams, conv - 1, channels)`` (a leaf of
    fewer than 8 rows: a cache has an episode's). ``None`` where there is
    no matrix."""
    total, seen = 0.0, False
    for leaf in state_leaves:
        ndim = getattr(leaf, "ndim", 0)
        if ndim == 4 or (ndim == 3 and leaf.shape[1] < 8):
            total += leaf.dtype.itemsize * float(math.prod(leaf.shape[1:]))
            seen = seen or ndim == 4
    return total if seen else None


def kda_step_bytes(config: Dict, envs: int) -> float:
    """Bytes ONE call of the one-token delta rule (one layer, one token
    of ``envs`` streams) must move: the layer's float32 matrices once in
    and once out, 8 bytes an element, and its rows in float32: ``q``,
    ``k`` and the decay (a number a head and key channel each), ``v`` and
    ``o`` (a number a head and value channel), ``beta`` (a number a
    head). The same for whatever computes the step: a body that reads a
    matrix three times moves more than this and reads a lower share."""
    z = sizes(config)
    h, hd = z["heads"], z["head"]
    return envs * (8.0 * h * hd * hd + 4.0 * (5 * h * hd + h))


def decode_step_bytes(config: Dict, num_actions: int, envs: int) -> float:
    """Bytes one decode step of ``envs`` streams MUST move through HBM:
    the weights of every bfloat16 product once at 2 bytes (the
    parameters are float32, but they do not change inside a rollout and
    are converted once, outside the step loop; every HELD expert among
    them: the one-token form multiplies all of them), the other weights
    at 4 (of the embedding only the rows looked up); each KDA layer's
    matrix and convolution inputs read once and written once; the latent
    layer's rows below the position at the MEAN depth (half an episode)
    read once and the step's own row written and read. Not the rows
    above the position that a masked product also reads, no expanded key
    or value, no matrix read a second time."""
    z, ks = sizes(config), kinds(config)
    in_products = product_weight_count(config, num_actions)
    others = _sum(config, num_actions, "others")
    weights = 2.0 * in_products + 4.0 * (others + envs * z["d"])
    rows = ks.count(LATENT) * latent_row_bytes(config) * (z["positions"] / 2.0 + 2)
    return weights + envs * (ks.count(KDA) * 2 * kda_state_bytes(config) + rows)
