"""What the readers and the FLOP rule of a state-space cell share: the
parameters this chip holds, by part, the bytes one decode step must
move and the state a stream carries, from the configuration's shapes
alone (a configuration with ``mamba_*`` keys, a ``layer_types`` pattern
of ``mamba`` and ``attention``, no expert layer and a tied table:
``perf/configs/granite_4_0_h_micro_ppo.json``). Device time by the
model's named scopes is ``perf/sequence_model.seconds_under``.
"""

from __future__ import annotations

from typing import Dict

MAMBA = "mamba"


def kinds(config: Dict):
    """The layers run: the published pattern's first ``num_hidden_layers``."""
    return list(config["layer_types"][: int(config["num_hidden_layers"])])


def layer_param_counts(config: Dict, num_actions: int) -> Dict[str, float]:
    """Parameters by part, as this chip holds them."""
    c = config
    d = int(c["hidden_size"])
    heads, kv = int(c["num_attention_heads"]), int(c["num_key_value_heads"])
    dh = int(c.get("head_dim") or d // heads)
    hs, p, n = (int(c[k]) for k in ("mamba_n_heads", "mamba_d_head", "mamba_d_state"))
    inner = hs * p
    channels = inner + 2 * int(c.get("mamba_n_groups", 1)) * n
    return {
        # W_in [z | x | B | C | dt] and W_out
        "ssm_products": d * (inner + channels + hs) + inner * d,
        # the convolution and its bias, dt_bias, A_log, D, the gated norm
        "ssm_others": channels * int(c["mamba_d_conv"])
        + (channels if c.get("mamba_conv_bias", True) else 0) + 3 * hs + inner,
        "attention_products": d * heads * dh + 2 * d * kv * dh + heads * dh * d,
        "mlp": 3 * d * int(c["shared_intermediate_size"]),
        "norms": 2 * d,
        "table": num_actions * d,  # embedding and output head, once
        "value_and_final_norm": d + 1 + d,
    }


def param_count(config: Dict, num_actions: int) -> float:
    p = layer_param_counts(config, num_actions)
    total = p["table"] + p["value_and_final_norm"]
    for kind in kinds(config):
        total += p["mlp"] + p["norms"] + (
            p["ssm_products"] + p["ssm_others"] if kind == MAMBA
            else p["attention_products"])
    return total


def product_weight_count(config: Dict, num_actions: int) -> float:
    """Parameters that enter a bfloat16 product (``W_in``, ``W_out``,
    q/k/v/o, the feed-forwards and the tied table as the output head);
    the rest (convolutions, ``dt_bias``, ``A_log``, ``D``, norms, the
    value head) is used in float32."""
    p = layer_param_counts(config, num_actions)
    total = p["table"]
    for kind in kinds(config):
        total += p["mlp"] + (
            p["ssm_products"] if kind == MAMBA else p["attention_products"])
    return total


def state_bytes(config: Dict) -> Dict[str, float]:
    """Bytes of state ONE stream carries, by part: a state-space
    layer's float32 matrix and convolution inputs, the attention
    layer's bfloat16 keys and values of every position."""
    c = config
    hs, p, n = (int(c[k]) for k in ("mamba_n_heads", "mamba_d_head", "mamba_d_state"))
    channels = hs * p + 2 * int(c.get("mamba_n_groups", 1)) * n
    heads, kv = int(c["num_attention_heads"]), int(c["num_key_value_heads"])
    dh = int(c.get("head_dim") or int(c["hidden_size"]) // heads)
    return {
        "ssm_layer": 4.0 * (hs * p * n + (int(c["mamba_d_conv"]) - 1) * channels),
        "cache_position": 2.0 * 2 * kv * dh,
        "cache_layer": 2.0 * 2 * kv * dh * int(c["max_position_embeddings"]),
    }


def decode_step_bytes(config: Dict, num_actions: int, envs: int) -> float:
    """Bytes one decode step of ``envs`` streams MUST move through HBM:
    the weights of every bfloat16 product once at 2 bytes (the
    parameters are float32, but they do not change inside a rollout
    and are converted once, outside the step loop), the other weights
    at 4 (of the float32 table only the rows looked up), every
    state-space matrix and convolution tail once in and once out in
    float32, and the attention layer's keys and values of the MEAN
    depth (half an episode) once in bfloat16 plus the step's own row
    written. Not the cache slots above the position that a masked
    product also reads: a program that reads them moves more than this
    and reads a lower share."""
    c = config
    in_products = product_weight_count(config, num_actions)
    others = param_count(config, num_actions) - in_products
    weights = 2.0 * in_products + 4.0 * (others + envs * int(c["hidden_size"]))
    s = state_bytes(config)
    layers = kinds(config)
    depth = int(c["max_position_embeddings"]) / 2.0
    return (
        weights
        + layers.count(MAMBA) * envs * 2 * s["ssm_layer"]
        + (len(layers) - layers.count(MAMBA)) * envs * s["cache_position"] * (depth + 1)
    )


def state_bytes_per_stream(state_leaves):
    """Bytes of model state one stream carries, from a carry's state
    leaves (every leaf's first axis is the stream's). ``None`` where
    there is none."""
    leaves = [leaf for leaf in state_leaves if getattr(leaf, "ndim", 0) >= 1]
    if not leaves:
        return None
    return sum(leaf.dtype.itemsize * leaf.size for leaf in leaves) / float(
        leaves[0].shape[0])
