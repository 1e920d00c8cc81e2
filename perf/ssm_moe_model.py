"""What the readers and the FLOP rule of a Nemotron-H cell share: the
parameters this chip holds, by part, the bytes one decode step must
move, the bytes one call of the grouped state-space step kernel must
move and the state a stream carries, from the configuration's shapes
alone (a ``nemotron_h`` configuration: ``hybrid_override_pattern`` over
``M``, ``E`` and ``*``, ``mamba_num_heads`` / ``ssm_state_size`` /
``n_groups``, ``experts_held`` of ``router_outputs`` experts:
``perf/configs/nemotron3_nano_30b_a3b_ppo.json``).

How it differs from ``perf/ssm_model.py`` (Granite 4.0-H, which stays
as it is): a block is ONE sublayer under one norm, so there is NO
feed-forward beside a mixer; the table is UNTIED (an embedding, of which
a step reads the rows looked up, and a head, which is a product); the
expert blocks hold ``experts_held`` experts of TWO matrices each (no
gate matrix) and a shared expert of two; ``B`` and ``C`` are ``n_groups``
rows. Device time by the model's named scopes is
``perf/sequence_model.seconds_under``.
"""

from __future__ import annotations

from typing import Dict

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


def kinds(config: Dict):
    """The blocks run: the published pattern's first ``num_hidden_layers``."""
    return list(str(config["hybrid_override_pattern"])[: int(config["num_hidden_layers"])])


def _ssm_sizes(config: Dict):
    c = config
    hs, p, n, g = (int(c[k]) for k in (
        "mamba_num_heads", "mamba_head_dim", "ssm_state_size", "n_groups"))
    return hs, p, n, g, hs * p + 2 * g * n  # ..., the convolution's channels


def layer_param_counts(config: Dict, num_actions: int) -> Dict[str, float]:
    """Parameters by part, as this chip holds them."""
    c = config
    d = int(c["hidden_size"])
    heads, kv, dh = (int(c[k]) for k in (
        "num_attention_heads", "num_key_value_heads", "head_dim"))
    hs, p, _, _, channels = _ssm_sizes(c)
    inner = hs * p
    return {
        # W_in [z | x | B | C | dt] and W_out
        "ssm_products": d * (inner + channels + hs) + inner * d,
        # the convolution and its bias, dt_bias, A_log, D, the gated norm
        "ssm_others": channels * int(c["conv_kernel"])
        + (channels if c.get("use_conv_bias", True) else 0) + 3 * hs + inner,
        "attention_products": d * heads * dh + 2 * d * kv * dh + heads * dh * d,
        # the router and its selection bias, float32
        "router": d * int(c["router_outputs"]) + int(c["router_outputs"]),
        "one_expert": 2 * d * int(c["moe_intermediate_size"]),
        "shared": 2 * d * int(c["moe_shared_expert_intermediate_size"]),
        "held": int(c["experts_held"][1]),
        "norm": d,  # one a block
        "embedding": num_actions * d,
        "head": d * num_actions,
        "value_and_final_norm": d + 1 + d,
    }


def _block_counts(config: Dict, num_actions: int):
    """``[(in bfloat16 products, used in float32)]`` a block."""
    p = layer_param_counts(config, num_actions)
    out = []
    for kind in kinds(config):
        if kind == MAMBA:
            out.append((p["ssm_products"], p["ssm_others"] + p["norm"]))
        elif kind == EXPERTS:
            out.append((p["held"] * p["one_expert"] + p["shared"],
                        p["router"] + p["norm"]))
        else:
            out.append((p["attention_products"], p["norm"]))
    return out


def param_count(config: Dict, num_actions: int) -> float:
    p = layer_param_counts(config, num_actions)
    return p["embedding"] + p["head"] + p["value_and_final_norm"] + sum(
        a + b for a, b in _block_counts(config, num_actions))


def product_weight_count(config: Dict, num_actions: int) -> float:
    """Parameters that enter a bfloat16 product (``W_in``, ``W_out``,
    q/k/v/o, every HELD expert's two matrices, as the dense one-token
    form reads them, the shared expert's two, the head); the rest
    (router, convolutions, ``dt_bias``, ``A_log``, ``D``, norms, the
    value head) is used in float32, and of the embedding a step reads
    rows."""
    p = layer_param_counts(config, num_actions)
    return p["head"] + sum(a for a, _ in _block_counts(config, num_actions))


def state_bytes(config: Dict) -> Dict[str, float]:
    """Bytes of state ONE stream carries, by part: a state-space
    block's float32 matrix and convolution inputs, the attention
    block's bfloat16 keys and values of every position."""
    c = config
    hs, p, n, _, channels = _ssm_sizes(c)
    kv, dh = int(c["num_key_value_heads"]), int(c["head_dim"])
    return {
        "ssm_layer": 4.0 * (hs * p * n + (int(c["conv_kernel"]) - 1) * channels),
        "cache_position": 2.0 * 2 * kv * dh,
        "cache_layer": 2.0 * 2 * kv * dh * int(c["max_position_embeddings"]),
    }


def decode_step_bytes(config: Dict, num_actions: int, envs: int) -> float:
    """Bytes one decode step of ``envs`` streams MUST move through HBM:
    the weights of every bfloat16 product once at 2 bytes (the
    parameters are float32, but they do not change inside a rollout
    and are converted once, outside the step loop), every HELD expert's
    two matrices among them (the dense one-token form runs every held
    expert over the step's tokens), the other weights at 4 (of the
    float32 embedding only the rows looked up), every state-space
    matrix and convolution tail once in and once out in float32, and
    the attention block's keys and values of the MEAN depth (half an
    episode) once in bfloat16 plus the step's own row written. Not the
    cache slots above the position that a masked product also reads: a
    program that reads them moves more than this and reads a lower
    share."""
    c = config
    p = layer_param_counts(config, num_actions)
    in_products = product_weight_count(config, num_actions)
    others = param_count(config, num_actions) - in_products - p["embedding"]
    weights = 2.0 * in_products + 4.0 * (others + envs * int(c["hidden_size"]))
    s = state_bytes(config)
    blocks = kinds(config)
    depth = int(c["max_position_embeddings"]) / 2.0
    return (
        weights
        + blocks.count(MAMBA) * envs * 2 * s["ssm_layer"]
        + blocks.count(ATTENTION) * envs * s["cache_position"] * (depth + 1)
    )


def act_seconds_under(rep, needle: str):
    """Device seconds, inside the traced span, of the leaf operations
    under the lane's ``rollout/act`` whose ``tf_op`` path goes on through
    ``needle`` (a scope of the model's, with its slashes). A run is a
    scan, so the loop's own frames stand between the two scopes on a
    path: they are matched in order, not as one string. ``None`` for a
    trace without such an operation."""
    from perf import program_trace

    if rep is None or not rep.op_scopes:
        return None
    total, seen = 0.0, False
    for op, d in program_trace._leaf_ops(rep.op_scopes, rep.trace.bounds):
        at = op[0].find("rollout/act/")
        if at >= 0 and needle in op[0][at:]:
            total += d / 1e9
            seen = True
    return total if seen else None


def ssm_step_bytes(config: Dict, envs: int) -> float:
    """Bytes ONE call of the one-token state-space step (one block, one
    token of ``envs`` streams) must move: the block's float32 matrices
    once in and once out, 8 bytes an element, and its rows: ``x`` and
    ``y`` (``heads x head``), ``dt`` (a number a head), ``B`` and ``C``
    (``groups x state`` each), float32."""
    hs, p, n, g, _ = _ssm_sizes(config)
    return envs * (8.0 * hs * p * n + 4.0 * (2 * hs * p + hs + 2 * g * n))
