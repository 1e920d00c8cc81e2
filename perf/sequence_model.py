"""What the per-layer readers of a sequence-model cell share: the
bytes one decode step must move (from the configuration's shapes) and
device time by the model's own named scopes.

The program's scopes two levels deep (``learn/loss_grad``,
``rollout/act``) are what ``perf/program_trace.scope_of`` resolves;
the model opens finer ones under them (``learn/moe/experts``,
``rollout/act/linear_attn``: ``ray_tpu/models/sequence_lm.py``), which
are matched here on the operation's whole ``tf_op`` path. A trace with
none of them gives ``None``, never a number.
"""

from __future__ import annotations

from typing import Dict, Optional

from perf import program_trace

LINEAR, FULL = "linear_attention", "full_attention"


def _kinds(config: Dict):
    every = int(config["full_attention_interval"])
    return [
        FULL if (i + 1) % every == 0 else LINEAR
        for i in range(int(config["num_hidden_layers"]))
    ]


def layer_param_counts(config: Dict, num_actions: int) -> Dict[str, float]:
    """Parameters by part, as this chip holds them."""
    c = config
    d = int(c["hidden_size"])
    kd = int(c["linear_num_key_heads"]) * int(c["linear_key_head_dim"])
    vd = int(c["linear_num_value_heads"]) * int(c["linear_value_head_dim"])
    hv = int(c["linear_num_value_heads"])
    heads, kv, hd = (int(c[k]) for k in
                     ("num_attention_heads", "num_key_value_heads", "head_dim"))
    f, fs = int(c["moe_intermediate_size"]), int(c["shared_expert_intermediate_size"])
    held = int(c["experts_held"][1])
    return {
        "linear_mixer": d * (2 * kd + 2 * vd) + d * 2 * hv
        + (2 * kd + vd) * int(c["linear_conv_kernel_dim"]) + 2 * hv
        + int(c["linear_value_head_dim"]) + vd * d,
        "full_mixer": d * heads * hd * 2 + 2 * d * kv * hd + heads * hd * d + 2 * hd,
        "router_and_shared": d * int(c["router_outputs"]) + 3 * d * fs + d,
        "one_expert": 3 * d * f,
        "experts_held": held * 3 * d * f,
        "norms": 2 * d,
        "embedding": num_actions * d,
        "head": d * num_actions + d + d + 1,
    }


def param_count(config: Dict, num_actions: int) -> float:
    p = layer_param_counts(config, num_actions)
    per_block = p["router_and_shared"] + p["experts_held"] + p["norms"]
    total = p["embedding"] + p["head"]
    for kind in _kinds(config):
        total += per_block + (p["linear_mixer"] if kind == LINEAR else p["full_mixer"])
    return total


def product_weight_count(config: Dict, num_actions: int) -> float:
    """Parameters that enter a bfloat16 product (projections, expert
    and shared-expert matrices, the output head); the rest (router,
    b/a projection, convolution, norms, gates, value head) is used in
    float32."""
    c = config
    d = int(c["hidden_size"])
    kd = int(c["linear_num_key_heads"]) * int(c["linear_key_head_dim"])
    vd = int(c["linear_num_value_heads"]) * int(c["linear_value_head_dim"])
    heads, kv, hd = (int(c[k]) for k in
                     ("num_attention_heads", "num_key_value_heads", "head_dim"))
    fs = int(c["shared_expert_intermediate_size"])
    p = layer_param_counts(config, num_actions)
    total = d * num_actions
    for kind in _kinds(config):
        total += p["experts_held"] + 3 * d * fs
        if kind == LINEAR:
            total += d * (2 * kd + 2 * vd) + vd * d
        else:
            total += d * heads * hd * 2 + 2 * d * kv * hd + heads * hd * d
    return total


def decode_step_bytes(config: Dict, num_actions: int, envs: int) -> float:
    """Bytes one decode step of ``envs`` streams has to move through
    HBM, as the program holds its data while it generates: the weights
    of every bfloat16 product at 2 bytes (the parameters are float32,
    but they do not change inside a rollout and the compiler converts
    them ONCE, outside the step loop: a step of 4.1 ms could not read
    2.35 GB of float32 and 1.6 GB of state), the other weights at 4
    (the embedding only the rows looked up); each DeltaNet matrix and
    convolution tail read once and written once in float32 (the
    program reads a matrix three times: decay and write, the read
    ``S^T k``, the output ``S^T q``); the stored keys and values of
    half an episode (the mean depth) in bfloat16 plus the step's own
    written (the program reads all 2,048 slots under a mask)."""
    c = config
    p = layer_param_counts(config, num_actions)
    d = int(c["hidden_size"])
    in_products = product_weight_count(config, num_actions)
    others = param_count(config, num_actions) - p["embedding"] - in_products
    weights = 2.0 * in_products + 4.0 * (others + envs * d)
    hv, dk, dv = (int(c[k]) for k in ("linear_num_value_heads",
                                      "linear_key_head_dim", "linear_value_head_dim"))
    kd = int(c["linear_num_key_heads"]) * dk
    conv_dim = 2 * kd + hv * dv
    delta_state = 2 * 4.0 * envs * (
        hv * dk * dv + (int(c["linear_conv_kernel_dim"]) - 1) * conv_dim
    )
    depth = int(c["max_position_embeddings"]) / 2.0
    kv, hd = int(c["num_key_value_heads"]), int(c["head_dim"])
    cache = 2.0 * envs * 2 * kv * hd * (depth + 1)
    kinds = _kinds(config)
    return (
        weights
        + kinds.count(LINEAR) * delta_state
        + kinds.count(FULL) * cache
    )


def seconds_under(rep, *needles: str) -> Optional[float]:
    """Device seconds, inside the traced span, of the leaf operations
    whose ``tf_op`` path contains one of ``needles``."""
    if rep is None or not rep.op_scopes:
        return None
    total, seen = 0.0, False
    for op, d in program_trace._leaf_ops(rep.op_scopes, rep.trace.bounds):
        if any(n in op[0] for n in needles):
            total += d / 1e9
            seen = True
    return total if seen else None


def decode_seconds(rep) -> Optional[float]:
    """Device seconds of the rollout's steps: acting (the model's
    decode step), the env step and the state reset."""
    if rep is None or rep.scopes is None:
        return None
    parts = [rep.scopes.get(k) for k in
             ("rollout/act", "rollout/env_step", "rollout/state_reset")]
    if parts[0] is None:
        return None
    return sum(p for p in parts if p is not None)


def fragment_steps(ctx) -> int:
    return int(ctx.cell.traffic["algo_config"]["rollout_fragment_length"])


def envs(ctx) -> int:
    return int(ctx.cell.traffic["algo_config"]["num_envs_per_worker"])
