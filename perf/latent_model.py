"""What the readers and the FLOP rule of a latent-attention cell share:
the parameters this chip holds, by part, and the bytes one decode step
must move, from the configuration's shapes alone (a configuration with
``kv_lora_rank``, ``hc_mult`` lanes, a leading dense layer and held
experts: ``perf/configs/xing4_0_29b_a4b_ppo.json``). Device time by the
model's named scopes is ``perf/sequence_model.seconds_under``.
"""

from __future__ import annotations

from typing import Dict


def layer_param_counts(config: Dict, num_actions: int) -> Dict[str, float]:
    """Parameters by part, as this chip holds them."""
    c = config
    d, h = int(c["hidden_size"]), int(c["num_attention_heads"])
    cq, ckv = int(c["q_lora_rank"]), int(c["kv_lora_rank"])
    dn, r, dv = (int(c[k]) for k in
                 ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    f = int(c["moe_intermediate_size"])
    n = int(c["hc_mult"])
    outputs = int(c["router_outputs"])
    return {
        # W_qa, W_qb, W_kva, W_kvb, W_o
        "mixer_products": d * cq + cq * h * (dn + r) + d * (ckv + r)
        + ckv * h * (dn + dv) + h * dv * d,
        "mixer_norms": cq + ckv,
        # the norm over the lanes, phi, a, b; two a layer
        "hyper_connection": n * d + n * d * (2 * n + n * n) + 3 + 2 * n + n * n,
        "dense_mlp": 3 * d * int(c["intermediate_size"]),
        "router": d * outputs + outputs,  # with the selection bias
        "shared": 3 * d * f * int(c["n_shared_experts"]),
        "one_expert": 3 * d * f,
        "experts_held": int(c["experts_held"][1]) * 3 * d * f,
        "norms": 2 * d,
        "embedding": num_actions * d,
        "head": d * num_actions + d + d + 1,  # head, final norm, value head
    }


def layers(config: Dict):
    """``(dense layers, expert layers)``."""
    dense = int(config["first_k_dense_replace"])
    return dense, int(config["num_hidden_layers"]) - dense


def param_count(config: Dict, num_actions: int) -> float:
    p = layer_param_counts(config, num_actions)
    dense, experts = layers(config)
    every = p["mixer_products"] + p["mixer_norms"] + 2 * p["hyper_connection"] + p["norms"]
    return (
        p["embedding"] + p["head"]
        + dense * (every + p["dense_mlp"])
        + experts * (every + p["router"] + p["shared"] + p["experts_held"])
    )


def product_weight_count(config: Dict, num_actions: int) -> float:
    """Parameters that enter a bfloat16 product (the attention
    projections, the dense layer, expert and shared-expert matrices,
    the output head); the rest (routers, hyper-connection maps, norms,
    the value head) is used in float32."""
    p = layer_param_counts(config, num_actions)
    dense, experts = layers(config)
    return (
        int(config["hidden_size"]) * num_actions
        + (dense + experts) * p["mixer_products"]
        + dense * p["dense_mlp"]
        + experts * (p["shared"] + p["experts_held"])
    )


def decode_step_bytes(config: Dict, num_actions: int, envs: int) -> float:
    """Bytes one decode step of ``envs`` streams MUST move through HBM:
    the weights of every bfloat16 product once at 2 bytes (``W_kvb``
    among them: the absorbed form reads both its halves once), the
    other weights at 4 (of the embedding only the rows looked up), and
    per layer and stream the latent rows of the MEAN depth (half an
    episode) read once in bfloat16 plus the step's own row written and
    read. Not the rows above the position that a masked product also
    reads, and no expanded key or value: a program that does either
    moves more than this and reads a lower share."""
    c = config
    p = layer_param_counts(config, num_actions)
    in_products = product_weight_count(config, num_actions)
    others = param_count(config, num_actions) - p["embedding"] - in_products
    weights = 2.0 * in_products + 4.0 * (others + envs * int(c["hidden_size"]))
    row = 2.0 * (int(c["kv_lora_rank"]) + int(c["qk_rope_head_dim"]))
    depth = int(c["max_position_embeddings"]) / 2.0
    cache = int(c["num_hidden_layers"]) * envs * row * (depth + 2)
    return weights + cache


def cache_bytes_per_position(state_leaves, config: Dict):
    """Bytes of attention state a stream holds per position and layer,
    from a carry's state leaves: those shaped ``(streams, positions,
    row)``. ``None`` where there is none."""
    positions = int(config["max_position_embeddings"])
    rows = [
        leaf.dtype.itemsize * leaf.shape[2] for leaf in state_leaves
        if getattr(leaf, "ndim", 0) == 3 and leaf.shape[1] == positions
    ]
    if not rows:
        return None
    return sum(rows) / float(config["num_hidden_layers"])
