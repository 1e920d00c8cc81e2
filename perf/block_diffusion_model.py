"""What the readers and the FLOP rule of a block-diffusion cell share:
the parameters this chip holds by part, the cache rows a block's queries
see, and the bytes the forwards of one lane step must move, from the
configuration's shapes alone (``model_type: sdar_moe``: every layer
``qwen3_moe``'s full attention with q/k norms over ``num_experts`` HELD
experts of a ``router_outputs``-way router, no shared expert; generation
by blocks of ``block_length`` in ``denoising_steps`` passes:
``perf/configs/sdar_30b_a3b_ppo.json``). Device time by the model's
named scopes is ``perf/sequence_model.seconds_under``.
"""

from __future__ import annotations

from typing import Dict


def generation(config: Dict) -> Dict[str, int]:
    """``block_length`` and ``denoising_steps`` as the model is run."""
    lm = config["algo_config"]["model"]["sequence_lm"]
    return {"block": int(lm["block_length"]), "steps": int(lm["denoising_steps"])}


def layer_param_counts(config: Dict) -> Dict[str, float]:
    """One layer's parameters by part, as this chip holds them.
    ``in_products`` of them enter a bfloat16 product; the rest (router,
    norms) is used in float32."""
    c = config
    d, dh = int(c["hidden_size"]), int(c["head_dim"])
    kv, h = int(c["num_key_value_heads"]), int(c["num_attention_heads"])
    one = 3 * d * int(c["moe_intermediate_size"])
    out = {
        # W_q and W_o; W_k and W_v
        "attention": 2 * d * h * dh + 2 * d * kv * dh,
        # the two layer norms, the q and k norms
        "norms": 2 * d + 2 * dh,
        "router": d * int(c.get("router_outputs", c["num_experts"])),
        "one_expert": one, "experts_held": int(c["num_experts"]) * one,
    }
    out["in_products"] = out["attention"] + out["experts_held"]
    out["all"] = out["in_products"] + out["norms"] + out["router"]
    return out


def param_count(config: Dict, num_actions: int) -> float:
    d = int(config["hidden_size"])
    body = int(config["num_hidden_layers"]) * layer_param_counts(config)["all"]
    # embedding, untied head, final norm, value head and its bias
    return body + 2 * num_actions * d + d + d + 1


def product_weight_count(config: Dict, num_actions: int) -> float:
    """Parameters that enter a bfloat16 product (q/k/v/o, the held
    experts' matrices, the output head)."""
    return (int(config["num_hidden_layers"])
            * layer_param_counts(config)["in_products"]
            + int(config["hidden_size"]) * num_actions)


def mean_rows_seen(config: Dict) -> float:
    """Cache rows inside the mask of a query at a position drawn evenly
    from an episode of ``max_position_embeddings``: every row of its own
    block and of the blocks before it, ``(p // B + 1) B``."""
    s, b = int(config["max_position_embeddings"]), generation(config)["block"]
    return (s + b) / 2.0


def cache_row_bytes(config: Dict) -> float:
    """One position's bfloat16 key and value of every KV head."""
    return 2.0 * 2 * int(config["num_key_value_heads"]) * int(config["head_dim"])


def block_forward_bytes(config: Dict, num_actions: int, envs: int) -> float:
    """Bytes ONE block forward of ``envs`` streams must move through
    HBM: the weights of every bfloat16 product once at 2 bytes (the
    parameters are float32, but they do not change inside a rollout and
    are converted once, outside the step loop), the other weights at 4
    (of the embedding only the rows looked up), per layer and stream the
    cache rows below the block at the mean depth once and the block's
    own rows written and read, and the block's float32 logits written.
    Every HELD expert's weights count: with ``envs x block x top_k /
    router_outputs`` routes a held expert (4 in the cell) every one is
    chosen, and the dense product reads them all either way."""
    d, b = int(config["hidden_size"]), generation(config)["block"]
    layers = int(config["num_hidden_layers"])
    in_products = product_weight_count(config, num_actions)
    others = param_count(config, num_actions) - num_actions * d - in_products
    weights = 2.0 * in_products + 4.0 * (others + envs * b * d)
    below = (int(config["max_position_embeddings"]) - b) / 2.0
    rows = layers * (below + 2 * b)
    return (weights + envs * cache_row_bytes(config) * rows
            + 4.0 * envs * b * num_actions)


def commit_forward_bytes(config: Dict, num_actions: int, envs: int) -> float:
    """Bytes the COMMIT forward must move: it is run for the key and
    value rows it leaves in every layer's cache and nothing else, so of
    the LAST layer only the two norms' and ``W_k``, ``W_v`` are needed
    (not its queries, its cache's rows, ``W_o``, its router or its
    experts), and neither the final norm, the head nor the logits: what
    a program that computed no more than that would move."""
    c = config
    d, dh, b = int(c["hidden_size"]), int(c["head_dim"]), generation(c)["block"]
    p = layer_param_counts(c)
    last = (2.0 * (p["experts_held"] + 2 * d * int(c["num_attention_heads"]) * dh)
            + 4.0 * p["router"]
            + envs * cache_row_bytes(c) * (int(c["max_position_embeddings"]) - b) / 2.0)
    head = 2.0 * d * num_actions + 4.0 * (2 * d + 1) + 4.0 * envs * b * num_actions
    return block_forward_bytes(c, num_actions, envs) - last - head


def block_step_bytes(config: Dict, num_actions: int, envs: int) -> float:
    """Bytes the ``S + 1`` forwards of one lane step must move: ``S``
    denoise forwards, each :func:`block_forward_bytes` (the weights cross
    HBM once a forward: 0.75 times a generated token at a block of 4 in
    2 passes), and the commit forward's :func:`commit_forward_bytes`."""
    return (generation(config)["steps"] * block_forward_bytes(
        config, num_actions, envs) + commit_forward_bytes(config, num_actions, envs))
