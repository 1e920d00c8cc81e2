"""What the readers and the FLOP rule of an EVA-attention cell share: the
parameters this chip holds, by part, the rows of the two stores a query
sees, the bytes one decode step and one call of the one-token EVA
attention must move and the stores a stream carries, from the
configuration's shapes alone (a configuration with ``attention_class:
"eva"``, ``window_size``, ``chunk_size``, a share of heads and a dense
feed-forward in every layer: ``perf/configs/evabyte_6_5b_ppo.json``).
Device time by the model's named scopes is
``perf/sequence_model.seconds_under``.
"""

from __future__ import annotations

from typing import Dict

# bytes of the ``(streams, rows, row)`` leaves of a carry, a stream
from perf.window_model import cache_bytes_per_stream  # noqa: F401


def is_eva(config: Dict) -> bool:
    return config.get("attention_class") == "eva"


def head_dim(config: Dict) -> int:
    return int(config.get("head_dim")
               or int(config["hidden_size"]) // int(config["num_attention_heads"]))


def layer_param_counts(config: Dict, num_actions: int) -> Dict[str, float]:
    """Parameters by part, as this chip holds them."""
    c = config
    d, wide = int(c["hidden_size"]), int(c["num_attention_heads"]) * head_dim(c)
    return {
        "attention": 4 * d * wide,  # W_q, W_k, W_v, W_o of the heads held
        "eva_vectors": 2 * wide,  # mu and phi, a vector a head each
        "feed_forward": 3 * d * int(c["intermediate_size"]),
        "norms": 2 * d,
        "embedding": num_actions * d,
        "head": d * num_actions,
        "value_and_final_norm": d + 1 + d,
    }


def param_count(config: Dict, num_actions: int) -> float:
    p = layer_param_counts(config, num_actions)
    layer = p["attention"] + p["eva_vectors"] + p["feed_forward"] + p["norms"]
    return (int(config["num_hidden_layers"]) * layer + p["embedding"] + p["head"]
            + p["value_and_final_norm"])


def product_weight_count(config: Dict, num_actions: int) -> float:
    """Parameters that enter a bfloat16 product (q/k/v/o, the
    feed-forward's three matrices, the output head); the rest (norms,
    ``mu`` and ``phi``, the value head) is used in float32."""
    p = layer_param_counts(config, num_actions)
    return int(config["num_hidden_layers"]) * (
        p["attention"] + p["feed_forward"]) + p["head"]


def mean_rows_seen(config: Dict) -> Dict[str, float]:
    """Rows inside each mask of a query at a position drawn evenly from
    an episode of ``max_position_embeddings``: ``t mod W + 1`` of the
    window store (its own among them), ``(W / c) floor(t / W)`` of the
    summary store."""
    s, w, c = (int(config[k]) for k in
               ("max_position_embeddings", "window_size", "chunk_size"))
    exact = sum(t % w + 1 for t in range(s)) / float(s)
    pooled = sum((w // c) * (t // w) for t in range(s)) / float(s)
    return {"window": exact, "summary": pooled}


def store_row_bytes(config: Dict) -> float:
    """One row's bfloat16 key and value of every head held."""
    return 2.0 * 2 * int(config["num_attention_heads"]) * head_dim(config)


def store_rows(config: Dict) -> Dict[str, int]:
    s, w, c = (int(config[k]) for k in
               ("max_position_embeddings", "window_size", "chunk_size"))
    return {"window": min(w, s), "summary": -(-s // c)}


def cache_bytes(config: Dict) -> Dict[str, float]:
    """Bytes ONE stream carries in a layer: a window's rows, and a row
    a chunk of an episode."""
    return {k: store_row_bytes(config) * n for k, n in store_rows(config).items()}


def eva_step_bytes(config: Dict, envs: int) -> float:
    """Bytes ONE call of the one-token EVA attention (one layer, one
    token of ``envs`` streams) must move: the rows INSIDE both masks at
    the mean depth once (``mean_rows_seen``), and the query and the
    output, a float32 row of the heads held each. Not the slots outside
    a mask, and not the blocks' rows past a mask's edge."""
    seen = mean_rows_seen(config)
    wide = int(config["num_attention_heads"]) * head_dim(config)
    return envs * (store_row_bytes(config) * (seen["window"] + seen["summary"])
                   + 2 * 4.0 * wide)


def decode_step_bytes(config: Dict, num_actions: int, envs: int) -> float:
    """Bytes one decode step of ``envs`` streams MUST move through HBM:
    the weights of every bfloat16 product once at 2 bytes (the
    parameters are float32, but they do not change inside a rollout and
    are converted once, outside the step loop), the other weights at 4
    (of the embedding only the rows looked up), and per layer and stream
    the rows INSIDE both masks at the mean depth once plus one window
    row and 1/c summary row written."""
    p = layer_param_counts(config, num_actions)
    in_products = product_weight_count(config, num_actions)
    others = param_count(config, num_actions) - p["embedding"] - in_products
    weights = 2.0 * in_products + 4.0 * (others + envs * int(config["hidden_size"]))
    seen = mean_rows_seen(config)
    rows = seen["window"] + seen["summary"] + 1 + 1.0 / int(config["chunk_size"])
    return weights + int(config["num_hidden_layers"]) * envs * store_row_bytes(
        config) * rows
