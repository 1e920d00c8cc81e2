"""What the readers and the FLOP rule of a cell whose attention chooses
its rows by a learned index share (a configuration with ``sa_config``
on a ``qwen3_moe`` stack: ``perf/configs/keye_vl_2_0_30b_a3b_ppo.json``):
the parameters this chip holds, by part, the rows a query scores and
the rows it attends to, the bytes one decode step must move and the
caches a stream carries, from the configuration's shapes alone. Device
time by the model's named scopes is ``perf/sequence_model.seconds_under``.
"""

from __future__ import annotations

from typing import Dict, Optional


def index_of(config: Dict) -> Optional[Dict[str, int]]:
    """``{heads, dim, topk}`` of the configuration's index; ``None``
    for one without ``sa_config``."""
    sa = config.get("sa_config")
    if not sa:
        return None
    return {"heads": int(sa["indexer_num_heads"]), "dim": int(sa["indexer_head_dim"]),
            "topk": int(sa["topk"])}


def layer_param_counts(config: Dict, num_actions: int) -> Dict[str, float]:
    """Parameters by part, as this chip holds them."""
    c = config
    d = int(c["hidden_size"])
    heads, kv, dh = (int(c[k]) for k in
                     ("num_attention_heads", "num_key_value_heads", "head_dim"))
    ix = index_of(config)
    one_expert = 3 * d * int(c["moe_intermediate_size"])
    return {
        "attention": 2 * d * heads * dh + 2 * d * kv * dh,  # W_q, W_o; W_k, W_v
        "qk_norms": 2 * dh,
        # W_qI, W_kI (bfloat16 products); W_w and LN's weight and bias (float32)
        "index_products": d * ix["heads"] * ix["dim"] + d * ix["dim"],
        "index_others": d * ix["heads"] + 2 * ix["dim"],
        "router": d * int(c.get("router_outputs", c["num_experts"])),
        "one_expert": one_expert,
        "experts_held": int(c["num_experts"]) * one_expert,
        "norms": 2 * d,
        "embedding": num_actions * d,
        "head": d * num_actions,
        "value_and_final_norm": d + 1 + d,
    }


def param_count(config: Dict, num_actions: int) -> float:
    p = layer_param_counts(config, num_actions)
    layer = (p["attention"] + p["qk_norms"] + p["index_products"] + p["index_others"]
             + p["router"] + p["experts_held"] + p["norms"])
    return (int(config["num_hidden_layers"]) * layer + p["embedding"] + p["head"]
            + p["value_and_final_norm"])


def product_weight_count(config: Dict, num_actions: int) -> float:
    """Parameters that enter a bfloat16 product (q/k/v/o, the index's
    two projections, the held experts' matrices, the output head); the
    rest (routers, ``W_w``, norms, the value head) is used in float32."""
    p = layer_param_counts(config, num_actions)
    return int(config["num_hidden_layers"]) * (
        p["attention"] + p["index_products"] + p["experts_held"]) + p["head"]


def mean_rows(config: Dict) -> Dict[str, float]:
    """Of a query at a position drawn evenly from an episode of
    ``max_position_embeddings``: the rows its index scores (``position +
    1``, its own among them), the rows it attends to (``min(position +
    1, topk)``), the mean of the second over the first a query, and the
    share of queries that see no more than ``topk``."""
    s, k = int(config["max_position_embeddings"]), index_of(config)["topk"]
    k = min(k, s)
    tail = sum(1.0 / rows for rows in range(k + 1, s + 1))
    return {
        "scored": (s + 1) / 2.0,
        "selected": (k * (k + 1) / 2.0 + (s - k) * k) / s,
        "selected_share": (k + k * tail) / s,
        "dense_query_share": k / float(s),
    }


def cache_row_bytes(config: Dict) -> Dict[str, float]:
    """One position's bfloat16 rows: key and value of every KV head, and
    the ONE index key."""
    return {
        "kv": 2.0 * 2 * int(config["num_key_value_heads"]) * int(config["head_dim"]),
        "index": 2.0 * index_of(config)["dim"],
    }


def cache_bytes(config: Dict) -> float:
    """Bytes of the three caches ONE stream carries in a layer."""
    row = cache_row_bytes(config)
    return (row["kv"] + row["index"]) * int(config["max_position_embeddings"])


def decode_step_bytes(config: Dict, num_actions: int, envs: int) -> float:
    """Bytes one decode step of ``envs`` streams MUST move through HBM,
    by what the equations need and whatever implements them: the weights
    of every bfloat16 product once at 2 bytes (converted once, outside
    the step loop), the other weights at 4 (of the embedding only the
    rows looked up), and per layer and stream, at a depth drawn evenly
    from the episode (``mean_rows``: the traffic's 16 streams, evenly
    apart, cover it),
    every index row it scores once, the ``min(depth + 1, topk)`` key and
    value rows it attends to once, and one row a leaf written. Not the
    key and value rows the index did not choose: a program that reads
    every row moves more than this and reads a lower share."""
    p = layer_param_counts(config, num_actions)
    in_products = product_weight_count(config, num_actions)
    others = param_count(config, num_actions) - p["embedding"] - in_products
    weights = 2.0 * in_products + 4.0 * (others + envs * int(config["hidden_size"]))
    row, rows = cache_row_bytes(config), mean_rows(config)
    layer = (row["index"] * (rows["scored"] + 1) + row["kv"] * (rows["selected"] + 1))
    return weights + envs * int(config["num_hidden_layers"]) * layer


def cache_bytes_per_stream(state_leaves):
    """Bytes of keys, values and index keys one stream carries, from a
    carry's state leaves: those shaped ``(streams, rows, row)``. ``None``
    where there is none."""
    caches = [leaf for leaf in state_leaves if getattr(leaf, "ndim", 0) == 3]
    if not caches:
        return None
    return sum(leaf.dtype.itemsize * leaf.size for leaf in caches) / float(
        caches[0].shape[0])
