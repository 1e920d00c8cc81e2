"""What the readers and the FLOP rule of a window-attention cell share:
the parameters this chip holds, by part, the cache rows a query sees,
the bytes one decode step must move and the cache a stream carries,
from the configuration's shapes alone (a configuration with
``sliding_window_layout`` / ``rope_layout``, ``moe_num_primary_experts``
held experts and no shared expert:
``perf/configs/smallthinker_21b_a3b_ppo.json``). Device time by the
model's named scopes is ``perf/sequence_model.seconds_under``.
"""

from __future__ import annotations

from typing import Dict, List


def windowed(config: Dict) -> List[bool]:
    """A window layer or a full one, for each layer run: the published
    layout's first ``num_hidden_layers``."""
    layers = int(config["num_hidden_layers"])
    return [bool(w) for w in config["sliding_window_layout"][:layers]]


def layer_param_counts(config: Dict, num_actions: int) -> Dict[str, float]:
    """Parameters by part, as this chip holds them."""
    c = config
    d = int(c["hidden_size"])
    heads, kv, dh = (int(c[k]) for k in
                     ("num_attention_heads", "num_key_value_heads", "head_dim"))
    one_expert = 3 * d * int(c["moe_ffn_hidden_size"])
    return {
        "attention": 2 * d * heads * dh + 2 * d * kv * dh,  # W_q, W_o; W_k, W_v
        "router": d * int(c.get("router_outputs", c["moe_num_primary_experts"])),
        "one_expert": one_expert,
        "experts_held": int(c["moe_num_primary_experts"]) * one_expert,
        "norms": 2 * d,
        "embedding": num_actions * d,
        "head": d * num_actions,
        "value_and_final_norm": d + 1 + d,
    }


def param_count(config: Dict, num_actions: int) -> float:
    p = layer_param_counts(config, num_actions)
    layer = p["attention"] + p["router"] + p["experts_held"] + p["norms"]
    return (len(windowed(config)) * layer + p["embedding"] + p["head"]
            + p["value_and_final_norm"])


def product_weight_count(config: Dict, num_actions: int) -> float:
    """Parameters that enter a bfloat16 product (q/k/v/o, the held
    experts' matrices, the output head); the rest (routers, norms, the
    value head) is used in float32."""
    p = layer_param_counts(config, num_actions)
    return len(windowed(config)) * (p["attention"] + p["experts_held"]) + p["head"]


def mean_rows_seen(config: Dict) -> Dict[str, float]:
    """Cache rows inside the mask of a query at a position drawn evenly
    from an episode of ``max_position_embeddings``, its own among them:
    ``min(position + 1, window)`` on average in a window layer,
    ``position + 1`` in a full one."""
    s, w = int(config["max_position_embeddings"]), int(config["sliding_window_size"])
    w = min(w, s)
    return {
        "full": (s + 1) / 2.0,
        "window": (w * (w + 1) / 2.0 + (s - w) * w) / s,
    }


def cache_row_bytes(config: Dict) -> float:
    """One position's bfloat16 key and value of every KV head."""
    return 2.0 * 2 * int(config["num_key_value_heads"]) * int(config["head_dim"])


def cache_bytes(config: Dict) -> Dict[str, float]:
    """Bytes of keys and values ONE stream carries in a layer of each
    kind: the episode's rows in a full layer, the window's in a ring."""
    s, w = int(config["max_position_embeddings"]), int(config["sliding_window_size"])
    return {"full": cache_row_bytes(config) * s,
            "window": cache_row_bytes(config) * min(w, s)}


def decode_step_bytes(config: Dict, num_actions: int, envs: int) -> float:
    """Bytes one decode step of ``envs`` streams MUST move through HBM:
    the weights of every bfloat16 product once at 2 bytes (the
    parameters are float32, but they do not change inside a rollout and
    are converted once, outside the step loop), the other weights at 4
    (of the embedding only the rows looked up), and per layer and stream
    the cache rows INSIDE the mask at the mean depth once (``mean_rows_seen``)
    plus the step's own row written. Not the slots outside the mask that a
    masked product also reads: a program that reads them moves more than
    this and reads a lower share."""
    p = layer_param_counts(config, num_actions)
    in_products = product_weight_count(config, num_actions)
    others = param_count(config, num_actions) - p["embedding"] - in_products
    weights = 2.0 * in_products + 4.0 * (others + envs * int(config["hidden_size"]))
    seen = mean_rows_seen(config)
    rows = sum(seen["window" if w else "full"] + 1 for w in windowed(config))
    return weights + envs * cache_row_bytes(config) * rows


def cache_bytes_per_stream(state_leaves):
    """Bytes of keys and values one stream carries, from a carry's
    state leaves: those shaped ``(streams, rows, row)``. ``None`` where
    there is none."""
    caches = [leaf for leaf in state_leaves if getattr(leaf, "ndim", 0) == 3]
    if not caches:
        return None
    return sum(leaf.dtype.itemsize * leaf.size for leaf in caches) / float(
        caches[0].shape[0])
