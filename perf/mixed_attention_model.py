"""What the readers and the FLOP rule of a mixed-geometry attention cell
share: the parameters this chip holds, by layer and part, the cache rows
a query sees, the bytes one decode step must move and the cache a stream
carries, from the configuration's shapes alone (a configuration with
``layer_types``, ``num_attention_heads_per_layer``, ``rope_parameters``,
``sliding_window``, ``mlp_layer_types`` and ``num_experts`` held experts
beside a shared one: ``perf/configs/laguna_xs2_33b_a3b_ppo.json``).
Device time by the model's named scopes is
``perf/sequence_model.seconds_under``.
"""

from __future__ import annotations

from typing import Dict, List

WINDOW = "sliding_attention"


def layers(config: Dict) -> List[Dict]:
    """``{"window", "heads", "sparse"}`` for each layer run: the
    published lists' first ``num_hidden_layers`` entries."""
    c = config
    n = int(c["num_hidden_layers"])
    return [
        {"window": kind == WINDOW, "heads": int(heads), "sparse": ffn != "dense"}
        for kind, heads, ffn in zip(
            c["layer_types"][:n], c["num_attention_heads_per_layer"][:n],
            c["mlp_layer_types"][:n])
    ]


def layer_param_counts(config: Dict, layer: Dict) -> Dict[str, float]:
    """One layer's parameters by part, as this chip holds them.
    ``in_products`` of them enter a bfloat16 product; the rest (router,
    norms) is used in float32."""
    c = config
    d, dh = int(c["hidden_size"]), int(c["head_dim"])
    kv, h = int(c["num_key_value_heads"]), layer["heads"]
    # W_q and W_o; W_k and W_v; the gate a head; the q and k norms
    attention = 2 * d * h * dh + 2 * d * kv * dh + d * h
    out = {"attention": attention, "norms": 2 * d + 2 * dh}
    if layer["sparse"]:
        one = 3 * d * int(c["moe_intermediate_size"])
        out.update(
            router=d * int(c.get("router_outputs", c["num_experts"])),
            one_expert=one, experts_held=int(c["num_experts"]) * one,
            shared=3 * d * int(c["shared_expert_intermediate_size"]))
        feed_forward = out["experts_held"] + out["shared"]
    else:
        out["dense"] = feed_forward = 3 * d * int(c["intermediate_size"])
    out["in_products"] = attention + feed_forward
    out["all"] = out["in_products"] + out["norms"] + out.get("router", 0)
    return out


def param_count(config: Dict, num_actions: int) -> float:
    d = int(config["hidden_size"])
    body = sum(layer_param_counts(config, l)["all"] for l in layers(config))
    # embedding, untied head, final norm, value head and its bias
    return body + 2 * num_actions * d + d + d + 1


def product_weight_count(config: Dict, num_actions: int) -> float:
    """Parameters that enter a bfloat16 product (q/k/v/g/o, the dense
    feed-forward, the held and the shared experts' matrices, the output
    head)."""
    return sum(
        layer_param_counts(config, l)["in_products"] for l in layers(config)
    ) + int(config["hidden_size"]) * num_actions


def mean_rows_seen(config: Dict) -> Dict[str, float]:
    """Cache rows inside the mask of a query at a position drawn evenly
    from an episode of ``max_position_embeddings``, its own among them:
    ``min(position + 1, window)`` on average in a window layer,
    ``position + 1`` in a full one."""
    s = int(config["max_position_embeddings"])
    w = min(int(config["sliding_window"]), s)
    return {
        "full": (s + 1) / 2.0,
        "window": (w * (w + 1) / 2.0 + (s - w) * w) / s,
    }


def cache_row_bytes(config: Dict) -> float:
    """One position's bfloat16 key and value of every KV head."""
    return 2.0 * 2 * int(config["num_key_value_heads"]) * int(config["head_dim"])


def cache_bytes(config: Dict) -> List[float]:
    """Bytes of keys and values ONE stream carries in each layer: the
    episode's rows in a full layer, the window's in a ring."""
    s = int(config["max_position_embeddings"])
    w = min(int(config["sliding_window"]), s)
    return [cache_row_bytes(config) * (w if l["window"] else s)
            for l in layers(config)]


def decode_step_bytes(config: Dict, num_actions: int, envs: int) -> float:
    """Bytes one decode step of ``envs`` streams MUST move through HBM:
    the weights of every bfloat16 product once at 2 bytes (the
    parameters are float32, but they do not change inside a rollout and
    are converted once, outside the step loop), the other weights at 4
    (of the embedding only the rows looked up), and per layer and stream
    the cache rows INSIDE the mask at the mean depth once
    (``mean_rows_seen``) plus the step's own row written. Every HELD
    expert's weights count, chosen by a token of the step or not: that is
    what the dense one-token product reads, and a product that read only
    the chosen ones would move less than this and read above its share.
    Not the slots outside the mask that a masked product also reads."""
    d = int(config["hidden_size"])
    in_products = product_weight_count(config, num_actions)
    others = param_count(config, num_actions) - num_actions * d - in_products
    weights = 2.0 * in_products + 4.0 * (others + envs * d)
    seen = mean_rows_seen(config)
    rows = sum(seen["window" if l["window"] else "full"] + 1 for l in layers(config))
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
