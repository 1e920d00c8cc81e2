"""What the readers, the FLOP rule and the tests of a SambaY cell share:
the kinds of its layers, the parameters by part, the rows a query of
each attention kind sees, the bytes a stream carries, and the bytes one
decode step, one call of the one-token cross-attention and one
fragment's selective scan must move, from the configuration's shapes
alone (``model_type: phi4flash``: ``perf/configs/phi4_mini_flash_ppo.json``).
Device time by the model's named scopes is
``perf/sequence_model.seconds_under``.
"""

from __future__ import annotations

from typing import Dict, List

# bytes of the ``(streams, rows, row)`` leaves of a carry, a stream
from perf.window_model import cache_bytes_per_stream  # noqa: F401

# the kinds' names and the rule that gives a published layer its kind
# are the reference's
from perf.reference.phi4_flash import (  # noqa: F401
    CROSS, FULL, MEMORY, SCAN, WINDOW, kind_of)


def is_sambay(config: Dict) -> bool:
    return config.get("model_type") == "phi4flash"


def kinds(config: Dict) -> List[str]:
    """The held layers' kinds, by their published indices
    (``layer_indices`` of ``published_num_hidden_layers`` where the depth
    is cut)."""
    c = config
    held = int(c["num_hidden_layers"])
    depth = int(c.get("published_num_hidden_layers", held))
    return [kind_of(int(i), depth, int(c.get("mb_per_layer", 2)))
            for i in c.get("layer_indices") or range(held)]


def sizes(config: Dict) -> Dict[str, int]:
    c = config
    d, heads = int(c["hidden_size"]), int(c["num_attention_heads"])
    return {
        "d": d, "heads": heads, "kv_heads": int(c["num_key_value_heads"]),
        "head": d // heads, "ffn": int(c["intermediate_size"]),
        "inner": int(c.get("mamba_expand", 2)) * d,
        "state": int(c.get("mamba_d_state", 16)),
        "dt_rank": int(c.get("mamba_dt_rank", -(-d // 16))),
        "conv": int(c.get("mamba_d_conv", 4)),
        "window": int(c["sliding_window"]),
        "positions": int(c["max_position_embeddings"]),
    }


def layer_param_counts(config: Dict, num_actions: int) -> Dict[str, Dict[str, int]]:
    """``{part: {"products": n, "others": n}}``: the parameters of one
    layer of each kind's MIXER, of a block's feed-forward and norms, and
    of the model's ends, split into those that enter a bfloat16 product
    and the rest (biases, norms, the convolution, ``A_log``, ``D``, the
    lambda vectors, the value head), which are used in float32."""
    z = sizes(config)
    d, i, n, r = z["d"], z["inner"], z["state"], z["dt_rank"]
    wide, kv, dh = z["heads"] * z["head"], z["kv_heads"] * z["head"], z["head"]
    diff = 4 * dh + 2 * dh  # four lambda vectors and the pair norm's weight
    attention = {"products": d * (wide + 2 * kv) + wide * d,
                 "others": wide + 2 * kv + d + diff}
    return {
        SCAN: {"products": d * 2 * i + i * (r + 2 * n) + r * i + i * d,
               "others": i * z["conv"] + i + i + n * i + i},
        WINDOW: attention, FULL: attention,
        MEMORY: {"products": 2 * d * i, "others": 0},
        CROSS: {"products": 2 * d * wide, "others": wide + d + diff},
        "block": {"products": 3 * d * z["ffn"], "others": 4 * d},
        # the tied table once (the output head; the lookup multiplies
        # nothing), the final norm and the value head
        "ends": {"products": num_actions * d, "others": 2 * d + d + 1},
    }


def _sum(config: Dict, num_actions: int, which: str, layer_kinds=None) -> int:
    p = layer_param_counts(config, num_actions)
    total = p["ends"][which]
    for kind in layer_kinds or kinds(config):
        total += p[kind][which] + p["block"][which]
    return total


def param_count(config: Dict, num_actions: int, layer_kinds=None) -> int:
    return sum(_sum(config, num_actions, which, layer_kinds)
               for which in ("products", "others"))


def product_weight_count(config: Dict, num_actions: int) -> int:
    return _sum(config, num_actions, "products")


def published_param_count(config: Dict) -> int:
    """The uncut model's, by the same parts: every layer of the published
    depth and the whole vocabulary."""
    pub = config["published"]
    depth = int(pub["num_hidden_layers"])
    every = [kind_of(i, depth, int(config.get("mb_per_layer", 2)))
             for i in range(depth)]
    # no value head: the published model has none
    return param_count(config, int(pub["vocab_size"]), every) - (
        int(config["hidden_size"]) + 1)


def mean_rows_seen(config: Dict) -> Dict[str, float]:
    """Rows inside each mask of a query at a position drawn evenly from
    an episode of ``max_position_embeddings``, its own among them: ``t +
    1`` at full depth (the full layer and every cross layer),
    ``min(t + 1, window)`` on a ring."""
    z = sizes(config)
    s, w = z["positions"], z["window"]
    return {"full": (s + 1) / 2.0,
            "window": sum(min(t + 1, w) for t in range(s)) / float(s)}


def cache_row_bytes(config: Dict) -> float:
    """A row's bfloat16 keys, then values, of every key head."""
    z = sizes(config)
    return 2.0 * 2 * z["kv_heads"] * z["head"]


def scan_state_bytes(config: Dict) -> float:
    """A scan layer's float32 matrix and convolution inputs, a stream."""
    z = sizes(config)
    return 4.0 * z["inner"] * (z["state"] + z["conv"] - 1)


def cache_bytes(config: Dict) -> Dict[str, float]:
    """Bytes ONE stream carries, by what holds them: the full layer's
    cache ONCE (its readers hold nothing), a ring a window layer, a
    matrix and a convolution's inputs a scan layer."""
    z, ks = sizes(config), kinds(config)
    row = cache_row_bytes(config)
    return {
        "shared": ks.count(FULL) * row * z["positions"],
        "rings": ks.count(WINDOW) * row * min(z["window"], z["positions"]),
        "scans": ks.count(SCAN) * scan_state_bytes(config),
    }


def xattn_step_bytes(config: Dict, envs: int) -> float:
    """Bytes ONE call of a cross layer's one-token attention (one layer,
    one token of ``envs`` streams) must move: the shared cache's rows
    below the position at the mean depth once, key and value, and the
    query and output rows in float32."""
    z = sizes(config)
    return envs * (cache_row_bytes(config) * mean_rows_seen(config)["full"]
                   + 2 * 4.0 * 2 * z["heads"] * z["head"])


def scan_fragment_bytes(config: Dict, envs: int, tokens: int) -> float:
    """Bytes the selective scan of ONE layer must move for a fragment
    batch of ``envs`` x ``tokens`` in an update, forward and backward:
    forward a token's ``u``, ``dt`` in and ``y`` out (``inner`` float32
    each) and ``B``, ``C`` in (``state`` each); backward ``dy`` and the
    same four inputs in, their four cotangents out; the matrix once in
    and once out each way. Not the recomputation, and not the matrix
    once a TOKEN, which is what a scan whose carry lives in HBM moves."""
    z = sizes(config)
    token = (3 * z["inner"] + 2 * z["state"]) + (5 * z["inner"] + 4 * z["state"])
    return envs * 4.0 * (tokens * token + 4 * z["inner"] * z["state"])


def decode_step_bytes(config: Dict, num_actions: int, envs: int) -> float:
    """Bytes one decode step of ``envs`` streams MUST move through HBM:
    the weights of every bfloat16 product once at 2 bytes (the
    parameters are float32, but they do not change inside a rollout and
    are converted once, outside the step loop), the other weights at 4
    (of the embedding only the rows looked up); the full layer's rows
    below the position at the mean depth once PER READING LAYER (the
    layer itself and every cross layer), a ring's rows inside the
    window, and one row written a cache; each scan's matrix and
    convolution inputs read and written."""
    ks = kinds(config)
    z = sizes(config)
    in_products = product_weight_count(config, num_actions)
    others = _sum(config, num_actions, "others")
    weights = 2.0 * in_products + 4.0 * (others + envs * z["d"])
    seen, row = mean_rows_seen(config), cache_row_bytes(config)
    rows = ((ks.count(FULL) + ks.count(CROSS)) * seen["full"]
            + ks.count(WINDOW) * seen["window"]
            + ks.count(FULL) + ks.count(WINDOW))
    return weights + envs * (
        row * rows + ks.count(SCAN) * 2 * scan_state_bytes(config))
