"""Model operations one env step (one token) costs a PPO over the
SmallThinker block stack on the fused lane: the rollout's forward pass
(one decode step) plus ``num_sgd_iter`` trainings of the token, forward
+ backward = 3 x forward. A multiply-add counts as two operations.
Counted: what the algorithm NEEDS. A token pays for the experts it is
routed to AND that are held here (``top_k x held / router_outputs`` of
them on average), not for the products the dense form or a grouped
buffer's empty rows run; attention pays a score and a value product
over the keys actually inside the mask at the mean depth of an episode
(``perf/window_model.mean_rows_seen``: the window's rows in a window
layer once the episode is past it, every row so far in the full layer),
not over the slots a masked product also multiplies. Recomputed
operations are not counted."""

from perf import window_model


def forward_flops_per_token(config, num_actions: int) -> float:
    c = config
    p = window_model.layer_param_counts(config, num_actions)
    heads, dh = int(c["num_attention_heads"]), int(c["head_dim"])
    routed = (
        int(c["moe_num_active_primary_experts"]) * int(c["moe_num_primary_experts"])
        / float(c.get("router_outputs", c["moe_num_primary_experts"]))
    )
    seen = window_model.mean_rows_seen(config)
    macs = p["head"] + int(c["hidden_size"])  # head and value head
    for is_window in window_model.windowed(config):
        macs += p["attention"] + p["router"] + routed * p["one_expert"]
        macs += heads * seen["window" if is_window else "full"] * 2 * dh
    return 2.0 * macs


def train_flops_per_env_step(config, num_actions: int) -> float:
    fwd = forward_flops_per_token(config, num_actions)
    return fwd * (1.0 + 3.0 * int(config["algo_config"].get("num_sgd_iter", 1)))
