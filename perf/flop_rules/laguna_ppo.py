"""Model operations one env step (one token) costs a PPO over the
Laguna block stack on the fused lane: the rollout's forward pass (one
decode step) plus ``num_sgd_iter`` trainings of the token, forward +
backward = 3 x forward. A multiply-add counts as two operations.
Counted: what the algorithm NEEDS. A token pays for the experts it is
routed to AND that are held here (``top_k x held / router_outputs`` of
them on average: one of its eight) and for the shared expert, not for
the products the dense form or a grouped buffer's empty rows run;
attention pays a score and a value product of ITS layer's query heads
over the keys actually inside the mask at the mean depth of an episode
(``perf/mixed_attention_model.mean_rows_seen``: the window's rows in a
window layer once the episode is past it, every row so far in a full
layer), not over the slots a masked product also multiplies. Recomputed
operations are not counted."""

from perf import mixed_attention_model as model


def forward_flops_per_token(config, num_actions: int) -> float:
    c = config
    d, dh = int(c["hidden_size"]), int(c["head_dim"])
    routed = (
        int(c["num_experts_per_tok"]) * int(c["num_experts"])
        / float(c.get("router_outputs", c["num_experts"]))
    )
    seen = model.mean_rows_seen(config)
    macs = d * num_actions + d  # head and value head
    for layer in model.layers(config):
        p = model.layer_param_counts(config, layer)
        macs += p["attention"]
        macs += layer["heads"] * seen["window" if layer["window"] else "full"] * 2 * dh
        if layer["sparse"]:
            macs += p["router"] + routed * p["one_expert"] + p["shared"]
        else:
            macs += p["dense"]
    return 2.0 * macs


def train_flops_per_env_step(config, num_actions: int) -> float:
    fwd = forward_flops_per_token(config, num_actions)
    return fwd * (1.0 + 3.0 * int(config["algo_config"].get("num_sgd_iter", 1)))
