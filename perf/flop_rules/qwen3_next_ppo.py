"""Model operations one env step (one token) costs a PPO over the
Qwen3-Next block stack on the fused lane: the rollout's forward pass
(one decode step) plus ``num_sgd_iter`` trainings of the token,
forward + backward = 3 x forward. A multiply-add counts as two
operations. Counted: what the algorithm NEEDS. A token pays for the
experts it is routed to AND that are held here (``top_k x held /
router_outputs`` of them on average), not for the dense grouped
product the program runs; attention over the mean depth of an episode
(half of ``max_position_embeddings``); the delta rule as three
``dk x dv`` products a value head (read, write, output). Recomputed
operations are not counted."""

from perf import sequence_model


def forward_flops_per_token(config, num_actions: int) -> float:
    c = config
    p = sequence_model.layer_param_counts(config, num_actions)
    d = int(c["hidden_size"])
    hv, dk, dv = (int(c[k]) for k in ("linear_num_value_heads",
                                      "linear_key_head_dim", "linear_value_head_dim"))
    heads, hd = int(c["num_attention_heads"]), int(c["head_dim"])
    depth = int(c["max_position_embeddings"]) / 2.0
    routed = (
        int(c["num_experts_per_tok"]) * int(c["experts_held"][1])
        / float(c["router_outputs"])
    )
    block = p["router_and_shared"] + routed * p["one_expert"]
    macs = p["head"]
    for kind in sequence_model._kinds(config):
        macs += block
        if kind == sequence_model.LINEAR:
            macs += p["linear_mixer"] + 3 * hv * dk * dv
        else:
            macs += p["full_mixer"] + 2 * heads * hd * depth
    return 2.0 * macs


def train_flops_per_env_step(config, num_actions: int) -> float:
    fwd = forward_flops_per_token(config, num_actions)
    return fwd * (1.0 + 3.0 * int(config["algo_config"].get("num_sgd_iter", 1)))
