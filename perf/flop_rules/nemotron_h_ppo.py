"""Model operations one env step (one token) costs a PPO over the
Nemotron-H block stack on the fused lane: the rollout's forward pass
(one decode step) plus ``num_sgd_iter`` trainings of the token, forward
+ backward = 3 x forward. A multiply-add counts as two operations.
Counted: what the algorithm NEEDS. A state-space block pays its two
projections, its convolution and the recurrence as the recurrence (per
element of the ``heads x head x state`` matrix a decay, a write and a
read: five operations), not the chunked form's ``(chunk, chunk)``
products; an expert block pays its router, the experts a token is
routed to AND that are held here (``top_k x held / router_outputs`` of
them on average: 0.375 of one), TWO matrices each, and the shared
expert's two, not the products the dense form or a grouped buffer's
empty rows run; the attention block pays a score and a value over the
mean depth of an episode (half of ``max_position_embeddings``); the
untied head is paid once (the lookup multiplies nothing). Recomputed
operations are not counted."""

from perf import ssm_moe_model as model


def forward_flops_per_token(config, num_actions: int) -> float:
    c = config
    p = model.layer_param_counts(config, num_actions)
    hs, head, n, _, channels = model._ssm_sizes(c)
    heads, dh = int(c["num_attention_heads"]), int(c["head_dim"])
    depth = int(c["max_position_embeddings"]) / 2.0
    routed = int(c["num_experts_per_tok"]) * p["held"] / float(c["router_outputs"])
    ops = 2.0 * (p["head"] + int(c["hidden_size"]))  # head and value head
    for kind in model.kinds(config):
        if kind == model.MAMBA:
            ops += 2.0 * (p["ssm_products"] + channels * int(c["conv_kernel"]))
            ops += 5.0 * hs * head * n
        elif kind == model.EXPERTS:
            ops += 2.0 * (p["router"] + routed * p["one_expert"] + p["shared"])
        else:
            ops += 2.0 * (p["attention_products"] + heads * depth * 2 * dh)
    return ops


def train_flops_per_env_step(config, num_actions: int) -> float:
    fwd = forward_flops_per_token(config, num_actions)
    return fwd * (1.0 + 3.0 * int(config["algo_config"].get("num_sgd_iter", 1)))
