"""Model operations one env step (one token) costs a PPO over the
state-space block stack on the fused lane: the rollout's forward pass
(one decode step) plus ``num_sgd_iter`` trainings of the token, forward
+ backward = 3 x forward. A multiply-add counts as two operations.
Counted: what the algorithm NEEDS. A state-space layer pays its two
projections, its convolution and the recurrence as the recurrence (per
element of the ``heads x head x state`` matrix a decay, a write and a
read: five operations), not the chunked form's ``(chunk, chunk)``
products; the attention layer pays a score and a value over the mean
depth of an episode (half of ``max_position_embeddings``); the tied
table is paid once, as the output head (the lookup multiplies nothing).
Recomputed operations are not counted."""

from perf import ssm_model


def forward_flops_per_token(config, num_actions: int) -> float:
    c = config
    p = ssm_model.layer_param_counts(config, num_actions)
    hs, head, n = (int(c[k]) for k in ("mamba_n_heads", "mamba_d_head", "mamba_d_state"))
    channels = hs * head + 2 * int(c.get("mamba_n_groups", 1)) * n
    heads = int(c["num_attention_heads"])
    dh = int(c.get("head_dim") or int(c["hidden_size"]) // heads)
    depth = int(c["max_position_embeddings"]) / 2.0
    ops = 2.0 * (p["table"] + int(c["hidden_size"]))  # head and value head
    for kind in ssm_model.kinds(config):
        ops += 2.0 * p["mlp"]
        if kind == ssm_model.MAMBA:
            ops += 2.0 * (p["ssm_products"] + channels * int(c["mamba_d_conv"]))
            ops += 5.0 * hs * head * n
        else:
            ops += 2.0 * (p["attention_products"] + heads * depth * 2 * dh)
    return ops


def train_flops_per_env_step(config, num_actions: int) -> float:
    fwd = forward_flops_per_token(config, num_actions)
    return fwd * (1.0 + 3.0 * int(config["algo_config"].get("num_sgd_iter", 1)))
