"""Model operations one env step (one byte) costs a PPO over the EvaByte
block stack on the fused lane: the rollout's forward pass (one decode
step) plus ``num_sgd_iter`` trainings of the token, forward + backward =
3 x forward. A multiply-add counts as two operations. Counted: what the
algorithm NEEDS. Attention pays a score and a value product over the
rows actually inside the two masks at the mean depth of an episode
(``perf/eva_model.mean_rows_seen``: ``t mod W + 1`` exact rows and ``(W /
c) floor(t / W)`` summaries), not over the slots a masked product also
multiplies, and the pooling: a token's share of its chunk's two pooling
logits (``phi . k``, ``mu . k``) and two weighted sums (of ``k`` and of
``v``), one head-row each. Recomputed operations are not counted."""

from perf import eva_model


def forward_flops_per_token(config, num_actions: int) -> float:
    c = config
    p = eva_model.layer_param_counts(config, num_actions)
    wide = int(c["num_attention_heads"]) * eva_model.head_dim(c)
    seen = eva_model.mean_rows_seen(config)
    macs = p["head"] + int(c["hidden_size"])  # head and value head
    macs += int(c["num_hidden_layers"]) * (
        p["attention"] + p["feed_forward"]
        + wide * 2 * (seen["window"] + seen["summary"])  # scores and values
        + 4 * wide)  # the pooling
    return 2.0 * macs


def train_flops_per_env_step(config, num_actions: int) -> float:
    fwd = forward_flops_per_token(config, num_actions)
    return fwd * (1.0 + 3.0 * int(config["algo_config"].get("num_sgd_iter", 1)))
