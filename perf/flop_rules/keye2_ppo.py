"""Model operations one env step (one token) costs a PPO over the
``qwen3_moe`` stack with a learned index (``sa_config``) on the fused
lane: the rollout's forward pass (one decode step) plus ``num_sgd_iter``
trainings of the token, forward + backward = 3 x forward. A multiply-add
counts as two operations. Counted: what the EQUATIONS need. The index
pays its three projections and, for each of its heads, a score product
over every row the query sees at the mean depth of an episode
(``perf/sparse_attention_model.mean_rows``: ``position + 1``) and the
weighted relu sum over them; attention pays its projections and a score
and a value product of every query head over the ``min(position + 1,
topk)`` rows the index keeps, NOT over the rows it scores: a lowering
that multiplies every row under a mask runs more than this and reads a
lower ``learner.mfu_pct``. A token pays for the experts it is routed to
AND that are held here (``top_k x held / router_outputs``). The top-k
itself is comparisons, not multiply-adds, and is not counted; nor are
recomputed operations. The index's share of the backward pass is
counted though its gradient is zero (3 x forward throughout): under 2%
of the sum, the other way an estimate of what a trained index costs."""

from perf import sparse_attention_model as model


def forward_flops_per_token(config, num_actions: int) -> float:
    c = config
    p = model.layer_param_counts(config, num_actions)
    ix, rows = model.index_of(config), model.mean_rows(config)
    heads, dh = int(c["num_attention_heads"]), int(c["head_dim"])
    routed = (
        int(c["num_experts_per_tok"]) * int(c["num_experts"])
        / float(c.get("router_outputs", c["num_experts"]))
    )
    layer = (
        p["attention"] + p["router"] + routed * p["one_expert"]
        + p["index_products"] + int(c["hidden_size"]) * ix["heads"]
        + ix["heads"] * rows["scored"] * (ix["dim"] + 1)
        + heads * rows["selected"] * 2 * dh
    )
    macs = int(c["num_hidden_layers"]) * layer + p["head"] + int(c["hidden_size"])
    return 2.0 * macs


def train_flops_per_env_step(config, num_actions: int) -> float:
    fwd = forward_flops_per_token(config, num_actions)
    return fwd * (1.0 + 3.0 * int(config["algo_config"].get("num_sgd_iter", 1)))
