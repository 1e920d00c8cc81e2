"""TEST DATA. Model operations one env step costs the learner of a PPO
over the small decoder: a sampled row is a sequence of S tokens, trained
``num_sgd_iter`` times, forward + backward = 3 x forward. A multiply-add
counts as two operations; causal attention needs S (S + 1) / 2 of the
S x S score and value products."""


def forward_flops_per_row(config, num_actions: int) -> float:
    m = config["model"]
    s, d = int(m["transformer_seq_len"]), int(m["transformer_dim"])
    ff, layers = int(m["transformer_ff_dim"]), int(m["transformer_num_layers"])
    tok = -(-int(config["obs_dim"]) // s)
    per_layer = (
        4 * s * d * d  # q, k, v and output projections (heads x head size = d)
        + 2 * (s * (s + 1) // 2) * d  # scores and probabilities x values
        + 2 * s * d * ff  # up and down
    )
    return 2.0 * (s * tok * d + layers * per_layer + d * (num_actions + 1))


def train_flops_per_env_step(config, num_actions: int) -> float:
    return (
        3.0 * forward_flops_per_row(config, num_actions)
        * int(config["algo_config"]["num_sgd_iter"])
    )
