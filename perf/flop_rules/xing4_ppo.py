"""Model operations one env step (one token) costs a PPO over the
latent-attention block stack on the fused lane: the rollout's forward
pass (one decode step) plus ``num_sgd_iter`` trainings of the token,
forward + backward = 3 x forward. A multiply-add counts as two
operations. Counted: what the algorithm NEEDS. A token pays for the
experts it is routed to AND that are held here (``top_k x held /
router_outputs`` of them on average: half an expert), not for the dense
grouped product the program runs; its own latent row goes through
``W_kvb`` once (not the 2,048 stored rows the fragment form expands
again); attention over the mean depth of an episode (half of
``max_position_embeddings``) at ``nope + rope`` for a score and
``v_head_dim`` for a value; the hyper-connection's maps and its three
mixes over the lanes. Recomputed operations are not counted."""

from perf import latent_model


def forward_flops_per_token(config, num_actions: int) -> float:
    c = config
    p = latent_model.layer_param_counts(config, num_actions)
    dense, experts = latent_model.layers(config)
    d, n = int(c["hidden_size"]), int(c["hc_mult"])
    heads = int(c["num_attention_heads"])
    depth = int(c["max_position_embeddings"]) / 2.0
    per_position = sum(int(c[k]) for k in
                       ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    # phi, then H_pre X, H_res X and the post-add over n lanes
    hyper = n * d * (2 * n + n * n) + (2 * n + n * n) * d
    routed = (
        int(c["num_experts_per_tok"]) * int(c["experts_held"][1])
        / float(c["router_outputs"])
    )
    every = p["mixer_products"] + heads * depth * per_position + 2 * hyper
    macs = (
        p["head"]
        + dense * (every + p["dense_mlp"])
        + experts * (every + p["router"] + p["shared"] + routed * p["one_expert"])
    )
    return 2.0 * macs


def train_flops_per_env_step(config, num_actions: int) -> float:
    fwd = forward_flops_per_token(config, num_actions)
    return fwd * (1.0 + 3.0 * int(config["algo_config"].get("num_sgd_iter", 1)))
