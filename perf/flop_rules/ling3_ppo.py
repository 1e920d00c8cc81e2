"""Model operations one env step (one token) costs a PPO over the Ling
3.0 block stack on the fused lane: the rollout's forward pass (one decode
step) plus ``num_sgd_iter`` trainings of the token, forward + backward =
3 x forward. A multiply-add counts as two operations. Counted: what the
algorithm NEEDS. Every mixer, dense-layer and shared-expert product
weight and the head once; a token pays for the experts it is routed to
AND that are held here (``top_k x held / router_outputs`` of them on
average: an eighth of an expert), not for the dense or grouped product
the program runs; the router over all its outputs. A KDA layer pays its
three convolutions and the delta rule as the reference's recurrence does
it: the decay of a ``dk x dv`` matrix (one multiply an element) and
three ``dk x dv`` multiply-adds a head (read, write, output), NOT the
chunk solve's products. The latent layer pays attention over the mean
depth of an episode (half of ``max_position_embeddings``) at ``nope +
rope`` for a score and ``v_head_dim`` for a value, its own row through
``W_kvb`` once (a product weight, counted above). Recomputed operations
are not counted."""

from perf import kda_latent_model as m


def forward_flops_per_token(config, num_actions: int) -> float:
    z = m.sizes(config)
    p = m.layer_param_counts(config, num_actions)
    h, hd = z["heads"], z["head"]
    routed = z["top_k"] * z["held"] / float(z["outputs"])
    ops = 2.0 * p["ends"]["products"]
    for mixer, ffn in m._layers(config):
        ops += 2.0 * p[mixer]["products"]
        if mixer == m.KDA:
            ops += 2.0 * 3 * h * hd * z["conv"] + h * hd * hd * (1.0 + 3 * 2.0)
        else:
            ops += 2.0 * h * (z["positions"] / 2.0) * (
                z["nope"] + z["rope"] + z["v_head"])
        if ffn == "dense":
            ops += 2.0 * p["dense"]["products"]
        else:
            ops += 2.0 * (3 * z["d"] * z["shared"] + z["d"] * z["outputs"]
                          + routed * p["one_expert"]["products"])
    return ops


def train_flops_per_env_step(config, num_actions: int) -> float:
    fwd = forward_flops_per_token(config, num_actions)
    return fwd * (1.0 + 3.0 * int(config["algo_config"].get("num_sgd_iter", 1)))
