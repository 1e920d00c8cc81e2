"""Model operations one env step costs the LEARNER of a PPO over a
Nature CNN (the rollout's own forward passes are the sampler's, not
counted): each sampled row is trained ``num_sgd_iter`` times, forward
+ backward = 3 x forward."""

from perf.flops import forward_flops_per_sample


def train_flops_per_env_step(config, num_actions: int) -> float:
    fwd = forward_flops_per_sample(config["model"], num_actions + 1)
    return 3.0 * fwd * int(config["algo_config"]["num_sgd_iter"])
