"""Model operations one env step costs the LEARNER of a DQN over a
Nature CNN (the rollout's own forward passes are the sampler's, not
counted): each env step owes ``training_intensity`` trained rows; a
row is one online forward+backward on ``obs`` (3x), one target forward
on ``new_obs`` (1x) and, under double-Q, one online forward on
``new_obs`` (1x)."""

from perf.flops import forward_flops_per_sample


def train_flops_per_env_step(config, num_actions: int) -> float:
    algo = config["algo_config"]
    fwd = forward_flops_per_sample(config["model"], num_actions + 1)
    per_row = (3.0 + 1.0 + (1.0 if algo.get("double_q", True) else 0.0)) * fwd
    return per_row * float(algo["training_intensity"])
