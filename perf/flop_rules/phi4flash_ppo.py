"""Model operations one env step (one token) costs a PPO over the SambaY
block stack on the fused lane: the rollout's forward pass (one decode
step) plus ``num_sgd_iter`` trainings of the token, forward + backward =
3 x forward. A multiply-add counts as two operations. Counted: what the
algorithm NEEDS. Every product weight once (the tied table as the output
head: the lookup multiplies nothing). An attention layer, window, full
or cross, pays for each of its query pairs BOTH maps' score products (a
head wide each) and BOTH value products (two heads wide each) over the
MEAN rows inside its mask (``perf/sambay_model.mean_rows_seen``), not
over the slots a masked product also multiplies, and not the zero halves
the system's paired queries carry through the score product. A scan
layer pays its convolution and the recurrence as ``9 x inner x state``
operations a token (an ``exp``, the decay, the write's two factors and
its add, the read's multiply and add, ``dt x u`` shared): VECTOR work,
none of it on the matrix unit. Recomputed operations are not counted."""

from perf import sambay_model as m


def forward_flops_per_token(config, num_actions: int) -> float:
    z, seen = m.sizes(config), m.mean_rows_seen(config)
    pairs = z["heads"] // 2
    ops = 2.0 * (m.product_weight_count(config, num_actions) + z["d"])
    for kind in m.kinds(config):
        if kind == m.SCAN:
            ops += 2.0 * z["inner"] * z["conv"] + 9.0 * z["inner"] * z["state"]
        elif kind in (m.WINDOW, m.FULL, m.CROSS):
            rows = seen["window" if kind == m.WINDOW else "full"]
            ops += 2.0 * pairs * rows * (2 * z["head"] + 2 * 2 * z["head"])
    return ops


def train_flops_per_env_step(config, num_actions: int) -> float:
    fwd = forward_flops_per_token(config, num_actions)
    return fwd * (1.0 + 3.0 * int(config["algo_config"].get("num_sgd_iter", 1)))
