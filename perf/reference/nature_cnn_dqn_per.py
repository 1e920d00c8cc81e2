"""Reference for ``nature_cnn_dqn_per``: Nature CNN with a dueling
head (Wang et al. 2016), the double-Q target (van Hasselt et al.
2016), Huber loss (delta 1) weighted by the importance weights of
prioritized replay (Schaul et al. 2016), the optimizer updates (clip,
Adam) with the priority each refreshes, and the proportional
stratified draw itself over a plain cumulative sum."""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from perf.reference import nature_cnn

HEAD_SCALE: Dict[str, float] = {}


def heads(num_actions: int) -> Dict[str, int]:
    return {"advantage": num_actions, "value": 1}


def init_params(key, config: Dict, num_actions: int):
    return nature_cnn.init_params(
        key, config["model"], heads(num_actions), HEAD_SCALE
    )


def to_policy_tree(params, config: Dict):
    """Reference names -> the flax tree of ``DQNModel``."""
    out = {}
    for i in range(len(config["model"]["conv_filters"])):
        out[f"_convs_{i}"] = params[f"conv{i}"]
    for j in range(len(config["model"]["dense"])):
        out[f"_fcs_{j}"] = params[f"dense{j}"]
    out["_adv_head"] = params["advantage"]
    out["_value_head"] = params["value"]
    return {"params": out}


def from_policy_tree(tree, config: Dict):
    t = tree["params"]
    out = {}
    for i in range(len(config["model"]["conv_filters"])):
        out[f"conv{i}"] = t[f"_convs_{i}"]
    for j in range(len(config["model"]["dense"])):
        out[f"dense{j}"] = t[f"_fcs_{j}"]
    out["advantage"] = t["_adv_head"]
    out["value"] = t["_value_head"]
    return out


def make_batch(rng: np.random.Generator, config: Dict, rows: int, num_actions: int):
    shape = tuple(config["model"]["input_shape"])
    return {
        "obs": rng.integers(0, 256, (rows,) + shape, dtype=np.uint8),
        "new_obs": rng.integers(0, 256, (rows,) + shape, dtype=np.uint8),
        "actions": rng.integers(0, num_actions, rows).astype(np.int32),
        # rewards off to one side and wide: the TD error then has
        # mostly one sign (the 512 per-row gradients do not cancel to
        # a remainder that rounding swamps) and lands on both sides
        # of the Huber knee
        "rewards": rng.normal(0.8, 0.8, rows).astype(np.float32),
        "dones": rng.random(rows) < 0.1,  # ray_tpu column name of "terminated"
        "weights": rng.uniform(0.2, 1.0, rows).astype(np.float32),
    }


def q_values(params, obs, config: Dict, precision: str):
    feat = nature_cnn.trunk(params, obs, config["model"], precision)
    adv = nature_cnn.head(params, "advantage", feat)
    value = nature_cnn.head(params, "value", feat)
    return value + adv - jnp.mean(adv, axis=1, keepdims=True)


def td_error(params, target_params, batch, config: Dict, precision: str = "float32"):
    """Per-row ``Q(s, a) - (r + gamma^n (1 - done) Q_target(s', a*))``
    with ``a*`` chosen by the online network under double-Q."""
    algo = config["algo_config"]
    q = q_values(params, batch["obs"], config, precision)
    q_sel = jnp.take_along_axis(
        q, batch["actions"][:, None].astype(jnp.int32), axis=1
    )[:, 0]
    q_next_target = q_values(target_params, batch["new_obs"], config, precision)
    if algo.get("double_q", True):
        chooser = q_values(params, batch["new_obs"], config, precision)
    else:
        chooser = q_next_target
    next_a = jnp.argmax(chooser, axis=1)
    q_next = jnp.take_along_axis(q_next_target, next_a[:, None], axis=1)[:, 0]
    not_done = 1.0 - batch["dones"].astype(jnp.float32)
    target = batch["rewards"] + float(algo["gamma"]) ** int(
        algo.get("n_step", 1)
    ) * not_done * jax.lax.stop_gradient(q_next)
    return q_sel - jax.lax.stop_gradient(target)


def loss(params, batch, config: Dict, precision: str = "float32", target_params=None):
    """Huber (delta 1) of the TD error, weighted by the importance
    weights, mean over the rows. ``target_params`` defaults to
    ``params`` held constant: the target network right after a sync."""
    if target_params is None:
        target_params = jax.lax.stop_gradient(params)
    td = td_error(params, target_params, batch, config, precision)
    a = jnp.abs(td)
    huber = jnp.where(a < 1.0, 0.5 * jnp.square(td), a - 0.5)
    return jnp.mean(batch["weights"] * huber)


ADAM_B1, ADAM_B2 = 0.9, 0.999


def updates(params, batches, config: Dict, precision: str = "float32"):
    """``K`` optimizer updates in a row, as the configuration states
    them, from a fresh optimizer and with the target network held at
    the starting weights: for each of the ``K`` stacked minibatches
    (``batches[col]`` is ``(K, B, ...)``, ``weights`` among them) the
    gradient of ``loss``, clipped to the global norm ``grad_clip``,
    then Adam (Kingma & Ba 2015: bias-corrected moments, ``lr``,
    ``adam_epsilon`` outside the root). After each update the rows'
    ``|TD error|`` under the NEW weights: what prioritized replay
    refreshes their priorities from. Returns the final weights, Adam's
    first moment, and per update the loss and the ``(B,)`` |TD|."""
    algo = config["algo_config"]
    lr, eps = float(algo["lr"]), float(algo.get("adam_epsilon", 1e-8))
    clip = algo.get("grad_clip")
    target = params
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)

    def one(carry, batch):
        p, mu, nu, t = carry
        value, g = jax.value_and_grad(loss)(p, batch, config, precision, target)
        if clip:
            norm = jnp.sqrt(
                sum(jnp.sum(jnp.square(x)) for x in jax.tree_util.tree_leaves(g))
            )
            g = jax.tree_util.tree_map(
                lambda x: x * jnp.minimum(1.0, float(clip) / norm), g
            )
        t = t + 1.0
        mu = jax.tree_util.tree_map(lambda m, x: ADAM_B1 * m + (1 - ADAM_B1) * x, mu, g)
        nu = jax.tree_util.tree_map(
            lambda v, x: ADAM_B2 * v + (1 - ADAM_B2) * jnp.square(x), nu, g
        )
        p = jax.tree_util.tree_map(
            lambda w, m, v: w
            - lr * (m / (1 - ADAM_B1**t)) / (jnp.sqrt(v / (1 - ADAM_B2**t)) + eps),
            p, mu, nu,
        )
        td = jnp.abs(td_error(p, target, batch, config, precision))
        return (p, mu, nu, t), (value, td)

    (p, mu, _, _), (losses, tds) = jax.lax.scan(
        one, (params, zeros, zeros, jnp.float32(0.0)), batches
    )
    return {"params": p, "mu": mu, "losses": losses, "abs_td": tds}


def stratified_draw(leaves: np.ndarray, rand: np.ndarray, beta: float):
    """The proportional stratified draw over ``leaves`` (priorities
    already raised to alpha, float64): stratum i of B covers mass
    ``[(i, i+1) / B) * total``; the row drawn is the first whose
    inclusive cumulative sum reaches the mass. Importance weights are
    ``(N * p)^-beta`` over their maximum. Plain ``cumsum`` +
    ``searchsorted`` — no tree."""
    leaves = np.asarray(leaves, np.float64)
    n = len(leaves)
    b = len(rand)
    csum = np.cumsum(leaves)
    total = csum[-1]
    mass = (np.asarray(rand, np.float64) + np.arange(b)) / b * total
    idx = np.minimum(np.searchsorted(csum, mass, side="left"), n - 1)
    p = leaves[idx] / total
    max_w = (leaves.min() / total * n) ** (-beta)
    return idx.astype(np.int64), ((p * n) ** (-beta) / max_w).astype(np.float32)
