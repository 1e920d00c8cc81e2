"""TEST DATA. A comparison of this family's own: the action
probabilities of the policy's inference forward (plain jit over the
model-sharded parameters, where the compiler and not the learn body
puts the collectives in) against the reference's, and the reference's
own attention probabilities held to a causal softmax. The number a
limit is set from is the largest absolute difference of an action
probability."""

import numpy as np

STAGE = "before_first_iterations"
LIMITS = ("action_prob_abs_max",)
ROWS = 64


def _obs(state):
    rng = np.random.default_rng([int(state.seed), 11])
    return state.ref.make_batch(
        rng, state.cell.config, ROWS, state.num_actions
    )["obs"]


def _reference_probs(state, obs, precision="float32"):
    import jax

    logits, _, attn = state.ref.forward(
        state.ref_params, obs, state.cell.config, precision
    )
    return np.asarray(jax.nn.softmax(logits), np.float64), np.asarray(attn)


def _system_probs(state, obs):
    import jax

    policy = state.policy
    logits, _, _ = jax.jit(policy.model.apply)(policy.params, obs)
    return np.asarray(jax.nn.softmax(logits), np.float64)


def readings(state):
    obs = _obs(state)
    want, _ = _reference_probs(state, obs)
    out = {"system": {
        "action_prob_abs_max": float(np.max(np.abs(_system_probs(state, obs) - want)))
    }}
    for precision in state.cell.control_precisions:
        got, _ = _reference_probs(state, obs, precision)
        out[precision] = {"action_prob_abs_max": float(np.max(np.abs(got - want)))}
    return out


def run(state):
    obs = _obs(state)
    want, attn = _reference_probs(state, obs)
    causal = np.triu(np.ones(attn.shape[-2:], bool), 1)
    state.checks.true(
        "reference_attention_is_a_causal_softmax",
        bool(np.all(attn[..., causal] == 0.0))
        and bool(np.allclose(attn.sum(-1), 1.0, atol=1e-5)),
        f"{attn.shape[0]} layers x {attn.shape[2]} heads x {attn.shape[-1]} positions",
    )
    state.checks.at_most(
        "action_prob_abs_max",
        float(np.max(np.abs(_system_probs(state, obs) - want))),
        state.cell.limit("action_prob_abs_max"),
        f"{ROWS} seeded observations through the policy's inference forward",
    )
