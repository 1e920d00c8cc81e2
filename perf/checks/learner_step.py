"""Learner step: the policy's OWN sharded learn body, one optimizer
step on seeded minibatches and seeded weights, against the plain
reference's loss and gradients. The gradient is read out of Adam's
first moment, so a sum in place of the mean over N shards reads N-1,
and a precision step below the configuration's reads over the limit.
How many rows and minibatches: the configuration's ``learner_check``;
what a row is: the reference's ``make_batch``."""

from perf import correct

STAGE = "before_first_iterations"
LIMITS = ("grad_rel_l2", "grad_leaf_rel_l2_max", "loss_rel")


def _distances(state, outcome):
    """``outcome(batch) -> (loss, gradients in the reference's names)``
    held to the reference's on the seeded minibatches. Returns the
    distances and the last pair of losses."""
    cell, ref = state.cell, state.ref
    rows, batches = cell.learner_check_shape
    floor = cell.limits.floor("loss_rel")
    leaf_floor = cell.limits.floor("grad_leaf_rel_l2_max", 0.0)
    per_batch = []
    for batch in correct.seeded_batches(
        ref, cell.config, state.seed, rows, batches, state.num_actions
    ):
        ref_loss, ref_grads = correct.reference_loss_and_grads(
            ref, state.ref_params, batch, cell.config
        )
        loss, grads = outcome(batch)
        d = correct.compare_grads(grads, ref_grads, leaf_floor)
        d["loss_rel"] = abs(loss - ref_loss) / max(abs(ref_loss), floor)
        per_batch.append(d)
    return correct.distances(per_batch), loss, ref_loss


def system_distances(state):
    cell, policy = state.cell, state.policy
    step_fn = correct.system_learn_step(policy, cell.learner_check_shape[0])

    def outcome(batch):
        loss, tree = correct.system_loss_and_grads(policy, step_fn, batch)
        return loss, state.ref.from_policy_tree(tree, cell.config)

    return _distances(state, outcome)


def control_distances(state, precision: str):
    """A control: the reference in the system's place, computed in
    ``precision``. Not part of a benchmark run; ``perf/control.py``
    and the tests call it."""
    return _distances(state, lambda batch: correct.reference_loss_and_grads(
        state.ref, state.ref_params, batch, state.cell.config, precision=precision
    ))[0]


def run(state):
    cell, checks = state.cell, state.checks
    rows, batches = cell.learner_check_shape
    out, sys_loss, ref_loss = system_distances(state)
    checks.at_most(
        "grad_rel_l2", out["grad_rel_l2"], cell.limit("grad_rel_l2"),
        f"rms over {batches} minibatches of {rows} rows on "
        f"{state.policy.n_shards} shard(s); last system loss {sys_loss:.6g}, "
        f"reference {ref_loss:.6g}",
    )
    checks.at_most(
        "grad_leaf_rel_l2_max", out["grad_leaf_rel_l2_max"],
        cell.limit("grad_leaf_rel_l2_max"), f"worst leaf {out['worst_leaf']}",
    )
    checks.at_most("loss_rel", out["loss_rel"], cell.limit("loss_rel"))
    return out


def readings(state):
    """``{"system": {...}, "<precision>": {...}}``: what sound runs
    give and what each control gives, for ``perf/control.py``."""
    out = {"system": run(state)}
    for precision in state.cell.control_precisions:
        out[precision] = control_distances(state, precision)
    return out
