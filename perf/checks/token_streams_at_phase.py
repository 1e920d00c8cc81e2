"""Set-up of the token cell: the streams are brought to the traffic's
stated phase with the MODEL's state at the same depth.

The env places stream ``i`` ``phase_stride * i`` tokens into its first
episode, but a stream's model state (DeltaNet matrices, convolution
inputs, keys and values, position) starts empty, so cache depth and
episode depth disagree until the stream has ended that first episode.
This runs the lane's own rollout body (the standalone program, no
update) for one episode's length of fragments: every stream ends its
first episode once, the env is back at ``phase_stride * i`` and each
model state is exactly that deep, generated by the policy itself. From
the first comparison and the first measured iteration on, cache depths
cover the episode evenly. Its cost (an episode of decode steps) is
set-up's.

While the streams are brought there (and the program's first iteration
compiles the fused program, over a minute), a thread compiles the large
programs of the later comparisons (each check's ``ahead(state)``: the
reference's forward and gradient, the policy's learn form) into the
compile cache, where their first calls find them: set-up is a hundred
seconds shorter on an empty cache for it. The thread only compiles; it
runs nothing and holds no device memory.

``streams_off_phase`` counts streams whose model position differs from
their env's position in the episode, or whose env is not ``phase_stride
* i`` tokens ahead of stream 0's."""

import contextlib
import threading
import time

import numpy as np

STAGE = "before_first_iterations"
LIMITS = ("streams_off_phase",)


def _where(eng):
    """``(model position, env position in the episode)`` a stream."""
    carry = eng._carry
    return np.asarray(carry["state"][-1]), np.asarray(carry["env"]["t"])


def _compile_ahead(state):
    """Start the thread; the comparisons wait for it before their
    first call (``state.prepared["compile_ahead"]``)."""
    import jax

    jobs = []
    for stage in ("after_first_iterations", "after_warmup"):
        for _, check in state.cell.checks(stage):
            if hasattr(check, "ahead"):
                jobs.extend(check.ahead(state))

    def work():
        t0 = time.perf_counter()
        try:
            for fn, args, highest in jobs:
                with jax.default_matmul_precision("highest") if highest else (
                        contextlib.nullcontext()):
                    fn.lower(*args).compile()
        except Exception as e:  # the calls compile for themselves then
            print(f"[setup-part] compile_ahead gave up: {e!r}"[:400], flush=True)
        print(f"[setup-part] compile_ahead {len(jobs)} programs in "
              f"{time.perf_counter() - t0:.1f}s", flush=True)

    thread = threading.Thread(target=work, name="compile_ahead", daemon=True)
    thread.start()
    state.prepared["compile_ahead"] = thread


def run(state):
    import jax

    algo, policy = state.algo, state.policy
    eng = algo._jax_engine()
    env_config = state.cell.traffic["algo_config"]["env_config"]
    length = int(env_config["episode_length"])
    stride = int(env_config["phase_stride"])
    key = jax.random.fold_in(
        jax.random.PRNGKey(int(state.seed) % (2**31 - 1)), 29
    )
    fragments, t0 = 0, time.perf_counter()
    position, at = _where(eng)
    while np.any(position != at) and fragments <= -(-length // eng.T):
        key, sub = jax.random.split(key)
        eng._carry, _, _ = eng.rollout_from(
            policy.params, eng._carry, jax.random.split(sub, eng.T),
            eng._pre_dispatch(),
        )
        fragments += 1
        if fragments == 1:  # the rollout program has compiled; the thread's turn
            _compile_ahead(state)
        position, at = _where(eng)
    print(f"[setup-part] token_streams_at_phase fragments={fragments} "
          f"in {time.perf_counter() - t0:.1f}s", flush=True)
    # the stated phase: stream i is ``stride * i`` tokens ahead of stream 0
    ahead = (np.arange(eng.N) * stride) % length
    off = int(np.sum((position != at) | ((at - at[0]) % length != ahead)))
    state.checks.equal(
        "streams_off_phase", off, state.cell.limit("streams_off_phase"),
        f"{fragments} fragments of {eng.T} generated without an update; "
        f"{eng.N} streams at depths {int(at.min())}-{int(at.max())}, "
        f"{len(np.unique(at))} distinct",
    )
    return {"streams_off_phase": off}


def readings(state):
    """For ``perf.control``: the same set-up; not a precision's."""
    out = {"system": run(state)}
    for precision in state.cell.control_precisions:
        out[precision] = {"streams_off_phase": 0}
    return out
