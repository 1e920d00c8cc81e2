"""The rows one real iteration's device rollout put into the ring are
transitions of PongLite (``env/jax_pong.py``, frames stacked by
``perf/envs.py``): pixels of the env's three grey levels, each live
row's ``new_obs`` its ``obs`` moved on by one frame, rewards of -1, 0
or 1, and not one constant action. A traffic mix over another env
names a check of its own."""

import numpy as np

STAGE = "after_warmup"
LIMITS = ()


def _buffer(algo):
    return algo.local_replay_buffer.buffers["default_policy"]


def prepare(state):
    """The ring's cursor before the check iteration."""
    buf = _buffer(state.algo)
    return buf.num_added % buf.capacity


def run(state):
    import jax

    buf = _buffer(state.algo)
    first = state.prepared["rollout_rows_pong_lite"]
    it = state.iteration
    count = it["after"]["sampled"] - it["before"]["sampled"]
    frame_stack = int(state.cell.config["model"].get("frame_stack", 1))
    pos = (int(first) + np.arange(int(count))) % buf.capacity
    rows = jax.device_get(buf.gather(pos).tree)
    obs, new_obs = rows["obs"], rows["new_obs"]
    live = ~(rows["dones"] | rows["truncateds"])
    shifted = bool(live.any())
    if frame_stack > 1:
        c = obs.shape[-1] // frame_stack
        shifted &= bool(np.array_equal(new_obs[live][..., :-c], obs[live][..., c:]))
    state.checks.true(
        "rollout_rows_are_transitions",
        shifted
        and bool(obs.any())
        and set(np.unique(obs)) <= {0, 180, 255}
        and set(np.unique(rows["rewards"])) <= {-1.0, 0.0, 1.0}
        and len(np.unique(rows["actions"])) > 1,
        f"{count} rows at ring position {first}: {int(live.sum())} live, "
        f"rewards {np.unique(rows['rewards']).tolist()}, "
        f"actions {np.unique(rows['actions']).tolist()}",
    )
