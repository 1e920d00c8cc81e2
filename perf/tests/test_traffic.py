"""The traffic the benchmark makes itself: the frame-stacked env and
the seeded ring fill."""

import numpy as np
import pytest

from perf import envs, ringfill


def _stacked(k=4):
    from ray_tpu.env.registry import get_env_creator

    name = envs.resolve({"base": "PongLiteJax-v0", "frame_stack": k})
    assert name == f"PongLiteJax-v0.stack{k}"
    return get_env_creator(name)({})


def test_frame_stack_keeps_the_newest_k_frames_oldest_first():
    import jax

    env = _stacked()
    base = env.env
    assert env.obs_spec.shape == (84, 84, 4) and env.obs_spec.dtype == np.uint8
    state = env.init(jax.random.PRNGKey(3))
    state, obs = env.reset(state)
    _, first = base.reset(base.init(jax.random.PRNGKey(3)))
    assert obs.shape == (84, 84, 4)
    for c in range(4):  # a reset fills the stack with the first frame
        np.testing.assert_array_equal(obs[..., c : c + 1], first)
    frames = [np.asarray(first)] * 4
    inner = state["inner"]
    for action in (1, 2, 2, 0, 1):
        state, obs, reward, term, trunc = env.step(state, action)
        inner, frame, r2, _, _ = base.step(inner, action)
        frames = frames[1:] + [np.asarray(frame)]
        np.testing.assert_array_equal(obs, np.concatenate(frames, axis=-1))
        assert float(reward) == float(r2)


def test_plain_name_passes_through():
    assert envs.resolve("PongLiteJax-v0") == "PongLiteJax-v0"
    assert envs.resolve({"base": "PongLiteJax-v0", "frame_stack": 1}) == "PongLiteJax-v0"


class _Ring:
    """The part of a device ring ``bulk_fill`` uses, held on the host."""

    def __init__(self, capacity, added, obs_shape):
        self.capacity, self.num_added = capacity, added
        self._meta = {
            "obs": (obs_shape, np.uint8, True),
            "new_obs": (obs_shape, np.uint8, True),
            "actions": ((), np.int32, False),
            "rewards": ((), np.float32, False),
            "dones": ((), np.bool_, False),
            "truncateds": ((), np.bool_, False),
            "t": ((), np.int32, False),
        }
        self.cols = {
            k: np.zeros((capacity,) + tuple(s), d) for k, (s, d, _) in self._meta.items()
        }
        self.priorities = np.zeros(capacity)
        self._size = 0

    def __len__(self):
        return self._size

    def add_device_tree(self, tree, priorities):
        n = len(priorities)
        pos = (self.num_added + np.arange(n)) % self.capacity
        for k, v in tree.items():
            self.cols[k][pos] = np.asarray(v)
        self.priorities[pos] = priorities
        self.num_added += n
        self._size = min(self.capacity, self._size + n)


@pytest.mark.parametrize("added", [0, 24])
def test_bulk_fill_overwrites_every_row_and_knows_the_rows_it_named(added):
    env = _stacked()
    ring = _Ring(128, added, (84, 84, 4))
    want = np.array([[0, 5, 127], [24, 23, 64]])
    spec = {"chunk_envs": 4, "chunk_steps": 8}
    raw, picked = ringfill.bulk_fill(ring, env, 3, 2**31 + 5, spec, want=want)
    assert len(ring) == 128 and ring.num_added == added + 128
    np.testing.assert_array_equal(ring.priorities, raw)
    assert len(np.unique(raw)) == 128 and raw.min() > 0
    for k in ringfill.ROW_COLUMNS:
        np.testing.assert_array_equal(np.asarray(picked[k]), ring.cols[k][want.ravel()])
    # real transitions: a row's new_obs is its obs shifted by one frame
    live = ~(ring.cols["dones"] | ring.cols["truncateds"])
    np.testing.assert_array_equal(
        ring.cols["new_obs"][live][..., :3], ring.cols["obs"][live][..., 1:]
    )
    assert ring.cols["obs"].any() and len(np.unique(ring.cols["actions"])) == 3
    # the same seed makes the same ring; another seed another
    again = _Ring(128, added, (84, 84, 4))
    ringfill.bulk_fill(again, env, 3, 2**31 + 5, spec)
    np.testing.assert_array_equal(again.cols["obs"], ring.cols["obs"])
    other = _Ring(128, added, (84, 84, 4))
    ringfill.bulk_fill(other, env, 3, 2**31 + 6, spec)
    assert (other.cols["actions"] != ring.cols["actions"]).any()
