"""CPU rehearsal of the harness: four virtual CPU devices, no TPU, no
topology call at import. Run with ``python -m pytest perf/tests -q``
(tier-1 collects ``tests/`` only)."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=4"
    ).strip()

import json
import shutil

import pytest

from perf import manifest as manifest_lib


OTHER_FAMILY = os.path.join(os.path.dirname(__file__), "data", "other_family")


def _rewrite(path, fn):
    with open(path) as f:
        data = json.load(f)
    fn(data)
    with open(path, "w") as f:
        json.dump(data, f)


@pytest.fixture()
def tiny_root(tmp_path):
    return make_tiny_root(str(tmp_path))


def make_tiny_root(root):
    """A temporary copy of the benchmark (BENCHMARK.json + perf/) with
    NEW files added beside the committed ones and new entries appended
    to the manifest — no committed file is edited: a throw-away
    configuration ``tiny_dqn``, a traffic mix ``tiny_replay`` and a
    per-layer metric ``tiny.iterations``, sized for a CPU; and a cell
    of ANOTHER FAMILY, ``seq.ppo.mp4`` (PPO over a small decoder whose
    parameters are split over a batch 2 x model 2 mesh), whose
    configuration, reference, FLOP rule, limits, check, traffic mix
    and choice of metrics are the files of ``data/other_family``."""
    shutil.copytree(
        manifest_lib.PERF_DIR,
        os.path.join(root, "perf"),
        ignore=shutil.ignore_patterns("__pycache__", "tests"),
    )
    os.symlink(
        os.path.join(manifest_lib.ROOT, "ray_tpu"),
        os.path.join(root, "ray_tpu"),
    )
    perf = os.path.join(root, "perf")
    shutil.copytree(OTHER_FAMILY, perf, dirs_exist_ok=True)

    def tiny_config(src, dst, edit):
        shutil.copy(
            os.path.join(perf, "configs", src),
            os.path.join(perf, "configs", dst),
        )
        _rewrite(os.path.join(perf, "configs", dst), edit)

    def dqn_edit(c):
        c["algo_config"].update(
            train_batch_size=32,
            num_steps_sampled_before_learning_starts=64,
            replay_device_resident=True,
            replay_device_tree=True,
        )
        c["algo_config"]["replay_buffer_config"]["capacity"] = 256
        c["learner_check"] = {"rows": 128, "batches": 4}

    tiny_config("nature_cnn_dqn_per.json", "tiny_dqn.json", dqn_edit)

    def write(rel, data):
        with open(os.path.join(perf, rel), "w") as f:
            if isinstance(data, str):
                f.write(data)
            else:
                json.dump(data, f)

    write("traffic/tiny_replay.json", {
        "name": "tiny_replay",
        "env": {"base": "PongLiteJax-v0", "frame_stack": 4},
        "algo_config": {"env_backend": "jax", "num_workers": 0,
                        "num_envs_per_worker": 4,
                        "rollout_fragment_length": 8, "superstep": 8},
        "ring_fill": {"chunk_envs": 4, "chunk_steps": 16},
        "warmup": {"first_iterations": 1, "then_iterations": 1},
        "expect": {"updates_per_iteration": 8, "dispatches_per_iteration": 1,
                   "dispatch_label": "superstep[", "trained_per_sampled": 8},
        "checks": ["rollout_rows_pong_lite", "replay_ring_full"],
        "trace_iterations": 2,
    })
    with open(os.path.join(perf, "limits", "seq_ppo_mp.json")) as f:
        lacking = json.load(f)
    del lacking["limits"]["grad_rel_l2"]
    write("limits/seq_ppo_mp_without_grad.json", lacking)
    write("layer_metrics/tiny.iterations.py",
          '"""Iterations in the window."""\n\n\n'
          "def read(ctx):\n    return float(len(ctx.window.walls))\n")

    def add_entries(m):
        m["configs"] += [
            {"name": "tiny_dqn", "source": "test", "reduced": [],
             "file": "perf/configs/tiny_dqn.json", "why": "test"},
            {"name": "seq_ppo_mp", "source": "test", "reduced": [],
             "file": "perf/configs/seq_ppo_mp.json", "why": "test"},
        ]
        m["workloads"] += [
            {"name": "tiny.dqn", "config": "tiny_dqn", "traffic": "tiny_replay",
             "chips": 1, "why": "test"},
            {"name": "tiny.dqn4", "config": "tiny_dqn", "traffic": "tiny_replay",
             "chips": 4, "why": "test"},
            {"name": "seq.ppo.mp4", "config": "seq_ppo_mp",
             "traffic": "host_rollout", "chips": 4, "why": "test"},
        ]
        m["per_layer"].append(
            {"name": "tiny.iterations", "unit": "count", "better": "higher",
             "source": "program_counter", "layer": "entry",
             "moves": "env_steps_per_s",
             "workloads": ["tiny.dqn", "tiny.dqn4"]}
        )

    shutil.copy(
        os.path.join(manifest_lib.ROOT, "BENCHMARK.json"),
        os.path.join(root, "BENCHMARK.json"),
    )
    _rewrite(os.path.join(root, "BENCHMARK.json"), add_entries)
    return root
