"""The whole run rehearsed on the CPU at a tiny size, on throw-away
cells that exist only as NEW files and entries in a temporary copy:
adding a cell, a configuration or a per-layer metric needs no edit."""

import json
import os
import subprocess
import sys

import pytest

from perf import correct as correct_lib
from perf import manifest as manifest_lib
from perf import run as run_lib


@pytest.mark.parametrize("cell_name", ["tiny.dqn", "tiny.dqn4"])
def test_throwaway_cell_runs_end_to_end(tiny_root, cell_name):
    """On one device and on four virtual ones (the rehearsal of a
    four-chip cell's comparison: params replicated, the ring and the
    env carry split over the devices, and the sharded programs held to
    the single-device reference)."""
    import jax

    cell = manifest_lib.load_cell(cell_name, tiny_root)
    assert len(jax.devices()) >= cell.chips
    out = run_lib.run_cell(cell, 2**31 + 99, 1.0, False, require_tpu=False)
    assert set(out) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert out["failed"] == 0 and out["attempted"] >= 1
    wanted = {m["name"] for m in cell.end_to_end}
    assert set(out["metrics"]) == wanted and "setup_s" in wanted
    assert out["metrics"]["env_steps_per_s"]["value"] > 0
    assert out["device"]["platform"] == "cpu"
    by_name = {r["check"]: r for r in out["checks"]}
    for name in ("iteration_adds_up", "grad_rel_l2", "rollout_rows_are_transitions",
                 "replay_ring_full_on_device_before_window",
                 "env_carry_split_over_every_chip", "superstep_priority_rel_l2"):
        assert by_name[name]["ok"], by_name[name]
    assert f"on {cell.chips} shard(s)" in by_name["grad_rel_l2"]["note"]
    assert by_name["tree_draw_mismatches"]["value"] == 0
    assert by_name["superstep_rows_refreshed_wrongly"]["value"] == 0
    assert by_name["replay_ring_rows_filled"]["value"] == 256
    assert out["correct"], [r for r in out["checks"] if not r["ok"]]


def test_a_cell_of_another_family_comes_as_files_alone(tiny_root):
    """PPO over a small decoder whose parameters are SPLIT over a
    batch 2 x model 2 mesh: its reference, FLOP rule, limits, check,
    layout, traffic mix and choice of metrics are new files only
    (``data/other_family``), and no file of the harness knows it."""
    cell = manifest_lib.load_cell("seq.ppo.mp4", tiny_root)
    out = run_lib.run_cell(cell, 2**31 + 77, 1.0, False, require_tpu=False)
    by_name = {r["check"]: r for r in out["checks"]}
    assert out["correct"], [r for r in out["checks"] if not r["ok"]]
    assert out["failed"] == 0 and out["attempted"] >= 1
    # the layout the configuration states, not replication
    assert "params_replicated_on_every_chip" not in by_name
    assert by_name["mesh_axes_as_stated"]["value"] == {"batch": 2, "model": 2}
    assert by_name["param_leaves_not_laid_out_as_stated"]["value"] == 0
    assert by_name["params_split_over_chips"]["ok"]
    assert 0.25 < by_name["param_share_on_fullest_chip"]["value"] < 0.6
    # limits from its own file, on its own shape of batch
    assert by_name["grad_rel_l2"]["limit"] == cell.limit("grad_rel_l2") == 3e-5
    assert "2 minibatches of 64 rows on 2 shard(s)" in by_name["grad_rel_l2"]["note"]
    # a check module of its own
    assert by_name["action_prob_abs_max"]["ok"]
    assert by_name["reference_attention_is_a_causal_softmax"]["ok"]
    assert by_name["iteration_adds_up"]["ok"]
    # iter_p95_ms belongs to every cell; two restricted per-layer
    # metrics come through the cell's own ``metrics`` list
    assert set(out["metrics"]) == {"env_steps_per_s", "iter_p95_ms", "setup_s"}
    assert out["metrics"]["iter_p95_ms"]["value"] > 0
    names = {m["name"] for m in cell.per_layer}
    assert {"tiny.iterations", "rollout.host_idle_ms_per_iter"} <= names
    assert "replay.h2d_bytes_per_update" not in names
    win = run_lib.Window()
    win.walls, win.seconds = [0.1, 0.2], 2.0
    win.before = {"sampled": 0, "trained": 0}
    win.after = {"sampled": 256, "trained": 256}
    ctx = run_lib.Context(cell, None, win, cell.chips, "TPU v5 lite", 2)
    assert cell.reader("tiny.iterations")(ctx) == 2.0
    assert cell.reader("rollout.host_idle_ms_per_iter")(ctx) is None  # no trace
    # learner.mfu_pct from the family's own FLOP rule
    per_step = cell.flop_rule()(cell.config, 2)
    s, d, ff = 4, 64, 128
    assert per_step == 3 * 2 * 2.0 * (
        s * d + 2 * (4 * s * d * d + 2 * 10 * d + 2 * s * d * ff) + d * 3
    )
    assert cell.reader("learner.mfu_pct")(ctx) == pytest.approx(
        100.0 * per_step * 128.0 / (4 * 197e12)
    )


def test_per_layer_metric_added_as_a_file_is_read(tiny_root):
    cell = manifest_lib.load_cell("tiny.dqn", tiny_root)
    names = [m["name"] for m in cell.per_layer]
    assert "tiny.iterations" in names
    win = run_lib.Window()
    win.walls = [0.1, 0.2, 0.3]
    ctx = run_lib.Context(cell, None, win, 1, "cpu", 3)
    assert cell.reader("tiny.iterations")(ctx) == 3.0
    # a trace reader that finds no trace returns nothing
    assert cell.reader("device.idle_pct")(ctx) is None
    # measured memory and the compiler's scratch estimate stay apart
    ctx.memory_peak_bytes, ctx.program_temp_bytes = 8 * 10**9, {"a": 3 * 10**9}
    assert cell.reader("device.peak_hbm_gb")(ctx) == 8.0
    assert cell.reader("device.program_scratch_gb")(ctx) == 3.0


def test_device_metric_is_never_made_up_on_a_cpu(tiny_root):
    cell = manifest_lib.load_cell("tiny.dqn", tiny_root)
    win = run_lib.Window()
    win.before = {"sampled": 0, "trained": 0}
    win.after = {"sampled": 64, "trained": 512}
    win.seconds = 1.0
    ctx = run_lib.Context(cell, None, win, 1, "cpu", 3)
    assert ctx.env_steps() == 64
    with pytest.raises(KeyError):
        cell.reader("learner.mfu_pct")(ctx)


def test_a_gradient_summed_over_shards_is_not_correct():
    """The failure the sharded comparison exists for: a gradient N
    times too large reads N - 1."""
    import numpy as np

    g = {"a": {"kernel": np.arange(6.0).reshape(2, 3), "bias": np.ones(3)}}
    summed = {"a": {k: 4.0 * v for k, v in g["a"].items()}}
    d = correct_lib.compare_grads(summed, g)
    assert abs(d["grad_rel_l2"] - 3.0) < 1e-12
    cell = manifest_lib.load_cell(manifest_lib.load_manifest()["workloads"][0]["name"])
    assert d["grad_rel_l2"] > cell.limit("grad_rel_l2")


def test_runner_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    name = manifest_lib.load_manifest()["workloads"][0]["name"]
    proc = subprocess.run(
        [sys.executable, "-m", "perf.run", "--workload", name,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=manifest_lib.ROOT, env=env, capture_output=True, text=True,
        timeout=300,
    )
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    last = (proc.stdout.strip().splitlines() or [""])[-1]
    with pytest.raises(ValueError):
        json.loads(last or "not json")


def test_runner_exits_nonzero_without_the_program(tmp_path):
    """Only BENCHMARK.json and the files under ``paths``: no result."""
    import shutil

    root = str(tmp_path)
    shutil.copy(os.path.join(manifest_lib.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(manifest_lib.PERF_DIR, os.path.join(root, "perf"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    name = manifest_lib.load_manifest()["workloads"][0]["name"]
    proc = subprocess.run(
        [sys.executable, "-m", "perf.run", "--workload", name,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=""),
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert "{" not in (proc.stdout.strip().splitlines() or [""])[-1]
