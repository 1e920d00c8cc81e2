"""BENCHMARK.json against the contract's limits, and every file it
names found by name."""

import glob
import json
import os
import re

import pytest

from perf import manifest as manifest_lib
from perf.tests.conftest import OTHER_FAMILY

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return manifest_lib.load_manifest()


def test_keys_and_sizes(manifest):
    assert set(manifest) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert manifest["paths"] == ["perf"]
    assert 1 <= manifest["run_seconds"] <= 51
    # a full check with all 24 cells must fit: 2 + 14 x cells runs
    runs = 2 + 14 * 24
    assert runs * (manifest["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    size = os.path.getsize(os.path.join(manifest_lib.ROOT, "BENCHMARK.json"))
    assert size <= 64 * 1024


def test_names_units_and_sources(manifest):
    names = []
    for section in ("end_to_end", "per_layer"):
        for m in manifest[section]:
            assert NAME.match(m["name"]), m["name"]
            assert UNIT.match(m["unit"]), m["unit"]
            assert m["better"] in ("lower", "higher")
            assert m["source"] in SOURCES
            names.append(m["name"])
    assert len(names) == len(set(names))
    for m in manifest["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.1
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
    e2e = {m["name"] for m in manifest["end_to_end"]}
    assert "setup_s" in e2e
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
    for w in manifest["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    cells = [w["name"] for w in manifest["workloads"]]
    assert len(cells) == len(set(cells))
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(cells) // 4)
    for c in manifest["configs"]:
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
        assert any(w["config"] == c["name"] for w in manifest["workloads"])


def test_every_cell_finds_its_files(manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    for section in ("end_to_end", "per_layer"):
        for m in manifest[section]:
            assert set(m.get("workloads", [])) <= cells, m["name"]
    for w in manifest["workloads"]:
        cell = manifest_lib.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert sorted(cell.config["reduced"]) == sorted(
            cell.config_entry["reduced"]
        )
        assert set(cell.config["reduced"]) <= set(cell.config["reduced_why"])
        assert cell.traffic["name"] == w["traffic"]
        ref = cell.reference()
        for fn in ("init_params", "to_policy_tree", "from_policy_tree",
                   "make_batch", "loss"):
            assert callable(getattr(ref, fn))
        assert callable(cell.flop_rule())
        listed = cell.config["checks"] + cell.traffic.get("checks", [])
        staged = [n for stage in manifest_lib.STAGES for n, _ in cell.checks(stage)]
        assert sorted(staged) == sorted(listed) and listed
        for stage in manifest_lib.STAGES:
            for _, check in cell.checks(stage):
                assert callable(check.run)
                for limit in check.LIMITS:
                    assert limit in cell.limits
        assert os.path.basename(cell.limits.path) == cell.config["limits"] + ".json"
        assert cell.param_layout == "replicated" or cell.param_layout["rules"]
        spec = cell.experiment_spec(7)
        assert spec["config"]["seed"] == 7 and spec["run"] and spec["env"]
        e2e = [m["name"] for m in cell.end_to_end]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert callable(cell.reader(m["name"]))


def _limits_files():
    return sorted(
        glob.glob(os.path.join(manifest_lib.PERF_DIR, "limits", "*.json"))
        + glob.glob(os.path.join(OTHER_FAMILY, "limits", "*.json"))
    )


@pytest.mark.parametrize("path", _limits_files(), ids=os.path.basename)
def test_every_limit_carries_the_readings_it_was_set_from(path):
    """No configuration commits a limit without both readings: what
    sound runs gave at most and what each control gave at least, with
    the limit between them wherever the controls separate."""
    limits = manifest_lib.Limits(path)
    assert limits.entries
    for name, e in limits.entries.items():
        assert isinstance(e["read"], str) and len(e["read"]) > 20, name
        assert isinstance(e["separates"], bool), name
        assert e["sound_max"] <= e["limit"], name
        if e["separates"]:
            assert e["control_min"], name
            assert e["sound_max"] < e["limit"] < min(e["control_min"].values()), name


def test_the_cnn_limits_are_the_numbers_they_were_in_code():
    """``LIMITS``, ``LOSS_FLOOR`` and ``SUPERSTEP_LOSS_FLOOR`` of
    perf/correct.py as PR 24 left them, moved into the file
    unchanged."""
    limits = manifest_lib.Limits(
        os.path.join(manifest_lib.PERF_DIR, "limits", "nature_cnn_dqn_per.json")
    )
    assert {n: limits.limit(n) for n in limits.entries} == {
        "grad_rel_l2": 1.6e-2,
        "grad_leaf_rel_l2_max": 2.5e-1,
        "loss_rel": 6.0e-3,
        "tree_draw_mismatches": 0,
        "tree_weight_rel_max": 1.0e-5,
        "superstep_priority_rel_l2": 6.0e-3,
        "superstep_update_rel_l2": 3.0e-1,
        "superstep_rows_refreshed_wrongly": 0,
        "superstep_loss_rel": 6.0e-3,
        "ring_leaves_rel_max": 1.0e-12,
    }
    assert limits.floor("loss_rel") == 0.05
    assert limits.floor("superstep_loss_rel") == 1e-3
    cell = manifest_lib.load_cell("dqn_per.fused.1chip")
    assert cell.learner_check_shape == (512, 4)
    assert cell.control_precisions == ("int8", "fp8")


def test_a_name_that_finds_no_file_is_an_error_at_load(tiny_root):
    def broken(kind, key, value):
        path = os.path.join(tiny_root, "perf", kind)
        with open(path) as f:
            data = json.load(f)
        saved = json.dumps(data)
        data[key] = value
        with open(path, "w") as f:
            json.dump(data, f)
        try:
            manifest_lib.load_cell("tiny.dqn", tiny_root)
        finally:
            with open(path, "w") as f:
                f.write(saved)

    with pytest.raises(FileNotFoundError, match="flop rule 'mamba'"):
        broken("configs/tiny_dqn.json", "flops_family", "mamba")
    with pytest.raises(FileNotFoundError, match="check 'router_sets'"):
        broken("configs/tiny_dqn.json", "checks", ["router_sets"])
    with pytest.raises(FileNotFoundError):
        broken("configs/tiny_dqn.json", "limits", "no_such_limits")
    # a limit one of its checks asks for, missing from the limits file
    with pytest.raises(KeyError, match="no limit 'grad_rel_l2'"):
        broken("configs/tiny_dqn.json", "limits", "seq_ppo_mp_without_grad")
    manifest_lib.load_cell("tiny.dqn", tiny_root)


def test_a_cell_takes_restricted_metrics_through_its_own_file(tiny_root):
    cell = manifest_lib.load_cell("seq.ppo.mp4", tiny_root)
    assert cell.chosen_metrics == ("tiny.iterations", "rollout.host_idle_ms_per_iter")
    assert "iter_p95_ms" in [m["name"] for m in cell.end_to_end]
    other = manifest_lib.load_cell("tiny.dqn4", tiny_root)
    assert other.chosen_metrics == ()
    assert "rollout.host_idle_ms_per_iter" not in [m["name"] for m in other.per_layer]
    with open(os.path.join(tiny_root, "perf", "cells", "tiny.dqn4.json"), "w") as f:
        json.dump({"metrics": ["no.such.metric"]}, f)
    with pytest.raises(KeyError, match="no.such.metric"):
        manifest_lib.load_cell("tiny.dqn4", tiny_root)


FAMILY_WORDS = re.compile(
    r"nature_cnn|dqn|ppo|pong|cartpole|transformer|replay|superstep_priority"
    r"|conv_filters|flops_family\s*==",
    re.I,
)


@pytest.mark.parametrize("module", ["run.py", "correct.py", "manifest.py", "flops.py"])
def test_the_harness_names_no_family_env_or_cell_and_no_limit(manifest, module):
    """Outside comments and docstrings, the harness's code holds no
    cell or configuration name; run.py, manifest.py and correct.py's
    dispatch hold no family or env word and no limit as a number."""
    import ast

    src = open(os.path.join(manifest_lib.PERF_DIR, module)).read()
    for w in manifest["workloads"]:
        assert w["name"] not in src
    for c in manifest["configs"]:
        assert c["name"] not in src
    tree = ast.parse(src)
    assert not any(
        isinstance(n, ast.Assign)
        and any(getattr(t, "id", "") in ("LIMITS", "LOSS_FLOOR") for t in n.targets)
        for n in ast.walk(tree)
    )
    if module in ("run.py", "manifest.py"):
        strings = [
            n.value for n in ast.walk(tree)
            if isinstance(n, ast.Constant) and isinstance(n.value, str)
            and "\n" not in n.value and len(n.value) < 60
        ]
        assert not [x for x in strings if FAMILY_WORDS.search(x)], module
    if module == "flops.py":
        assert "flops_family" not in {
            n.value for n in ast.walk(tree) if isinstance(n, ast.Constant)
        }


def test_perf_imports_nothing_from_the_old_benchmarks():
    for base, _, files in os.walk(manifest_lib.PERF_DIR):
        for f in files:
            if f.endswith(".py") and "tests" not in base:
                src = open(os.path.join(base, f)).read()
                assert not re.search(
                    r"^\s*(from|import)\s+(bench|bench_e2e|benchmarks)\b",
                    src, re.M,
                ), f
