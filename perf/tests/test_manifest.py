"""BENCHMARK.json against the contract's limits, and every file it
names found by name."""

import os
import re

import pytest

from perf import manifest as manifest_lib

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
        spec = cell.experiment_spec(7)
        assert spec["config"]["seed"] == 7 and spec["run"] and spec["env"]
        e2e = [m["name"] for m in cell.end_to_end]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert callable(cell.reader(m["name"]))


def test_run_py_has_no_branch_on_a_cell_name(manifest):
    src = open(os.path.join(manifest_lib.PERF_DIR, "run.py")).read()
    for w in manifest["workloads"]:
        assert w["name"] not in src
    for c in manifest["configs"]:
        assert c["name"] not in src


def test_perf_imports_nothing_from_the_old_benchmarks():
    for base, _, files in os.walk(manifest_lib.PERF_DIR):
        for f in files:
            if f.endswith(".py") and "tests" not in base:
                src = open(os.path.join(base, f)).read()
                assert not re.search(
                    r"^\s*(from|import)\s+(bench|bench_e2e|benchmarks)\b",
                    src, re.M,
                ), f
