"""The five per-layer metrics that move ``setup_s`` (PR 36) read the
program's own tables: a number on the CPU rehearsal cell, ``None``
where the program keeps no such table (the parent commit) or the table
is empty."""

import pytest

from perf import manifest as manifest_lib
from perf import run as run_lib

READERS = (
    "compile.trace_lower_s",
    "compile.program_backend_s",
    "compile.cache_misses",
    "entry.build_s",
    "entry.model_init_s",
)


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """The tiny DQN cell run once; what each reader says afterwards."""
    from perf.tests.conftest import make_tiny_root

    root = make_tiny_root(str(tmp_path_factory.mktemp("setup_readers")))
    cell = manifest_lib.load_cell("tiny.dqn", root)
    out = run_lib.run_cell(cell, 2**31 + 99, 0.5, False, require_tpu=False)
    ctx = run_lib.Context(cell, None, None, cell.chips, "cpu", 2)
    ctx.setup = {
        "setup_s": out["metrics"]["setup_s"]["value"],
        "compile_backend_s": 1e9,
    }
    return cell, ctx, {name: cell.reader(name)(ctx) for name in READERS}


def test_the_manifest_gives_every_cell_the_five_metrics():
    for workload in manifest_lib.load_manifest()["workloads"]:
        cell = manifest_lib.load_cell(workload["name"])
        entries = {m["name"]: m for m in cell.per_layer}
        for name in READERS:
            assert entries[name]["moves"] == "setup_s", (cell.name, name)
            assert entries[name]["better"] == "lower"
            assert "workloads" not in entries[name]


@pytest.mark.parametrize("name", READERS)
def test_a_reader_gives_a_number_on_the_rehearsal_cell(rehearsed, name):
    _, ctx, values = rehearsed
    value = values[name]
    assert isinstance(value, float) and value >= 0.0
    if name == "compile.cache_misses":
        assert value == 0.0  # no persistent cache on the CPU backend
    else:
        assert 0.0 < value < ctx.setup["setup_s"]


def test_the_parts_lie_inside_their_wholes(rehearsed):
    _, ctx, values = rehearsed
    assert values["entry.model_init_s"] < values["entry.build_s"]
    assert (
        values["compile.program_backend_s"] <= ctx.setup["compile_backend_s"]
    )


@pytest.mark.parametrize("name", READERS)
def test_a_reader_gives_none_where_the_table_is_empty(
    rehearsed, name, monkeypatch
):
    from ray_tpu.sharding import compile as compile_lib
    from ray_tpu.util import tracing

    cell, ctx, _ = rehearsed
    monkeypatch.setattr(compile_lib, "_FAMILIES", {})
    monkeypatch.setattr(tracing, "_phases", [])
    assert cell.reader(name)(ctx) is None


@pytest.mark.parametrize("name", READERS)
def test_a_reader_gives_none_on_a_program_without_the_table(
    rehearsed, name, monkeypatch
):
    """The parent commit: ``compile_stats()`` has no ``families`` and
    ``tracing`` no ``phase_seconds``; a reader says nothing and does
    not raise."""
    from ray_tpu.sharding import compile as compile_lib
    from ray_tpu.util import tracing

    cell, ctx, _ = rehearsed
    stats = compile_lib.compile_stats

    def parents_stats():
        out = stats()
        out.pop("families")
        return out

    monkeypatch.setattr(compile_lib, "compile_stats", parents_stats)
    monkeypatch.delattr(tracing, "phase_seconds")
    assert cell.reader(name)(ctx) is None
