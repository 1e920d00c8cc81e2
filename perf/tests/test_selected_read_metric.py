"""``index.selected_read_device_ms_per_update`` (PR 66): which leaf
operations of a trace it counts, and that it says nothing, without an
error, where there is nothing to read."""

import types

import pytest

from perf import manifest as manifest_lib
from perf import run as run_lib

CELL = "keye2_ppo.fused_tokens.1chip"
NAME = "index.selected_read_device_ms_per_update"
LEARN = "jit(rollout_superstep)/sgd_nest/while/body/learn/loss_grad/"


def _ctx(cell, ops, updates=2.0):
    """A run's context whose traced span holds the leaf operations
    ``ops`` (``[tf_op, start ns, duration ns]``) over ``updates``."""
    rep = types.SimpleNamespace(
        op_scopes=ops, trace=types.SimpleNamespace(bounds=None), updates=updates)
    ctx = run_lib.Context(cell, None, None, 1, "cpu", 64)
    ctx.trace = types.SimpleNamespace(_program_report=rep)
    ctx.traced = object()
    return ctx


def test_the_manifest_gives_the_metric_to_the_index_cell_alone():
    cell = manifest_lib.load_cell(CELL)
    (entry,) = [m for m in cell.per_layer if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "ms", "better": "lower", "source": "device_trace",
        "layer": "model", "moves": "env_steps_per_s", "workloads": [CELL]}
    assert cell.manifest["per_layer"][-1] == entry  # at the list's end
    other = manifest_lib.load_cell("smallthinker_ppo.fused_tokens.1chip")
    assert NAME not in {m["name"] for m in other.per_layer}


@pytest.mark.parametrize("lowering", ["text", "kernel"])
def test_the_reader_counts_scores_and_out_in_both_spellings(lowering):
    """The text's tiles and the kernel pair's custom calls stand under
    the same scopes: the first forward pass under ``jvp(...)`` around
    the whole scope or around its outer part, the recomputation and the
    backward pass under ``transpose(jvp(...))/.../learn/attn/scores``;
    the index, the projections and the scatter are not the read's."""
    leaf = {"text": "dot_general", "kernel": "jit(_fragment_fwd)/"
            "fragment_attention_fwd/pallas_call"}[lowering]
    ops = [
        [LEARN + "jvp(learn/attn/scores)/" + leaf, 0, 4000],
        [LEARN + "jvp(learn/attn)/scores/" + leaf, 4000, 500],
        [LEARN + "transpose(jvp())/checkpoint/rematted_computation/"
         "learn/attn/scores/" + leaf, 5000, 4000],
        [LEARN + "transpose(jvp())/checkpoint/learn/attn/scores/" + leaf, 9000, 8000],
        [LEARN + "jvp(learn/attn/out)/dot_general", 20000, 300],
        [LEARN + "jvp(learn/attn)/out/dot_general", 20300, 100],
        [LEARN + "transpose(jvp())/learn/attn/out/dot_general", 21000, 600],
        # none of these
        [LEARN + "learn/attn/index/scores/dot_general", 30000, 70000],
        [LEARN + "learn/attn/index/topk/while", 100000, 9000],
        [LEARN + "jvp(learn/attn)/dot_general", 110000, 900],
        [LEARN + "learn/attn/scatter/scatter", 111000, 50],
        [LEARN + "learn/moe/experts/dot_general", 112000, 800],
        ["jit(rollout_superstep)/while/body/rollout/act/attn/scores/dot_general",
         113000, 700],
    ]
    cell = manifest_lib.load_cell(CELL)
    read = cell.reader(NAME)
    assert read(_ctx(cell, ops)) == pytest.approx(17500 / 1e9 * 1e3 / 2.0)
    # a program with none of the scopes: nothing, not zero
    assert read(_ctx(cell, ops[7:])) is None


def test_the_reader_says_nothing_where_there_is_nothing_to_read():
    cell = manifest_lib.load_cell(CELL)
    read = cell.reader(NAME)
    # no trace; a trace with no operations; no update in the span
    assert read(run_lib.Context(cell, None, None, 1, "cpu", 64)) is None
    assert read(_ctx(cell, None)) is None
    ops = [[LEARN + "jvp(learn/attn/scores)/dot_general", 0, 4000]]
    assert read(_ctx(cell, ops, updates=0)) is None
    # a configuration without an index
    other = manifest_lib.load_cell("smallthinker_ppo.fused_tokens.1chip")
    assert read(_ctx(other, ops)) is None
