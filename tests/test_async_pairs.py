"""The asynchronous pairs of a compiled program
(``ray_tpu/sharding/async_pairs.py``), on a hand-written module in the
v5e compiler's printed form (``tests/data/async_pairs_program.hlo.txt``:
a rollout loop with a prefetched expert weight, a start hoisted across
the back edge, a nested loop with a ``slice`` and a wrapped ``async``
pair, two pairs in the entry computation), and the compile layer's
request for a live program's table, which must move no compile counter.
"""

import json
import os

import pytest

from ray_tpu.sharding import async_pairs as ap

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
LANE = "jit(rollout_superstep)/while/body/closed_call/"
ROLLOUT_LOOP = LANE + "while"
ENV_LOOP = LANE + "rollout/env_step/while"


@pytest.fixture(scope="module")
def text():
    with open(os.path.join(DATA, "async_pairs_program.hlo.txt")) as f:
        return f.read()


@pytest.fixture(scope="module")
def rows(text):
    return {row["name"]: row for row in ap.pairs(text)}


def test_every_done_of_the_module_has_a_row(rows):
    assert sorted(rows) == [
        "async-done.2", "copy-done.0", "copy-done.1", "copy-done.7",
        "copy-done.9", "slice-done.3",
    ]


# name -> (opcode, plain shape, bytes, memory space, start, room, hoisted)
IDENTITY = {
    "copy-done.7": ("copy-done", "bf16[64,64]", 8192, 1, "copy-start.7", 2, False),
    "copy-done.9": ("copy-done", "f32[64]", 256, 1, "copy-start.9", 14, True),
    "slice-done.3": ("slice-done", "f32[2,64]", 512, 1, "slice-start.3", 3, False),
    "async-done.2": ("async-done", "(f32[8,64], f32[8])", 2080, None,
                     "async-start.2", 4, False),
    "copy-done.1": ("copy-done", "f32[64,64]", 16384, 1, "copy-start.1", 7, False),
    # the last iteration's start leaves the loop in its carried tuple:
    # the entry's done has no start in its own computation
    "copy-done.0": ("copy-done", "f32[64]", 256, 1, None, None, False),
}


@pytest.mark.parametrize("name", sorted(IDENTITY))
def test_a_row_names_the_done_its_start_and_its_room(rows, name):
    row = rows[name]
    got = (row["opcode"], row["shape"], row["bytes"], row["space"],
           row["start"], row["room"], row["hoisted"])
    assert got == IDENTITY[name]


# name -> (innermost loop's path, kinds of the call graph upward)
PLACES = {
    "copy-done.7": (ROLLOUT_LOOP, ["while", "entry"]),
    "copy-done.9": (ROLLOUT_LOOP, ["while", "entry"]),
    "slice-done.3": (ENV_LOOP, ["while", "while", "entry"]),
    "async-done.2": (ENV_LOOP, ["while", "while", "entry"]),
    "copy-done.1": ("(entry)", ["entry"]),
    "copy-done.0": ("(entry)", ["entry"]),
}


@pytest.mark.parametrize("name", sorted(PLACES))
def test_a_row_says_where_the_done_sits(rows, name):
    loop, kinds = PLACES[name]
    row = rows[name]
    assert ap.loop_of(row) == loop
    assert [level["kind"] for level in row["under"]] == kinds
    if len(kinds) == 3:  # the nested loop runs under the rollout loop
        assert row["under"][1]["op_name"] == ROLLOUT_LOOP
        assert row["under"][1]["name"] == "while.1"


# name -> the consumers' paths: through a fusion's parameter to the
# instruction inside it, through get-tuple-element and bitcast, a plain
# user, an instruction of the entry computation, none
CONSUMERS = {
    "copy-done.7": [LANE + "rollout/act/moe/experts/convert_element_type"],
    "copy-done.9": [LANE + "rollout/act/head/add"],
    "slice-done.3": [ENV_LOOP + "/body/neg"],
    "async-done.2": [ENV_LOOP + "/body/reduce_sum"],
    "copy-done.1": [LANE + "sgd_nest/while/body/closed_call/learn/optimizer/mul"],
    "copy-done.0": [],
}


@pytest.mark.parametrize("name", sorted(CONSUMERS))
def test_a_row_says_what_the_done_is_for(rows, name):
    assert rows[name]["consumers"] == CONSUMERS[name]


def test_a_fusions_own_path_serves_where_its_inside_names_none(text):
    # the same module with the fused computation's metadata stripped
    bare = text.replace(
        ', metadata={op_name="' + LANE
        + 'rollout/act/moe/experts/convert_element_type" stack_frame_id=12}', ""
    )
    row = {r["name"]: r for r in ap.pairs(bare)}["copy-done.7"]
    assert row["consumers"] == [LANE + "rollout/act/moe/experts/dot_general"]
    assert row["consumer_ops"] == ["fusion.12"]


def test_a_start_that_reads_a_weight_says_which(rows):
    # element 3 of the loop's carried tuple, handed on unchanged, is
    # argument 2 of the program: the final norm's weight
    assert rows["copy-done.9"]["source"] == {
        "parameter": 3, "shape": "f32[64]", "of": "while.1",
        "from": {"entry_parameter": 2, "shape": "f32[64]",
                 "name": "args[0]['final_norm']['weight']"},
    }
    # element 2 is made before the loop by a convert
    source = rows["copy-done.7"]["source"]
    assert (source["parameter"], source["of"]) == (2, "while.1")
    assert source["from"]["opcode"] == "convert"
    assert rows["copy-done.1"]["source"]["entry_parameter"] == 1
    assert rows["slice-done.3"]["source"] == {
        "parameter": 1, "shape": "f32[8,64]", "of": "while.2",
    }


@pytest.mark.parametrize("shape, plain, size", [
    ("f32[2560]{0:T(1024)S(1)}", "f32[2560]", 10240),
    ("bf16[2,768,2560]{2,1,0:T(8,128)(2,1)S(1)}", "bf16[2,768,2560]", 7864320),
    ("(f32[8,64]{1,0:T(8,128)}, f32[8]{0:T(8)})", "(f32[8,64], f32[8])", 2080),
    ("pred[256,32]{1,0:T(8,128)(4,1)}", "pred[256,32]", 8192),
    ("s32[]{:T(128)}", "s32[]", 4),
    ("token[]", "token[]", 0),
])
def test_shapes_as_a_profile_prints_them_and_their_bytes(shape, plain, size):
    assert ap.plain_shape(shape) == plain
    assert ap.shape_bytes(shape) == size


def test_the_table_lists_pairs_by_loop_and_consumer(rows):
    table = ap.format_table(list(rows.values()), top=1)
    lines = table.splitlines()
    assert lines[0].split()[:3] == ["loop", "consumer", "kind"]
    hoisted = next(ln for ln in lines if "rollout/act/head/add" in ln)
    assert hoisted.split()[-5:] == ["copy-done", "1", "256", "14-14", "1"]
    assert "copy-done.1 f32[64,64] 16384 B, room 7, in (entry)" in table
    assert "reads argument 1 args[0]['head']['kernel']" in table


def test_rows_are_plain_json(rows):
    assert json.loads(json.dumps(rows)) == rows


def test_report_cli_prints_a_programs_pairs(capsys):
    from ray_tpu.telemetry import report

    path = os.path.join(DATA, "async_pairs_program.hlo.txt")
    assert report.main(["--pairs", path, "--top", "2"]) == 0
    out = capsys.readouterr().out
    assert "-- jit_rollout_superstep: 6 asynchronous pairs" in out
    assert "rollout/act/head/add" in out


def test_report_cli_takes_the_compile_layers_json(tmp_path, rows, capsys):
    from ray_tpu.telemetry import report

    path = tmp_path / "pairs.json"
    path.write_text(json.dumps({"rollout_superstep[T=256]": list(rows.values())}))
    assert report.main(["--pairs", str(path)]) == 0
    assert "rollout_superstep[T=256]: 6 asynchronous pairs" in capsys.readouterr().out


@pytest.fixture()
def ledger():
    """The device ledger switched by the test, left as it was found."""
    from ray_tpu.telemetry import device as device_ledger

    was_on, was_analyzing = device_ledger.enabled(), device_ledger._analyze
    yield device_ledger
    device_ledger.enable(analyze=was_analyzing)
    if not was_on:
        device_ledger.disable()


def test_a_request_for_a_live_programs_table_moves_no_compile_counter(ledger):
    """A small ``lax.scan`` program on the CPU: the compile layer's
    table parses (pairs or none: the CPU's compiler prefetches
    nothing), and asking for it is no trace, no miss and no second of
    the three phases the set-up metrics add up."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.sharding import compile as compile_lib

    def fn(w, x):
        def body(carry, _):
            with jax.named_scope("rollout/act"):
                carry = jnp.tanh(carry @ w)
            return carry, carry.sum()

        return jax.lax.scan(body, x, None, length=5)

    ledger.enable(analyze=True)
    program = compile_lib.sharded_jit(fn, label="async_pairs_probe[5]")
    program(jnp.ones((16, 16)), jnp.ones((4, 16)))
    assert compile_lib.async_pairs("no_such_family") == {}
    before = compile_lib.compile_stats()
    text = program.compiled_text()
    table = compile_lib.async_pairs("async_pairs_probe")
    after = compile_lib.compile_stats()
    assert text.startswith("HloModule jit_async_pairs_probe")
    assert " while(" in text
    assert list(table) == ["async_pairs_probe[5]"]
    assert table["async_pairs_probe[5]"] == ap.pairs(text)
    for row in table["async_pairs_probe[5]"]:
        assert row["opcode"].endswith("-done") and row["under"]
    assert after["traces"] == before["traces"]
    assert program.traces == 1 and program.calls == 1
    was, now = (s["families"]["async_pairs_probe"] for s in (before, after))
    for key in ("trace_s", "lower_s", "backend_s", "cache_misses", "cache_hits"):
        assert now[key] == was[key], key
    assert now["analysis_s"] >= was["analysis_s"]


def test_a_program_the_ledger_never_analysed_has_no_text(ledger):
    import jax.numpy as jnp

    from ray_tpu.sharding import compile as compile_lib

    ledger.disable()
    program = compile_lib.sharded_jit(lambda x: x + 1, label="never_analysed[1]")
    program(jnp.ones(4))
    assert program.compiled_text() is None
    assert compile_lib.async_pairs("never_analysed") == {}
