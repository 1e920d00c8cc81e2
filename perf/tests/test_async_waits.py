"""``perf/async_waits.py`` on a hand-made trace with known answers: two
programs' loops (the rollout lane's ``while``, which names no scope
itself, an env loop inside it, a superstep loop of mixed content),
scoped and unscoped leaves, start/done pairs, and the program's table
for some of them; the four readers of ``perf/layer_metrics`` on it;
and the traces that must give 0 or nothing."""

import json
import os

import pytest

from perf import async_waits as aw
from perf import manifest as manifest_lib
from perf import program_trace as pt
from perf import run as run_lib
from perf import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
US = 1_000
LANE = "jit(rollout_superstep)/while/body/closed_call/"
LOOP = LANE + "while"
STEP = LOOP + "/body/closed_call/"
ENV_LOOP = STEP + "rollout/env_step/while"
K_LOOP = "jit(superstep)/while"
METRICS = (
    "rollout.exposed_wait_device_ms_per_step",
    "learner.exposed_wait_device_ms_per_update",
    "device.exposed_wait_device_ms_per_iter",
    "device.unplaced_device_ms_per_iter",
)
SEQUENCE_CELLS = (
    "qwen3next_ppo.fused_tokens.1chip", "xing4_ppo.fused_tokens.1chip",
    "granite4h_ppo.fused_tokens.1chip", "smallthinker_ppo.fused_tokens.1chip",
    "laguna_ppo.fused_tokens.1chip", "sdar_ppo.fused_blocks.1chip",
    "nemotron3nano_ppo.fused_tokens.1chip",
)


def _ops(pairs=True):
    """One iteration of 2,400 us. ``rollout_superstep`` runs 0..1,700:
    the lane's loop 0..1,000 (two steps, each with a prefetch of the
    head's norm that the chip waits 119 and 81 us for, and an env loop
    with an unnamed slice wait of 10), then the entry computation: a
    whole-cache copy, an expert weight's slice the learn half waits
    40 us for, a 16-byte copy nobody is known to consume. ``superstep``
    runs 1,800..2,300: one loop of replay and learn work with a wait of
    30 us inside. A ``while`` event carries no ``tf_op``, as on the
    chip. [tf_op, start_ns, duration_ns, name]."""
    step = [
        [STEP + "rollout/act/attn/dot_general:", 10, 100, "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)"],
        [LOOP + ":", 110, 5, "%copy-start.294 = (f32[2560]{0}, f32[2560]{0}, u32[]) copy-start(f32[2560]{0} %w)"],
        [STEP + "rollout/act/moe/experts/dot_general:", 115, 100, "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %q)"],
        [LOOP + ":", 215, 119, "%copy-done.294 = f32[2560]{0} copy-done((f32[2560]{0}, f32[2560]{0}, u32[]) %copy-start.294)"],
        [STEP + "rollout/act/head/add:", 334, 50, "%fusion.3 = f32[8]{0} fusion(f32[8]{0} %r)"],
        ["", 390, 100, "%while.2 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t)"],
        [ENV_LOOP + "/body/add:", 395, 60, "%fusion.4 = f32[8]{0} fusion(f32[8]{0} %s)"],
        ["", 460, 10, "%slice-done.3 = f32[2,64]{1,0} slice-done(((f32[8,64]{1,0}), f32[2,64]{1,0}, s32[]) %slice-start.3)"],
    ]
    ops = [["", 0, 1000, "%while.859 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %tuple.1)"]]
    for at, wait in ((0, 119), (500, 81)):
        for tf_op, s, d, name in step:
            d = wait if "copy-done.294" in name else d
            ops.append([tf_op, at + s, d, name])
    ops += [
        ["", 1100, 50, "%copy.9 = bf16[32,4096,512]{2,1,0} copy(bf16[32,4096,512]{2,1,0} %c)"],
        ["", 1150, 2, "%slice-start.5 = ((bf16[8,768,2560]{2,1,0}), bf16[2,768,2560]{2,1,0}, s32[]) slice-start(bf16[8,768,2560]{2,1,0} %e)"],
        [LANE + "sgd_nest/while/body/closed_call/learn/loss_grad/mul:", 1160, 300, "%fusion.7 = f32[8]{0} fusion(f32[8]{0} %g)"],
        ["", 1460, 40, "%slice-done.5 = bf16[2,768,2560]{2,1,0} slice-done(((bf16[8,768,2560]{2,1,0}), bf16[2,768,2560]{2,1,0}, s32[]) %slice-start.5)"],
        ["", 1500, 10, "%copy-done.77 = f32[4]{0} copy-done((f32[4]{0}, f32[4]{0}, u32[]) %copy-start.77)"],
        ["", 1800, 500, "%while.1 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %tuple.9)"],
        [K_LOOP + "/body/replay/gather/gather:", 1810, 150, "%fusion.8 = f32[8]{0} fusion(f32[8]{0} %h)"],
        [K_LOOP + ":", 1960, 30, "%copy-done.3 = f32[512]{0} copy-done((f32[512]{0}, f32[512]{0}, u32[]) %copy-start.3)"],
        [K_LOOP + "/body/learn/loss_grad/dot_general:", 2000, 250, "%fusion.9 = f32[8]{0} fusion(f32[8]{0} %i)"],
    ]
    if not pairs:
        ops = [op for op in ops if "-done" not in op[3] and "-start" not in op[3]]
    return [[tf_op, s * US, d * US, tr.short_op_name(name)]
            for tf_op, s, d, name in ops]


def _trace(ops):
    plain = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_rollout_superstep(7)", 0, 1700 * US],
                ["jit_superstep(8)", 1800 * US, 500 * US],
            ]},
            {"name": "XLA Ops", "events": [[op[3], op[1], op[2]] for op in ops]},
        ]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [
            ["perf:train", 0, 2400 * US],
            ["rollout:device", 0, 2400 * US],
        ]}]},
    ]}
    return tr.Trace(plain, 1, (0, 2400 * US))


UNDER_LANE = [{"kind": "while", "name": "while.859", "op_name": LOOP},
              {"kind": "entry", "name": "main.7", "op_name": ""}]
TABLES = {
    "rollout_superstep": {
        "copy-done.294": {
            "name": "copy-done.294", "start": "copy-start.294", "bytes": 10240,
            "room": 2, "hoisted": False, "under": UNDER_LANE,
            "consumers": [STEP + "rollout/act/head/add"],
            "source": {"parameter": 31, "shape": "f32[2560]", "of": "while.859"},
        },
        "slice-done.3": {
            "name": "slice-done.3", "start": "slice-start.3", "bytes": 512,
            "room": 3, "hoisted": False, "consumers": [],
            "under": [{"kind": "while", "name": "while.2", "op_name": ENV_LOOP}]
            + UNDER_LANE,
        },
        "slice-done.5": {
            "name": "slice-done.5", "start": "slice-start.5",
            "bytes": 2 * 768 * 2560 * 2, "room": 30, "hoisted": False,
            "consumers": [LANE + "sgd_nest/while/body/closed_call/learn/"
                          "loss_grad/jvp(learn/moe/experts)/convert_element_type"],
        },
        "copy-done.77": {
            "name": "copy-done.77", "start": "copy-start.77", "bytes": 16,
            "room": 1, "hoisted": False, "consumers": [],
        },
    },
    "superstep": {
        "copy-done.3": {
            "name": "copy-done.3", "start": "copy-start.3", "bytes": 2048,
            "room": 12, "hoisted": False,
            "consumers": [K_LOOP + "/body/learn/loss_grad/dot_general"],
        },
    },
}


def test_nest_keeps_the_loops_around_every_leaf():
    ops = _ops()
    leaves, containers = aw.nest(ops, (0, 2400 * US))
    assert sorted(aw.instruction_of(op[3]) for op in containers.values()) == [
        "while.1", "while.2", "while.2", "while.859",
    ]
    by_name = {}
    for leaf in leaves:
        by_name.setdefault(aw.instruction_of(leaf.name), leaf)
    path = [aw.instruction_of(containers[i][3])
            for i in by_name["slice-done.3"].loops]
    assert path == ["while.859", "while.2"]
    assert [aw.instruction_of(containers[i][3])
            for i in by_name["copy-done.294"].loops] == ["while.859"]
    assert by_name["copy.9"].loops == ()
    # the leaves are Trace._ops': the same operations, the same time
    plain_leaves = pt._leaf_ops(ops, (0, 2400 * US))
    assert sum(d for _, d in plain_leaves) == sum(leaf.ns for leaf in leaves)
    assert len(plain_leaves) == len(leaves)


def test_nest_clips_to_the_span():
    leaves, _ = aw.nest(_ops(), (0, 250 * US))
    done = next(x for x in leaves if "copy-done.294" in x.name)
    assert done.ns == 35 * US  # 215..334 cut at 250


def test_a_loop_takes_the_layer_of_its_path_or_of_what_it_holds():
    ops = _ops()
    leaves, containers = aw.nest(ops, None)

    def named(layers):
        return {(aw.instruction_of(containers[i][3]), layer)
                for i, layer in layers.items()}

    # by content alone: what each loop's own operations are scoped as
    assert named(aw.loop_layers(leaves, containers)) == {
        ("while.859", "rollout"), ("while.2", "rollout"), ("while.1", "")}
    # a path for the env loops (the table's) names them whatever they hold
    paths = {i: LANE + "learn/loss_grad/while" for i, op in containers.items()
             if "while.2" in op[3]}
    assert named(aw.loop_layers(leaves, containers, paths)) == {
        ("while.859", "rollout"), ("while.2", "learn"), ("while.1", "")}


def test_a_loop_has_the_tables_path_for_its_name():
    ops = _ops()
    w = aw.Waits(_trace(ops), ops, TABLES)
    by_name = {aw.instruction_of(x.name): x for x in w.leaves}
    assert w.loop_path(by_name["copy-done.294"]) == LOOP
    assert w.loop_path(by_name["slice-done.3"]) == ENV_LOOP
    assert w.loop_path(by_name["copy-done.3"]) == "%while.1"  # no row names it
    assert w.loop_path(by_name["copy.9"]) == ""
    bare = aw.Waits(_trace(ops), ops)
    by_name = {aw.instruction_of(x.name): x for x in bare.leaves}
    assert bare.loop_path(by_name["copy-done.294"]) == "%while.859"


@pytest.mark.parametrize("path, layer", [
    (STEP + "rollout/act/head/add:", "rollout"),
    (LANE + "sgd_nest/while/body/closed_call/learn/loss_grad/mul", "learn"),
    (LANE + "sgd_nest/while", "learn"),
    (K_LOOP + "/body/replay/gather/gather", "replay"),
    ("jit(rollout_superstep)/while/body/closed_call/gae/mul", "gae"),
    (LOOP + ":", ""),
    ("", ""),
])
def test_layer_of_a_path(path, layer):
    assert aw.layer_of(path) == layer


def test_without_a_table_the_enclosing_loop_alone_places_a_wait():
    ops = _ops()
    w = aw.Waits(_trace(ops), ops)
    assert w.exposed_ns("rollout") == (119 + 81 + 10 + 10) * US
    assert w.exposed_ns("learn") == 0
    assert w.exposed_ns() == (119 + 81 + 20 + 40 + 10 + 30) * US
    # starts, the whole-cache copy and the three dones outside a loop
    # with a layer
    assert w.unplaced_ns() == (50 + 2 + 40 + 10 + 30) * US
    assert w.unscoped_ns() == w.unplaced_ns() + (2 * 5 + 200 + 20) * US
    assert all(rec["hidden_ns"] is None for rec in w.pairs())


def test_the_table_places_a_wait_by_what_consumes_it():
    ops = _ops()
    w = aw.Waits(_trace(ops), ops, TABLES)
    assert w.exposed_ns("rollout") == 220 * US
    assert w.exposed_ns("learn") == (40 + 30) * US
    assert w.exposed_ns() == 300 * US
    assert w.exposed_ns() >= w.exposed_ns("rollout") + w.exposed_ns("learn")
    assert w.unplaced_ns() == (50 + 2 + 10) * US
    left = sorted(aw.instruction_of(x.name) for x in w.unplaced())
    assert left == ["copy-done.77", "copy.9", "slice-start.5"]
    how = {aw.instruction_of(x.name): x.by for x in w.dones()}
    assert how == {"copy-done.294": "loop", "slice-done.3": "loop",
                   "slice-done.5": "consumer", "copy-done.3": "consumer",
                   "copy-done.77": ""}


def test_a_pair_has_its_exposed_hidden_and_rate():
    ops = _ops()
    w = aw.Waits(_trace(ops), ops, TABLES)
    pairs = {rec["name"]: rec for rec in w.pairs()}
    head = pairs["copy-done.294"]
    assert (head["n"], head["layer"], head["loop"]) == (2, "rollout", LOOP)
    assert head["exposed_ns"] == 200 * US
    assert head["hidden_ns"] == 2 * 100 * US  # start ends 115, done begins 215
    assert aw.rate_of(head) == pytest.approx(2 * 10240 / 400e-6)
    expert = pairs["slice-done.5"]
    assert expert["hidden_ns"] == (1460 - 1152) * US
    assert aw.rate_of(expert) == pytest.approx(7864320 / 348e-6)
    # no start of that name in the trace: exposed alone
    assert pairs["copy-done.77"]["hidden_ns"] is None
    assert aw.rate_of(pairs["copy-done.77"]) is None
    # a row, and no start of its name in the trace
    assert pairs["slice-done.3"]["hidden_ns"] is None
    assert [rec["name"] for rec in w.pairs()][0] == "copy-done.294"


PEAK = 819e9


@pytest.mark.parametrize("path, scope", [
    (STEP + "rollout/act/head/add", "rollout/act/head"),
    (STEP + "rollout/act", "rollout/act"),
    (LANE + "sgd_nest/while/body/closed_call/learn/loss_grad/"
     "jvp(learn/moe/experts)/convert_element_type", "learn/moe/experts"),
    (LANE + "gae/mul", "gae"),
    (STEP + "rollout/act/moe/experts/checkpoint/td,edf->tef/dot_general",
     "rollout/act/moe/experts/checkpoint"),
    (LANE + "learn/mla/scores/jit(_fragment_fwd)/fragment_attention_fwd/"
     "pallas_call", "learn/mla/scores"),
    ("jit(f)/while/body/dynamic_update_slice", "body/dynamic_update_slice"),
])
def test_a_consumer_is_named_from_the_programs_scope_on(path, scope):
    assert aw.consumer_scope(path) == scope


@pytest.mark.parametrize("hidden_us, exposed_us, nbytes, room, says", [
    (0.0, 20.0, 10_000_000, 2, "bandwidth"),     # 500 GB/s
    (100.0, 119.0, 10240, 2, "late start"),      # 47 MB/s, 2 instructions
    (300.0, 40.0, 7_864_320, 30, "queued"),      # 23 GB/s, 30 instructions
    (None, 40.0, 7_864_320, 30, "-"),            # no start seen
])
def test_what_a_rate_says(hidden_us, exposed_us, nbytes, room, says):
    rec = {"n": 1, "exposed_ns": exposed_us * US,
           "hidden_ns": None if hidden_us is None else hidden_us * US,
           "row": {"bytes": nbytes, "room": room}}
    assert aw.reading(rec, PEAK) == says


class _Ctx:
    """What a reader reads of ``perf.run.Context``."""

    def __init__(self, trace, updates, root, block_length=None, steps=2):
        self.trace = trace
        self.traced = run_lib.Window()
        self.traced.walls = [0.1]
        self.traced.before = {"updates": 0, "learn_steps": 0}
        self.traced.after = {"updates": updates, "learn_steps": updates}
        lm = {} if block_length is None else {"block_length": block_length}
        self.cell = type("Cell", (), {
            "root": root,
            "traffic": {"algo_config": {"rollout_fragment_length": steps}},
            "config": {"algo_config": {"model": {"sequence_lm": lm}}},
        })()


def _readers():
    cell = manifest_lib.load_cell("smallthinker_ppo.fused_tokens.1chip")
    return {name: cell.reader(name) for name in METRICS}


def test_the_four_readers_on_the_synthetic_trace(tmp_path, capsys):
    ops = _ops()
    trace = _trace(ops)
    trace._program_report = pt.Report(trace, 1, 4, ops)
    ctx = _Ctx(trace, 4, str(tmp_path))
    os.makedirs(tmp_path / ".perf_trace")
    readers = _readers()
    # this process holds no such program: the loops alone place
    got = {name: read(ctx) for name, read in readers.items()}
    assert got == {
        "rollout.exposed_wait_device_ms_per_step": pytest.approx(0.220 / 2),
        "learner.exposed_wait_device_ms_per_update": 0.0,
        "device.exposed_wait_device_ms_per_iter": pytest.approx(0.300),
        "device.unplaced_device_ms_per_iter": pytest.approx(0.132),
    }
    assert "[async-pairs] table of ['rollout_superstep', 'superstep']" in (
        capsys.readouterr().out)
    with open(tmp_path / ".perf_trace" / aw.TABLE_FILE) as f:
        assert json.load(f) == {}
    # with the program's table
    trace._async_waits = aw.Waits(trace, ops, TABLES)
    got = {name: read(ctx) for name, read in readers.items()}
    assert got == {
        "rollout.exposed_wait_device_ms_per_step": pytest.approx(0.110),
        "learner.exposed_wait_device_ms_per_update": pytest.approx(0.070 / 4),
        "device.exposed_wait_device_ms_per_iter": pytest.approx(0.300),
        "device.unplaced_device_ms_per_iter": pytest.approx(0.062),
    }
    # the step as the chip lives it, on one line
    rep = trace._program_report
    assert rep.scope_ms("", 1) == pytest.approx(
        aw.Waits(trace, ops).unscoped_ns() / 1e6)


def test_a_trace_with_no_pair_reads_zero_not_nothing(tmp_path):
    ops = _ops(pairs=False)
    trace = _trace(ops)
    trace._program_report = pt.Report(trace, 1, 4, ops)
    ctx = _Ctx(trace, 4, str(tmp_path))
    got = {name: read(ctx) for name, read in _readers().items()}
    assert got["rollout.exposed_wait_device_ms_per_step"] == 0.0
    assert got["learner.exposed_wait_device_ms_per_update"] == 0.0
    assert got["device.exposed_wait_device_ms_per_iter"] == 0.0
    assert got["device.unplaced_device_ms_per_iter"] == pytest.approx(0.050)


def test_a_trace_without_scopes_and_an_untraced_run_read_nothing(tmp_path):
    with open(os.path.join(DATA, "recorded_trace.json")) as f:
        plain = json.load(f)["plain"]
    ctx = _Ctx(tr.Trace(plain, 1), 8, str(tmp_path))
    readers = _readers()
    for name, read in readers.items():
        assert read(ctx) is None, name
    ctx.trace = None
    for name, read in readers.items():
        assert read(ctx) is None, name


@pytest.mark.parametrize("block_length, steps", [(None, 256), (4, 64), (1, 256)])
def test_a_lane_step_is_an_env_step_or_a_block(block_length, steps, tmp_path):
    ctx = _Ctx(None, 1, str(tmp_path), block_length=block_length, steps=256)
    assert aw.lane_steps(ctx) == steps


def test_the_block_cells_lane_takes_a_block_a_step():
    cell = manifest_lib.load_cell("sdar_ppo.fused_blocks.1chip")
    ctx = type("Ctx", (), {"cell": cell})()
    assert aw.lane_steps(ctx) == 64


@pytest.mark.parametrize("name", manifest_lib.load_manifest()["workloads"],
                         ids=lambda w: w["name"])
def test_every_cell_takes_the_metrics_its_list_gives_it(name):
    cell = manifest_lib.load_cell(name["name"])
    taken = {m["name"] for m in cell.per_layer}
    want = set(METRICS)
    if cell.name not in SEQUENCE_CELLS:
        want.discard("rollout.exposed_wait_device_ms_per_step")
    assert want <= taken
    assert ("rollout.exposed_wait_device_ms_per_step" in taken) == (
        cell.name in SEQUENCE_CELLS)
    for metric in want:
        assert callable(cell.reader(metric))


def test_the_manifest_states_the_four_as_the_issue_gave_them():
    entries = {m["name"]: m for m in manifest_lib.load_manifest()["per_layer"]}
    every = [w["name"] for w in manifest_lib.load_manifest()["workloads"]]
    for name in METRICS:
        m = entries[name]
        assert (m["unit"], m["better"], m["source"], m["moves"]) == (
            "ms", "lower", "device_trace", "env_steps_per_s")
        assert m["layer"] == name.split(".")[0]
    assert entries[METRICS[0]]["workloads"] == list(SEQUENCE_CELLS)
    assert entries[METRICS[1]]["workloads"] == every
    assert "workloads" not in entries[METRICS[2]]
    assert "workloads" not in entries[METRICS[3]]
    assert list(entries)[-4:] == list(METRICS)


def test_main_prints_the_account_of_a_cut(tmp_path, capsys):
    ops = _ops()
    trace = _trace(ops)
    cut = pt.cut_iterations(trace.plain, ops, 0, 1)
    cut["tables"] = TABLES
    path = tmp_path / "cut.json"
    path.write_text(json.dumps(cut))
    assert aw.main([str(path), "--top", "2"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["exposed_wait_ms_per_iter"] == pytest.approx(0.300)
    assert out["exposed_wait_ms_per_iter_by_layer"] == {
        "(unplaced)": pytest.approx(0.010), "learn": pytest.approx(0.070),
        "rollout": pytest.approx(0.220),
    }
    assert out["unplaced_ms_per_iter"] == pytest.approx(0.062)
    first = out["waits_by_loop_and_consumer"][0]
    assert (first["loop"], first["consumer"], first["layer"]) == (
        LOOP, "rollout/act/head", "rollout")
    head = first["pairs"][0]
    assert head["op"] == "%copy-done.294 copy-done f32[2560]"
    assert (head["room"], head["bytes"], head["reading"]) == (2, 10240, "late start")
    assert head["hidden_ms_per_iter"] == pytest.approx(0.200)
    assert list(out["unplaced_ops_ms_per_iter"])[0] == "copy bf16[32,4096,512]"
