"""The reductions of ``perf/program_trace.py`` on a hand-made trace
with known answers, on the chip trace recorded before the program
named anything (``data/recorded_trace.json``: every reader must find
nothing there), and on a cut of two whole iterations recorded on the
chip after PR 25 (``data/recorded_trace_spans.json``, written by
``python3 -m perf.program_trace --save-cut``)."""

import json
import os

import pytest

from perf import manifest as manifest_lib
from perf import program_trace as pt
from perf import run as run_lib
from perf import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1_000_000
NEW_METRICS = (
    "rollout.device_ms_per_iter", "replay.insert_device_ms_per_iter",
    "learner.superstep_device_ms_per_update",
    "rollout.host_idle_ms_per_iter", "replay.host_idle_ms_per_iter",
    "learner.host_idle_ms_per_iter", "entry.unattributed_idle_pct",
    "replay.scope_device_ms_per_update",
    "learner.scope_device_ms_per_update",
    "device.unscoped_device_ms_per_iter",
)


def _plain():
    """One 100 ms iteration. Device busy 10..30 (rollout), 40..50
    (insert), 52..53 (the insert's tree update), 60..90 (superstep),
    95..97 (the refresh's tree update): idle 37 ms."""
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_jax_rollout(11)", 10 * MS, 20 * MS],
                ["jit_replay_insert(12)", 40 * MS, 10 * MS],
                ["jit_tree_update(13)", 52 * MS, 1 * MS],
                ["jit_superstep(14)", 60 * MS, 30 * MS],
                ["jit_tree_update(15)", 95 * MS, 2 * MS],
            ]},
            {"name": "XLA Ops", "events": [
                ["%fusion.1", 10 * MS, 20 * MS],
                ["%copy.2", 40 * MS, 10 * MS],
                ["%scatter.3", 52 * MS, 1 * MS],
                ["%while.4", 60 * MS, 30 * MS],  # a container
                ["%fusion.5", 60 * MS, 12 * MS],
                ["%fusion.6", 72 * MS, 18 * MS],
                ["%scatter.7", 95 * MS, 2 * MS],
            ]},
        ]},
        {"name": "/host:CPU", "lines": [
            {"name": "main", "events": [
                ["perf:train", 0, 100 * MS],
                ["train:iteration", 1 * MS, 97 * MS],
                ["rollout:keys", 2 * MS, 7 * MS],          # idle 2..9
                ["rollout:device", 9 * MS, 23 * MS],       # 9..32
                ["PjitFunction(jax_rollout)", 9 * MS, 1 * MS],
                ["rollout:drain", 11 * MS, 20 * MS],       # 11..31
                ["replay:insert", 33 * MS, 5 * MS],        # 33..38
                ["PjitFunction(replay_insert)", 33 * MS, 1 * MS],
                ["PjitFunction(tree_update)", 36 * MS, 1 * MS],
                ["learn:superstep", 55 * MS, 37 * MS],     # 55..92
                ["jit:superstep[x]", 56 * MS, 2 * MS],     # looked through
                ["learn:drain", 58 * MS, 33 * MS],         # 58..91
                ["replay:refresh", 92 * MS, 2 * MS],       # 92..94
                ["PjitFunction(tree_update)", 93 * MS, 1 * MS],
                ["np.asarray_jax.Array_", 58 * MS, 32 * MS],
            ]},
            {"name": "another thread", "events": [
                ["feeder:transfer", 0, 100 * MS],
            ]},
        ]},
    ]}


def _trace():
    return tr.Trace(_plain(), 1, (0, 100 * MS))


def test_idle_pieces_add_up_to_the_traces_idle_time_exactly():
    t = _trace()
    by_span = pt.idle_by_span(t)
    idle_ns = round((t.span_s() - t.busy_s()) * 1e9)
    assert idle_ns == 37 * MS
    assert sum(by_span.values()) == idle_ns
    assert sum(b - a for a, b in pt.idle_intervals(t)) == idle_ns
    assert sum(pt.idle_by_layer(t).values()) == idle_ns


def test_an_innermost_span_wins_over_its_parent():
    by_span = pt.idle_by_span(_trace())
    # idle 0..10: keys 2..9 (7), rollout:device 9..10 (1), 0..2 nobody
    # idle 30..40: drain 30..31, device 31..32, insert 33..38, 3 nobody
    # idle 50..52 nobody; idle 53..60: superstep 55..58 (jit: looked
    #   through), drain 58..60, 2 nobody; idle 90..95: drain 90..91,
    #   superstep 91..92, refresh 92..94, 1 nobody; idle 97..100 nobody
    assert by_span == {
        "rollout:keys": 7 * MS,
        "rollout:device": 2 * MS,
        "rollout:drain": 1 * MS,
        "replay:insert": 5 * MS,
        "learn:superstep": 4 * MS,
        "learn:drain": 3 * MS,
        "replay:refresh": 2 * MS,
        "": 13 * MS,
    }
    assert pt.idle_by_layer(_trace()) == {
        "rollout": 10 * MS, "replay": 7 * MS, "learn": 7 * MS, "": 13 * MS,
    }


def test_segments_of_nested_and_sibling_spans():
    spans = [(0, 100, "a:x"), (10, 30, "b:x"), (40, 50, "c:x"), (120, 130, "d:x")]
    assert pt.innermost_segments(spans) == [
        (0, 10, "a:x"), (10, 30, "b:x"), (30, 40, "a:x"), (40, 50, "c:x"),
        (50, 100, "a:x"), (120, 130, "d:x"),
    ]


def test_module_families_match_by_prefix():
    t = _trace()
    assert pt.family_of("jit_replay_insert(12)") == "replay_insert"
    assert pt.family_seconds(t, "jax_rollout") == pytest.approx(0.020)
    assert pt.family_seconds(t, "tree_update") == pytest.approx(0.003)
    assert pt.family_seconds(t, "superstep") == pytest.approx(0.030)
    assert pt.family_seconds(t, "rollout_superstep") is None
    # of the two tree updates, the one dispatched inside replay:insert
    assert pt.seconds_dispatched_under(
        t, "tree_update", "replay:insert"
    ) == pytest.approx(0.001)
    assert pt.seconds_dispatched_under(
        t, "tree_update", "replay:refresh"
    ) == pytest.approx(0.002)


def test_scope_of_takes_the_innermost_of_the_programs_scopes():
    assert pt.scope_of(
        "jit(superstep)/jit(main)/sgd_nest/while/body/closed_call/learn/"
        "loss_grad/transpose(jvp(conv0))/conv_general_dilated:"
    ) == "learn/loss_grad"
    assert pt.scope_of("jit(x)/sgd_nest/while/cond/lt:") == "sgd_nest"
    assert pt.scope_of("jit(x)/rollout/postprocess/gae/mul:") == "gae"
    assert pt.scope_of("jit(x)/jvp(replay/gather)/gather:") == "replay/gather"
    assert pt.scope_of("jit(x)/sgd_nested/add:") == ""
    assert pt.scope_of("") == ""


def _op_scopes():
    return [
        ["jit(jax_rollout)/while/body/rollout/act/conv0/conv:", 10 * MS, 20 * MS],
        ["", 40 * MS, 10 * MS, "%copy.2 copy u32[8,4]"],  # the compiler's
        ["jit(tree_update)/replay/refresh/scatter:", 52 * MS, 1 * MS],
        ["jit(superstep)/while:", 60 * MS, 30 * MS],  # a container
        ["jit(superstep)/replay/gather/gather:", 60 * MS, 12 * MS],
        ["jit(superstep)/while/body/sgd_nest/while/body/learn/loss_grad/dot:",
         72 * MS, 18 * MS],
        ["jit(tree_update)/replay/refresh/scatter:", 95 * MS, 2 * MS],
    ]


def test_scope_seconds_leaves_containers_out_and_keeps_the_unscoped():
    got = pt.scope_seconds(_op_scopes(), (0, 100 * MS))
    assert got == {
        "rollout/act": pytest.approx(0.020), "": pytest.approx(0.010),
        "replay/refresh": pytest.approx(0.003),
        "replay/gather": pytest.approx(0.012),
        "learn/loss_grad": pytest.approx(0.018),
    }
    assert sum(got.values()) == pytest.approx(_trace().busy_s())
    # a program without scopes: nothing, not "all unscoped"
    assert pt.scope_seconds([["", 0, 5], ["jit(f)/add:", 5, 5]], None) is None
    assert pt.scope_seconds(None, None) is None


def test_operations_go_to_the_program_they_ran_inside():
    by = pt.scope_seconds_by_family(_trace(), _op_scopes())
    assert by["replay_insert"] == {"": pytest.approx(0.010)}
    assert by["superstep"] == {
        "replay/gather": pytest.approx(0.012),
        "learn/loss_grad": pytest.approx(0.018),
    }
    assert by["tree_update"] == {"replay/refresh": pytest.approx(0.003)}
    names = pt.scope_seconds_by_family(_trace(), _op_scopes(), names=True)
    assert names == {"replay_insert": {"%copy.2 copy u32[8,4]": pytest.approx(0.010)}}


def _early(plain, ns):
    """The same trace with the device's lines ``ns`` early."""
    for line in plain["planes"][0]["lines"]:
        line["events"] = [[n, s - ns, d] for n, s, d in line["events"]]
    return plain


def test_device_lines_that_run_early_are_moved_onto_the_hosts_plane():
    # as drawn, every execution starts after its dispatch: no shift
    assert pt.device_clock_offset_ns(_trace()) == 0
    # 8 ms early, the least shift that mends every pair is the largest
    # over the families: the rollout seems to run at 2 (given at 9): 7;
    # the insert at 32 (33): 1; the superstep at 52 (56): 4; the tree
    # updates, matched in order, at 44 (36) and 87 (93): 6
    plain = _early(_plain(), 8 * MS)
    t = tr.Trace(plain, 1, (0, 100 * MS))
    assert pt.device_clock_offset_ns(t) == 7 * MS
    by_span = pt.idle_by_span(t)
    assert sum(by_span.values()) == sum(
        b - a for a, b in pt.idle_intervals(t)
    )
    # the refresh's tree update still belongs to replay:refresh
    assert pt.seconds_dispatched_under(
        t, "tree_update", "replay:refresh"
    ) == pytest.approx(0.002)
    # a family that ran more often than it was given (an execution
    # from before the span) is left out of the estimate
    plain["planes"][0]["lines"][0]["events"].insert(
        0, ["jit_jax_rollout(11)", 0, 1 * MS]
    )
    assert pt.device_clock_offset_ns(tr.Trace(plain, 1, (0, 100 * MS))) == 6 * MS


def test_report_values_per_iteration_and_per_update():
    rep = pt.Report(_trace(), 1, 8, _op_scopes())
    assert rep.family_ms("jax_rollout", rep.iterations) == pytest.approx(20)
    assert rep.family_ms("superstep", rep.updates) == pytest.approx(30 / 8)
    assert rep.family_ms(
        "replay_insert", 1, also_s=pt.seconds_dispatched_under(
            rep.trace, "tree_update", "replay:insert")
    ) == pytest.approx(11)
    assert rep.idle_ms("rollout") == pytest.approx(10)
    assert rep.idle_ms("replay") == pytest.approx(7)
    assert rep.idle_ms("learn") == pytest.approx(7)
    assert rep.unattributed_idle_pct() == pytest.approx(100 * 13 / 37)
    assert rep.scope_ms("replay/", rep.updates) == pytest.approx(15 / 8)
    assert rep.scope_ms("learn/", rep.updates) == pytest.approx(18 / 8)
    assert rep.scope_ms("", rep.iterations) == pytest.approx(10)


class _Ctx:
    """What a reader reads of ``perf.run.Context``."""

    def __init__(self, trace, iterations, updates, root):
        self.trace = trace
        self.traced = run_lib.Window()
        self.traced.walls = [0.1] * iterations
        self.traced.before = {"updates": 0, "learn_steps": 0}
        self.traced.after = {"updates": updates, "learn_steps": updates}
        self.cell = type("Cell", (), {"root": root})()


@pytest.fixture()
def cell():
    return manifest_lib.load_cell("dqn_per.fused.1chip")


def test_every_new_metric_is_in_the_manifest_with_a_reader(cell):
    names = [m["name"] for m in cell.per_layer]
    for name in NEW_METRICS:
        assert name in names
        assert callable(cell.reader(name))


def test_every_new_reader_finds_nothing_in_a_trace_without_program_names(
    cell, tmp_path
):
    with open(os.path.join(DATA, "recorded_trace.json")) as f:
        plain = json.load(f)["plain"]
    ctx = _Ctx(tr.Trace(plain, 1), 1, 8, str(tmp_path))
    for name in NEW_METRICS:
        assert cell.reader(name)(ctx) is None, name
    # and nothing at all in an untraced run
    ctx.trace = None
    for name in NEW_METRICS:
        assert cell.reader(name)(ctx) is None, name


def test_readers_on_the_synthetic_trace(cell, tmp_path):
    trace = _trace()
    trace._program_report = pt.Report(trace, 1, 8, _op_scopes())
    ctx = _Ctx(trace, 1, 8, str(tmp_path))
    got = {name: cell.reader(name)(ctx) for name in NEW_METRICS}
    assert got == {
        "rollout.device_ms_per_iter": pytest.approx(20),
        "replay.insert_device_ms_per_iter": pytest.approx(11),
        "learner.superstep_device_ms_per_update": pytest.approx(3.75),
        "rollout.host_idle_ms_per_iter": pytest.approx(10),
        "replay.host_idle_ms_per_iter": pytest.approx(7),
        "learner.host_idle_ms_per_iter": pytest.approx(7),
        "entry.unattributed_idle_pct": pytest.approx(100 * 13 / 37),
        "replay.scope_device_ms_per_update": pytest.approx(15 / 8),
        "learner.scope_device_ms_per_update": pytest.approx(18 / 8),
        "device.unscoped_device_ms_per_iter": pytest.approx(10),
    }


def _encode(fields):
    """A protobuf message from ``[(number, int | bytes), ...]``."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)

    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += varint(number << 3) + varint(value)
        else:
            out += varint(number << 3 | 2) + varint(len(value)) + value
    return out


def test_the_wire_reader_finds_each_operations_tf_op(tmp_path):
    stat_meta = _encode([(1, 7), (2, b"tf_op")])
    other_meta = _encode([(1, 8), (2, b"flops")])
    scoped = _encode([(1, 1), (2, b"%fusion.1 = f32[4]{0} fusion(f32[4]{0} %x), kind=kLoop"), (5, _encode(
        [(1, 8), (3, 12)])), (5, _encode([(1, 7), (5, b"jit(f)/replay/draw/add:")]))])
    bare = _encode([(1, 2), (2, b"%copy.2 = u32[8,4]{1,0} copy(u32[8,4]{0,1} %y)")])
    line = _encode([
        (2, b"XLA Ops"), (3, 1000),
        (4, _encode([(1, 1), (2, 5_000_000), (3, 2_000_000)])),
        (4, _encode([(1, 2), (2, 9_000_000), (3, 1_000_000)])),
    ])
    other_line = _encode([(2, b"XLA Modules"),
                          (4, _encode([(1, 1), (2, 0), (3, 9)]))])
    plane = _encode([
        (2, b"/device:TPU:0"), (3, other_line), (3, line),
        (4, _encode([(1, 1), (2, scoped)])), (4, _encode([(1, 2), (2, bare)])),
        (5, _encode([(1, 7), (2, stat_meta)])),
        (5, _encode([(1, 8), (2, other_meta)])),
    ])
    host = _encode([(2, b"/host:CPU")])
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_encode([(1, host), (1, plane)]))
    assert pt.load_op_scopes(str(path)) == [
        ["jit(f)/replay/draw/add:", 6000.0, 2000.0, "%fusion.1 fusion f32[4]"],
        ["", 10000.0, 1000.0, "%copy.2 copy u32[8,4]"],
    ]
    path.write_bytes(_encode([(1, host)]))
    assert pt.load_op_scopes(str(path)) is None


def _same(got, want, at="summary"):
    if isinstance(want, dict):
        assert isinstance(got, dict) and set(got) == set(want), at
        for key in want:
            _same(got[key], want[key], f"{at}.{key}")
    elif isinstance(want, float):
        assert got == pytest.approx(want, rel=1e-9, abs=1e-12), at
    else:
        assert got == want, at


def test_recorded_chip_trace_with_program_names():
    with open(os.path.join(DATA, "recorded_trace_spans.json")) as f:
        cut = json.load(f)
    trace, ops = pt.load_cut(cut)
    rep = pt.Report(trace, cut["iterations"], cut["updates"], ops)
    _same(json.loads(json.dumps(pt.summary(rep))), cut["expected"])
    # whole iterations: every perf:train of the cut holds its layers' spans
    host = pt.main_thread_events(trace.plain)
    trains = [(s, s + d) for n, s, d in host if n == tr.TRAIN_ANNOTATION]
    assert len(trains) == cut["iterations"]
    for lo, hi in trains:
        inside = {n for n, s, d in host if lo <= s and s + d <= hi}
        assert {"train:iteration", "rollout:keys", "rollout:device",
                "rollout:drain", "replay:insert", "replay:draw", "learn:keys",
                "learn:superstep", "learn:drain", "replay:refresh",
                "rollout:sync_weights", "train:result"} <= inside
    # the pieces add up: idle by layer to the trace's idle time exactly,
    # the scopes to the busy time, no program is called _counted
    idle_ns = sum(b - a for a, b in pt.idle_intervals(trace))
    assert sum(rep.idle.values()) == idle_ns
    assert idle_ns / 1e9 == pytest.approx(trace.span_s() - trace.busy_s())
    assert sum(rep.scopes.values()) == pytest.approx(trace.busy_s(), rel=0.01)
    assert not any("_counted" in name for name in trace.module_seconds())
