"""The trace reduction on a hand-made trace with known answers, and
on a small trace recorded on the chip (``data/recorded_trace.json``,
the plain form ``load_xplane`` gives, cut to a few program
executions)."""

import json
import os

import pytest

from perf import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _plain():
    ms = 1_000_000
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit__counted(1)", 0 * ms, 40 * ms],
                ["jit__counted(2)", 50 * ms, 10 * ms],
                ["jit__counted(1)", 60 * ms, 40 * ms],
            ]},
            {"name": "XLA Ops", "events": [
                # a while loop enclosing its body is a container
                ["while.1", 0 * ms, 40 * ms],
                ["convolution.3", 0 * ms, 25 * ms],
                ["all-reduce.7", 20 * ms, 20 * ms],  # 5 ms under the conv
                ["fusion.9", 50 * ms, 10 * ms],
                ["convolution.3", 60 * ms, 40 * ms],
            ]},
        ]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [
                ["perf:train", 0, 100 * ms],
                ["device_get", 41 * ms, 8 * ms],
            ]},
        ]},
    ]}


def test_union_and_subtract():
    assert tr.union_ns([(0, 10), (5, 20), (30, 40)]) == 30
    assert tr.subtract_ns([(20, 40)], [(0, 25)]) == 15


def test_busy_idle_and_per_program_time():
    t = tr.Trace(_plain())
    assert t.span_s() == pytest.approx(0.100)
    assert t.busy_s() == pytest.approx(0.090)  # idle 40..50 ms
    assert t.idle_share() == pytest.approx(0.10)
    assert t.module_seconds() == {
        "jit__counted(1)": pytest.approx(0.080),
        "jit__counted(2)": pytest.approx(0.010),
    }
    assert t.module_counts() == {"jit__counted(1)": 2, "jit__counted(2)": 1}


def test_containers_are_not_counted_as_operations():
    ops = tr.Trace(_plain()).op_seconds()
    assert "while.1" not in ops
    assert ops["convolution.3"] == pytest.approx(0.065)
    assert ops["all-reduce.7"] == pytest.approx(0.020)


def test_exposed_collective_time():
    # all-reduce 20..40 ms, conv covers 0..25 ms: 15 ms exposed
    assert tr.Trace(_plain()).exposed_collective_s() == pytest.approx(0.015)


def test_idle_gap_is_named_by_the_host_event_inside_it():
    gaps = tr.Trace(_plain()).idle_gaps()
    assert gaps[0][0] == "device_get"
    assert gaps[0][1] == pytest.approx(0.010)
    b = tr.Trace(_plain()).breakdown()
    assert b["device_ops"][0][0] == "convolution.3"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_mean_over_chips_and_chip_limit():
    plain = _plain()
    second = json.loads(json.dumps(plain["planes"][0]))
    second["name"] = "/device:TPU:1"
    second["lines"][1]["events"] = second["lines"][1]["events"][:2]  # 0..40 ms
    plain["planes"].append(second)
    assert tr.Trace(plain).busy_s() == pytest.approx((0.090 + 0.040) / 2)
    assert tr.Trace(plain, chips=1).busy_s() == pytest.approx(0.090)


def test_recorded_chip_trace():
    path = os.path.join(DATA, "recorded_trace.json")
    with open(path) as f:
        rec = json.load(f)
    t = tr.Trace(rec["plain"])
    want = rec["expected"]
    assert t.span_s() == pytest.approx(want["span_s"], rel=1e-9)
    assert t.busy_s() == pytest.approx(want["busy_s"], rel=1e-9)
    # recomputed here independently: busy time by a sweep over sorted ends
    ops = next(
        line["events"] for line in rec["plain"]["planes"][0]["lines"]
        if line["name"] == tr.OPS_LINE
    )
    marks = sorted([(s, 1) for _, s, d in ops] + [(s + d, -1) for _, s, d in ops])
    depth, busy, last = 0, 0, None
    for at, step in marks:
        if depth > 0:
            busy += at - last
        depth += step
        last = at
    assert t.busy_s() == pytest.approx(busy / 1e9, rel=1e-9)
    top = max(t.module_seconds().items(), key=lambda kv: kv[1])
    assert top[0] == want["top_module"]
    assert top[1] == pytest.approx(want["top_module_s"], rel=1e-9)


def test_bounds_clip_events_and_set_the_span():
    ms = 1_000_000
    t = tr.Trace(_plain(), bounds=(10 * ms, 70 * ms))
    assert t.span_s() == pytest.approx(0.060)
    # busy: 10..40 and 50..70 ms
    assert t.busy_s() == pytest.approx(0.050)
    assert tr.annotation_bounds(_plain(), "perf:train") == (0, 100 * ms)
    assert tr.annotation_bounds(_plain(), "absent") is None


def test_edges_of_a_bounded_span_are_idle_gaps():
    ms = 1_000_000
    t = tr.Trace(_plain(), bounds=(-20 * ms, 100 * ms))
    assert t.idle_share() == pytest.approx(30 / 120)
    gaps = sorted(g[1] for g in t.idle_gaps())
    assert gaps == [pytest.approx(0.010), pytest.approx(0.020)]
