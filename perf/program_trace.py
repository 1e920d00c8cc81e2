"""Per-layer readings from the program's own names in a profiler trace.

Since PR 25 the program writes three kinds of names into the trace the
JAX profiler takes of it, on the profiler's clock:

- its spans (``ray_tpu/util/tracing.py``: ``rollout:keys``,
  ``replay:insert``, ``learn:drain`` ...), as events on the host thread
  that opened them;
- its programs' families: ``sharded_jit`` names the function it hands
  to ``jax.jit`` after the label up to the first ``[``, so an
  ``XLA Modules`` event reads ``jit_replay_insert(<fingerprint>)`` and
  the host's dispatch ``PjitFunction(replay_insert)``;
- ``jax.named_scope``s inside the programs (``replay/gather``,
  ``learn/loss_grad`` ...). On this runtime (libtpu 0.0.34) the scope
  of an operation is the ``tf_op`` stat of its *event metadata* in the
  ``.xplane.pb``; ``jax.profiler.ProfileData`` shows an event's own
  stats only, so ``load_op_scopes`` reads the file's wire format
  itself (six message types, a screenful).

Three reductions, each on the plain form ``perf/trace_reduce.py``
gives, so ``perf/tests`` checks them on small traces:

``idle_by_span``  every interval in which the first chip ran nothing,
    inside the traced span, moved onto the host's plane (the device's
    lines run early on it: ``device_clock_offset_ns``) and cut by the
    main thread's program spans;
    each piece goes to the innermost span (``train:iteration``, the
    benchmark's ``perf:train`` and ``jit:`` dispatch spans are looked
    through), and what no span covers is unattributed ("").
``family_seconds``  device seconds of the programs whose family starts
    with a name; ``seconds_dispatched_under`` keeps the executions
    whose host dispatch lies inside a span of a given prefix.
``scope_seconds``  device seconds of the leaf operations by the
    innermost of the program's scopes on their ``tf_op`` path; ""
    holds the operations under none, where copies the compiler
    inserted land.

A trace that lacks what a reduction reads (a program from before
PR 25, a CPU run) gives ``None``, never a number.
"""

from __future__ import annotations

import bisect
import json
import os
import re
from typing import Dict, Iterable, Iterator, List, Optional, Tuple

from perf import trace_reduce as tr

Interval = Tuple[int, int]
Span = Tuple[int, int, str]  # start_ns, end_ns, name

# a program span is "<layer prefix>:<what>"; host events of the
# runtime ("PjitFunction(x)", "Foo::Bar", "$file.py:12 fn") are not
SPAN_NAME = re.compile(r"^([a-z][a-z0-9_]*):[A-Za-z0-9_<]")
# spans the attribution looks through: the iteration as a whole, and
# the compile layer's dispatch span inside a layer's own
SEE_THROUGH = ("train:iteration", tr.TRAIN_ANNOTATION, "jit:")
UNATTRIBUTED = ""

# the scopes the program opens (docs/observability.md "Named scopes")
_STAGED = re.compile(r"(?:^|[/(])((?:replay|learn|rollout)/[a-z0-9_]+)")
_BARE = re.compile(r"(?:^|[/(])(gae|sgd_nest)(?=[/):]|$)")


# -- the main thread's program spans --------------------------------------


def main_thread_events(plain: Dict) -> List[tr.Event]:
    """Events of the host thread that ran the iterations: the line of
    a host plane with the most ``perf:train`` (failing that,
    ``train:iteration``) events."""
    best: Tuple[int, List] = (0, [])
    for marker in (tr.TRAIN_ANNOTATION, "train:iteration"):
        for plane in plain["planes"]:
            if not plane["name"].startswith(tr.HOST_PLANE_PREFIX):
                continue
            for line in plane["lines"]:
                n = sum(1 for ev in line["events"] if ev[0] == marker)
                if n > best[0]:
                    best = (n, line["events"])
        if best[0]:
            break
    return best[1]


def program_spans(plain: Dict) -> List[Span]:
    """The main thread's program spans, those looked through left out."""
    return sorted(
        (int(s), int(s + d), name)
        for name, s, d in main_thread_events(plain)
        if SPAN_NAME.match(name) and not name.startswith(SEE_THROUGH)
    )


def innermost_segments(spans: Iterable[Span]) -> List[Span]:
    """Cut nested spans of one thread into pieces that do not overlap,
    each named by the innermost span over it."""
    out: List[Span] = []
    stack: List[Tuple[int, str]] = []  # (end, name), outermost first
    cursor = 0

    def emit(upto: int, name: str) -> None:
        if upto > cursor:
            out.append((cursor, upto, name))

    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, inner = stack.pop()
            emit(end, inner)
            cursor = max(cursor, end)
        if stack:
            emit(s, stack[-1][1])
            e = min(e, stack[-1][0])  # a child ends with its parent
        cursor = s
        stack.append((e, name))
    while stack:
        end, inner = stack.pop()
        emit(end, inner)
        cursor = max(cursor, end)
    return out


# -- the device's lines against the host's ---------------------------------------

# a device execution that seems to begin this long before its own
# dispatch is a wrong match, not a clock
_OFFSET_CAP_NS = 20_000_000


def dispatches(plain: Dict, lo: int, hi: int) -> Dict[str, List[int]]:
    """``{family: start_ns of each main-thread dispatch inside
    [lo, hi)}``: the ``PjitFunction(<family>)`` events (jax writes two,
    one inside the other: the outer one counts)."""
    out: Dict[str, List[int]] = {}
    ends: Dict[str, int] = {}
    for name, s, d in sorted(
        main_thread_events(plain), key=lambda e: (e[1], -e[2])
    ):
        if not name.startswith("PjitFunction(") or not lo <= s < hi:
            continue
        if s < ends.get(name, -1):
            continue
        ends[name] = s + d
        out.setdefault(name[len("PjitFunction("):-1], []).append(int(s))
    return out


def device_clock_offset_ns(trace: tr.Trace) -> int:
    """How far the first chip's lines run EARLY against the host's
    plane. The profiler puts both on one clock, but not exactly: in
    the v5e traces of PR 25 a program seems to start 2.1 ms before the
    host dispatched it. The least shift that puts every execution at
    or after its own dispatch, taken over the program families that
    were executed in the traced span as often as they were dispatched
    in it (matched in order: one chip runs its programs in the order
    it was given them). 0 where nothing can be matched."""
    if not trace.devices:
        return 0
    known = getattr(trace, "_clock_offset_ns", None)
    if known is not None:  # every reduction of one trace asks
        return known
    lo, hi = trace.span_ns()
    given = dispatches(trace.plain, lo, hi)
    ran: Dict[str, List[int]] = {}
    for name, s, _ in sorted(
        trace._line(trace.devices[0], tr.MODULES_LINE), key=lambda e: e[1]
    ):
        ran.setdefault(family_of(name), []).append(s)
    offset = 0
    for family, starts in ran.items():
        at = given.get(family, [])
        if len(at) != len(starts):
            continue
        early = max(d - e for d, e in zip(at, starts))
        if early < _OFFSET_CAP_NS:
            offset = max(offset, early)
    trace._clock_offset_ns = int(offset)
    return trace._clock_offset_ns


# -- idle time, by span and by layer ----------------------------------------


def idle_intervals(trace: tr.Trace, programs: bool = False) -> List[Interval]:
    """The intervals of the traced span in which the first chip ran no
    operation. Their lengths add up to what ``Trace`` calls idle time
    (span minus busy) on that chip. With ``programs`` the intervals in
    which it ran no PROGRAM: the part of the idle time that is not the
    device's own gaps between the operations of a running program."""
    if not trace.devices:
        return []
    plane = trace.devices[0]
    ops = [] if programs else trace._line(plane, tr.OPS_LINE)
    busy = sorted(
        (s, s + d)
        for _, s, d in (ops or trace._line(plane, tr.MODULES_LINE))
    )
    lo, hi = trace.span_ns()
    out, cursor = [], lo
    for s, e in busy:
        if s > cursor:
            out.append((cursor, min(s, hi)))
        cursor = max(cursor, e)
    if hi > cursor:
        out.append((cursor, hi))
    return [(s, e) for s, e in out if e > s]


def idle_by_span(trace: tr.Trace,
                 programs: bool = False) -> Optional[Dict[str, int]]:
    """``{innermost span name: idle ns}`` with ``""`` for the idle time
    no program span covers; ``None`` where the trace has no device or
    the main thread carries no program span. Each idle interval is
    first moved onto the host's plane (``device_clock_offset_ns``).
    ``programs``: as in ``idle_intervals``."""
    spans = program_spans(trace.plain)
    if not trace.devices or not spans:
        return None
    segments = innermost_segments(spans)
    out: Dict[str, int] = {UNATTRIBUTED: 0}
    i = 0
    late = device_clock_offset_ns(trace)
    for gs, ge in idle_intervals(trace, programs):
        gs, ge = gs + late, ge + late  # on the host's plane
        while i < len(segments) and segments[i][1] <= gs:
            i += 1
        covered, j = 0, i
        while j < len(segments) and segments[j][0] < ge:
            s, e, name = segments[j]
            piece = min(e, ge) - max(s, gs)
            if piece > 0:
                out[name] = out.get(name, 0) + piece
                covered += piece
            j += 1
        out[UNATTRIBUTED] += (ge - gs) - covered
    return out


def idle_by_layer(trace: tr.Trace) -> Optional[Dict[str, int]]:
    """``idle_by_span`` summed by the span's layer prefix
    (``rollout``, ``replay``, ``learn``, ``train`` ...; ``""`` stays
    the unattributed part)."""
    by_span = idle_by_span(trace)
    if by_span is None:
        return None
    out: Dict[str, int] = {}
    for name, ns in by_span.items():
        prefix = name.split(":", 1)[0]
        out[prefix] = out.get(prefix, 0) + ns
    return out


# -- device time by program family --------------------------------------------


def family_of(module_name: str) -> str:
    """``jit_replay_insert(123)`` -> ``replay_insert``."""
    name = module_name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def family_seconds(trace: tr.Trace, prefix: str) -> Optional[float]:
    """Device seconds (mean over the chips) of the programs whose
    family starts with ``prefix``; ``None`` where no program does."""
    hit = [
        sec for name, sec in trace.module_seconds().items()
        if family_of(name).startswith(prefix)
    ]
    return sum(hit) if hit else None


def seconds_dispatched_under(trace: tr.Trace, family: str,
                             span_prefix: str) -> float:
    """Device seconds, on the first chip, of the executions of
    ``family`` programs that were dispatched inside a main-thread span
    whose name starts with ``span_prefix``. An execution belongs to
    the latest host dispatch (``PjitFunction(<family>)``) that began
    before it did (on the host's plane: ``device_clock_offset_ns``):
    right wherever fewer than two executions of one family are in
    flight at once, which a round that ends each layer's work with a
    blocking read guarantees."""
    if not trace.devices:
        return 0.0
    host = main_thread_events(trace.plain)
    given = sorted(
        s for name, s, _ in host
        if name.startswith("PjitFunction(" + family)
    )
    spans = [
        (s, s + d) for name, s, d in host if name.startswith(span_prefix)
    ]
    late = device_clock_offset_ns(trace)
    total = 0
    for name, s, d in trace._line(trace.devices[0], tr.MODULES_LINE):
        if not family_of(name).startswith(family):
            continue
        i = bisect.bisect_right(given, s + late)
        if i and any(a <= given[i - 1] < b for a, b in spans):
            total += d
    return total / 1e9


# -- device time by named scope -------------------------------------------------


def scope_of(tf_op: str) -> str:
    """The innermost of the program's scopes on an operation's
    ``tf_op`` path (``jit(superstep)/.../sgd_nest/while/body/learn/
    loss_grad/conv0/dot_general:`` -> ``learn/loss_grad``), ``""``
    where it is under none."""
    best, at = UNATTRIBUTED, -1
    for pattern in (_STAGED, _BARE):
        for m in pattern.finditer(tf_op):
            if m.start(1) > at:
                best, at = m.group(1), m.start(1)
    return best


def _varint(buf, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of one protobuf message: an int for a
    varint or fixed field, a memoryview for a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value = int.from_bytes(buf[i:i + size], "little")
            i += size
        else:
            raise ValueError(f"wire type {kind} in an .xplane.pb")
        yield key >> 3, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entries(plane_fields, field: int) -> Iterator[Tuple[int, object]]:
    for number, value in plane_fields:
        if number == field:
            entry = dict(_fields(value))
            yield int(entry.get(1, 0)), entry.get(2, b"")


def load_op_scopes(path: str) -> Optional[List[List]]:
    """``[[tf_op, start_ns, duration_ns, name], ...]`` of the
    ``XLA Ops`` line of the first ``/device:TPU:`` plane of an
    ``.xplane.pb``: each operation's ``tf_op`` metadata stat ("" where
    it has none) first, its shortened name (``short_op_name``) last.
    ``None`` where the file has no such plane. Field numbers:
    tsl/profiler/protobuf/xplane.proto."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = []
    for number, plane in _fields(space):
        if number != 1:
            continue
        name = next((_text(v) for n, v in _fields(plane) if n == 2), "")
        if name.startswith(tr.DEVICE_PLANE_PREFIX):
            planes.append((name, plane))
    if not planes:
        return None
    plane = list(_fields(min(planes, key=lambda p: p[0])[1]))
    stat_names = {
        key: _text(dict(_fields(meta)).get(2, b""))
        for key, meta in _map_entries(plane, 5)
    }
    tf_op_ids = {k for k, v in stat_names.items() if v == "tf_op"}
    scope_of_metadata: Dict[int, str] = {}
    name_of_metadata: Dict[int, str] = {}
    for key, meta in _map_entries(plane, 4):
        for number, stat in _fields(meta):
            if number == 2:
                name_of_metadata[key] = tr.short_op_name(_text(stat))
            if number != 5:
                continue
            stat = dict(_fields(stat))
            if stat.get(1) in tf_op_ids:
                if 5 in stat:
                    scope_of_metadata[key] = _text(stat[5])
                elif 7 in stat:  # a reference into the stat names
                    scope_of_metadata[key] = stat_names.get(stat[7], "")
    out: List[List] = []
    for number, line in plane:
        if number != 3:
            continue
        line = list(_fields(line))
        if next((_text(v) for n, v in line if n == 2), "") != tr.OPS_LINE:
            continue
        t0 = next((v for n, v in line if n == 3), 0)
        for n, event in line:
            if n != 4:
                continue
            event = dict(_fields(event))
            key = event.get(1, 0)
            out.append([
                scope_of_metadata.get(key, ""),
                t0 + event.get(2, 0) / 1e3,
                event.get(3, 0) / 1e3,
                name_of_metadata.get(key, ""),
            ])
    return out


def _leaf_ops(op_scopes: List[List],
              bounds: Optional[Interval]) -> List[Tuple[List, float]]:
    """``(entry of op_scopes, its duration inside bounds)`` of every
    leaf operation (``Trace._ops``: a ``while`` around its body is a
    container, not work itself)."""
    plain = {"planes": [{"name": tr.DEVICE_PLANE_PREFIX + "0", "lines": [
        {"name": tr.OPS_LINE,
         "events": [[i, op[1], op[2]] for i, op in enumerate(op_scopes)]},
    ]}]}
    trace = tr.Trace(plain, 1, bounds)
    return [(op_scopes[i], d) for i, _, d in trace._ops(trace.devices[0])]


def scope_seconds(op_scopes: Optional[List[List]],
                  bounds: Optional[Interval]) -> Optional[Dict[str, float]]:
    """``{scope: device seconds}`` of the leaf operations inside
    ``bounds`` (``""``: under none of the program's scopes); ``None``
    where no operation carries one of the program's scopes."""
    if not op_scopes:
        return None
    out: Dict[str, float] = {}
    for op, d in _leaf_ops(op_scopes, bounds):
        scope = scope_of(op[0])
        out[scope] = out.get(scope, 0.0) + d / 1e9
    if set(out) <= {UNATTRIBUTED}:
        return None
    return out


def scope_seconds_by_family(trace: tr.Trace, op_scopes: Optional[List[List]],
                            names: bool = False) -> Dict[str, Dict[str, float]]:
    """``{program family: {scope: device seconds}}`` on the first
    chip: each leaf operation goes to the program execution it began
    inside. With ``names`` the operations under no scope only, by
    their own name: which program owns which copy."""
    if not op_scopes or not trace.devices:
        return {}
    runs = sorted(
        (s, s + d, family_of(name))
        for name, s, d in trace._line(trace.devices[0], tr.MODULES_LINE)
    )
    starts = [r[0] for r in runs]
    out: Dict[str, Dict[str, float]] = {}
    for op, d in _leaf_ops(op_scopes, trace.bounds):
        at = max(op[1], trace.bounds[0]) if trace.bounds else op[1]
        i = bisect.bisect_right(starts, at + 1) - 1
        family = runs[i][2] if i >= 0 and at < runs[i][1] else "(no program)"
        key = scope_of(op[0])
        if names:
            if key != UNATTRIBUTED:
                continue
            key = op[3] if len(op) > 3 else ""
        by = out.setdefault(family, {})
        by[key] = by.get(key, 0.0) + d / 1e9
    return out


# -- what the readers of perf/layer_metrics share ---------------------------------


class Report:
    """The three reductions of one traced run, made once."""

    def __init__(self, trace: tr.Trace, iterations: int, updates: float,
                 op_scopes: Optional[List[List]] = None):
        self.trace = trace
        self.iterations = iterations
        self.updates = updates
        self.op_scopes = op_scopes
        self.idle = idle_by_layer(trace)
        self.scopes = scope_seconds(op_scopes, trace.bounds)

    # idle, ms per traced iteration
    def idle_ms(self, prefix: str) -> Optional[float]:
        if self.idle is None or not self.iterations:
            return None
        return self.idle.get(prefix, 0) / 1e6 / self.iterations

    def unattributed_idle_pct(self) -> Optional[float]:
        if self.idle is None or not sum(self.idle.values()):
            return None
        return 100.0 * self.idle[UNATTRIBUTED] / sum(self.idle.values())

    # device time of a program family, ms per `per`
    def family_ms(self, prefix: str, per: float,
                  also_s: float = 0.0) -> Optional[float]:
        seconds = family_seconds(self.trace, prefix)
        if seconds is None or not per:
            return None
        return 1e3 * (seconds + also_s) / per

    # device time under scopes, ms per `per`
    def scope_ms(self, prefix: str, per: float) -> Optional[float]:
        if self.scopes is None or not per:
            return None
        hit = [
            v for k, v in self.scopes.items()
            if (k.startswith(prefix) if prefix else k == UNATTRIBUTED)
        ]
        return 1e3 * sum(hit) / per if hit else None


def report(ctx) -> Optional[Report]:
    """The ``Report`` of a benchmark run's traced span (``ctx`` is
    ``perf.run.Context``), made on first use and kept on the trace;
    ``None`` where the run was not traced."""
    if ctx.trace is None or ctx.traced is None:
        return None
    made = getattr(ctx.trace, "_program_report", None)
    if made is None:
        try:
            op_scopes = load_op_scopes(tr.newest_xplane(
                os.path.join(ctx.cell.root, ".perf_trace")
            ))
        except (OSError, ValueError, IndexError):
            op_scopes = None
        made = Report(
            ctx.trace, len(ctx.traced.walls), ctx.traced.updates(), op_scopes
        )
        ctx.trace._program_report = made
    return made


# -- a cut of whole iterations, for perf/tests ---------------------------------


def cut_iterations(plain: Dict, op_scopes: List[List], first: int,
                   count: int) -> Dict:
    """``count`` whole iterations of a trace, from the start of the
    ``first``-th ``perf:train`` on the main thread: the main thread's
    events, the first chip's program executions and its operations
    (clipped to the cut, named by their ``tf_op``), as the compact
    object ``load_cut`` reads back."""
    host = main_thread_events(plain)
    marks = sorted(
        (s, s + d) for name, s, d in host if name == tr.TRAIN_ANNOTATION
    )[first:first + count]
    lo, hi = marks[0][0], marks[-1][1]
    device = min(
        (p for p in plain["planes"]
         if p["name"].startswith(tr.DEVICE_PLANE_PREFIX)),
        key=lambda p: p["name"],
    )
    names: Dict[str, int] = {}

    def index(name):
        return names.setdefault(name, len(names))

    def clipped(events):
        return [
            [index(ev[0]), max(ev[1], lo),
             min(ev[1] + ev[2], hi) - max(ev[1], lo)]
            + [index(x) for x in ev[3:]]
            for ev in events if ev[1] < hi and ev[1] + ev[2] > lo
        ]

    return {
        "bounds": [lo, hi],
        "iterations": count,
        "host": clipped(
            [n, s, d] for n, s, d in host if s >= lo and s + d <= hi
        ),
        "modules": clipped(tr.Trace._line(device, tr.MODULES_LINE)),
        "ops": clipped(op_scopes),
        "names": list(names),
    }


def load_cut(cut: Dict) -> Tuple[tr.Trace, List[List]]:
    """``(Trace, op_scopes)`` of a ``cut_iterations`` object."""
    names = cut["names"]

    def named(events):
        return [
            [names[ev[0]], ev[1], ev[2]] + [names[i] for i in ev[3:]]
            for ev in events
        ]

    ops = named(cut["ops"])
    plain = {"planes": [
        {"name": tr.DEVICE_PLANE_PREFIX + "0", "lines": [
            {"name": tr.MODULES_LINE, "events": named(cut["modules"])},
            {"name": tr.OPS_LINE, "events": [op[:3] for op in ops]},
        ]},
        {"name": tr.HOST_PLANE_PREFIX + "CPU", "lines": [
            {"name": "main", "events": named(cut["host"])},
        ]},
    ]}
    return tr.Trace(plain, 1, tuple(cut["bounds"])), ops


def summary(rep: Report) -> Dict:
    """Everything a ``Report`` holds, by name: what ``main`` prints
    and what PERF.md's breakdown is written from."""
    trace, n = rep.trace, max(1, rep.iterations)
    by_span = idle_by_span(trace) or {}
    families: Dict[str, float] = {}
    for name, sec in trace.module_seconds().items():
        fam = family_of(name)
        families[fam] = families.get(fam, 0.0) + sec
    return {
        "iterations": rep.iterations,
        "updates": rep.updates,
        "span_ms_per_iter": 1e3 * trace.span_s() / n,
        "busy_ms_per_iter": 1e3 * trace.busy_s() / n,
        "idle_ms_per_iter": 1e3 * (trace.span_s() - trace.busy_s()) / n,
        "module_ms_per_iter": {
            k: 1e3 * v / n
            for k, v in sorted(families.items(), key=lambda kv: -kv[1])
        },
        "module_counts": trace.module_counts(),
        "device_clock_offset_ms": device_clock_offset_ns(trace) / 1e6,
        "idle_ms_per_iter_by_span": {
            k or "(unattributed)": v / 1e6 / n
            for k, v in sorted(by_span.items(), key=lambda kv: -kv[1])
        },
        # of which with no program on the chip at all (the rest is the
        # device's own gaps between the operations of a running program)
        "no_program_idle_ms_per_iter_by_span": {
            k or "(unattributed)": v / 1e6 / n
            for k, v in sorted((idle_by_span(trace, True) or {}).items(),
                               key=lambda kv: -kv[1])
        },
        "idle_ms_per_iter_by_layer": {
            k or "(unattributed)": v / 1e6 / n
            for k, v in sorted((rep.idle or {}).items(),
                               key=lambda kv: -kv[1])
        },
        "unattributed_idle_pct": rep.unattributed_idle_pct(),
        "scope_ms_per_iter": {
            k or "(unscoped)": 1e3 * v / n
            for k, v in sorted((rep.scopes or {}).items(),
                               key=lambda kv: -kv[1])
        },
        "scope_ms_per_iter_by_family": {
            family: {k or "(unscoped)": 1e3 * v / n
                     for k, v in sorted(by.items(), key=lambda kv: -kv[1])}
            for family, by in scope_seconds_by_family(
                trace, rep.op_scopes).items()
        },
        "unscoped_ops_ms_per_iter_by_family": {
            family: {k: 1e3 * v / n for k, v in
                     sorted(by.items(), key=lambda kv: -kv[1])[:6]}
            for family, by in scope_seconds_by_family(
                trace, rep.op_scopes, names=True).items()
            if 1e3 * sum(by.values()) / n >= 0.05
        },
        "tree_update_in_replay_insert_ms_per_iter": 1e3
        * seconds_dispatched_under(trace, "tree_update", "replay:insert") / n,
    }


def main(argv=None) -> int:
    """``python3 -m perf.program_trace <log_dir or .xplane.pb>``: what
    the reductions make of a trace ``perf.run --trace 1`` left, and
    ``--save-cut`` a cut of whole iterations with their readings."""
    import argparse

    parser = argparse.ArgumentParser(prog="python3 -m perf.program_trace")
    parser.add_argument("path")
    parser.add_argument("--chips", type=int, default=1)
    parser.add_argument("--updates-per-iteration", type=float, default=8)
    parser.add_argument("--save-cut", default=None)
    parser.add_argument("--cut-first", type=int, default=2)
    parser.add_argument("--cut-iterations", type=int, default=2)
    args = parser.parse_args(argv)
    path = (args.path if args.path.endswith(".pb")
            else tr.newest_xplane(args.path))
    plain = tr.load_xplane(path)
    op_scopes = load_op_scopes(path)
    bounds = tr.annotation_bounds(plain, tr.TRAIN_ANNOTATION)
    trace = tr.Trace(plain, args.chips, bounds)
    n = sum(1 for ev in main_thread_events(plain)
            if ev[0] == tr.TRAIN_ANNOTATION)
    rep = Report(trace, n, n * args.updates_per_iteration, op_scopes)
    print(json.dumps(summary(rep), indent=1))
    if args.save_cut:
        cut = cut_iterations(
            plain, op_scopes or [], args.cut_first, args.cut_iterations
        )
        small, ops = load_cut(cut)
        cut["updates"] = args.cut_iterations * args.updates_per_iteration
        small_rep = Report(small, cut["iterations"], cut["updates"], ops)
        cut["expected"] = summary(small_rep)
        with open(args.save_cut, "w") as f:
            json.dump(cut, f, separators=(",", ":"))
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
