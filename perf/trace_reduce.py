"""From a profiler trace to numbers.

``load_xplane`` turns the ``.xplane.pb`` the JAX profiler writes into
plain data (``jax.profiler.ProfileData`` needs nothing but JAX):

    {"planes": [{"name": str, "lines": [{"name": str,
                 "events": [[name, start_ns, duration_ns], ...]}]}]}

and ``Trace`` reduces that: device busy time (the union of the
intervals in which an operation ran), per-program and per-operation
device time, collective time not hidden behind compute, and the
longest idle gaps with what the host was doing in them. The
reductions work on the plain form, so ``perf/tests`` checks them on a
small recorded trace kept beside the tests.

What a v5e trace holds (looked at by hand, PR 24): one plane per chip
named ``/device:TPU:<n>``; on it the line ``XLA Modules`` has one
event per program execution (``jit__counted(<fingerprint>)`` — the
program's ``sharded_jit`` wrapper gives every program the same
function name, so programs are told apart by fingerprint, not by
label: PERF.md Open questions) and the line ``XLA Ops`` one event per
operation; host threads are lines of the ``/host:CPU`` plane.
"""

from __future__ import annotations

import glob
import os
import shutil
from typing import Dict, Iterable, List, Optional, Tuple

Event = Tuple[str, int, int]  # name, start_ns, duration_ns

DEVICE_PLANE_PREFIX = "/device:TPU:"
HOST_PLANE_PREFIX = "/host:"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
TRAIN_ANNOTATION = "perf:train"  # what perf/run.py wraps each train() in
# operation names that are communication between chips
COLLECTIVE_PARTS = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)


def load_xplane(path: str, keep_host_events: int = 20000) -> Dict:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        host = plane.name.startswith(HOST_PLANE_PREFIX)
        if not (device or host):
            continue
        lines = []
        for line in plane.lines:
            events = [
                [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                for ev in line.events
            ]
            if host:
                # the longest host events say what the host was at
                events.sort(key=lambda e: -e[2])
                events = sorted(events[:keep_host_events], key=lambda e: e[1])
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def short_op_name(name: str) -> str:
    """An ``XLA Ops`` event is named by its whole HLO line. Keep the
    instruction's name, its opcode and its first result shape:
    ``%copy.9 copy u32[524288,1764]``."""
    if " = " not in name:
        return name[:120]
    lhs, rhs = name.split(" = ", 1)
    shape = rhs.lstrip("(").split("{", 1)[0].split(" ", 1)[0]
    after = rhs.split(") ", 1)[1] if rhs.startswith("(") and ") " in rhs else (
        rhs.split(" ", 1)[1] if " " in rhs else ""
    )
    opcode = after.split("(", 1)[0].strip()
    return f"{lhs} {opcode} {shape}"[:120]


def union_ns(intervals: Iterable[Tuple[int, int]]) -> int:
    """Total length covered by ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def subtract_ns(intervals: List[Tuple[int, int]],
                cover: List[Tuple[int, int]]) -> int:
    """Length of ``intervals`` (their union) NOT covered by ``cover``."""
    both = union_ns(list(intervals) + list(cover))
    return both - union_ns(cover)


class Trace:
    def __init__(self, plain: Dict, chips: Optional[int] = None,
                 bounds: Optional[Tuple[int, int]] = None):
        """``bounds`` (ns, on the trace's own clock) keeps the part of
        every device event inside them and makes them the span."""
        if bounds is not None:
            plain = _clip(plain, *bounds)
        self.bounds = bounds
        self.plain = plain
        self.devices = [
            p for p in plain["planes"]
            if p["name"].startswith(DEVICE_PLANE_PREFIX)
        ]
        if chips is not None:
            self.devices = sorted(self.devices, key=lambda p: p["name"])[:chips]
        self.hosts = [
            p for p in plain["planes"]
            if p["name"].startswith(HOST_PLANE_PREFIX)
        ]

    # -- lines -----------------------------------------------------------

    @staticmethod
    def _line(plane: Dict, name: str) -> List[Event]:
        for line in plane["lines"]:
            if line["name"] == name:
                return line["events"]
        return []

    def _ops(self, plane: Dict) -> List[Event]:
        """Leaf operations: an op event that encloses others (a
        ``while`` around its body) is a container, not work itself."""
        events = sorted(self._line(plane, OPS_LINE), key=lambda e: (e[1], -e[2]))
        marked: List[List] = []  # [event, encloses another]
        stack: List[List] = []
        for ev in events:
            end = ev[1] + ev[2]
            while stack and end > stack[-1][0][1] + stack[-1][0][2]:
                stack.pop()  # ends after it: not inside it
            if stack:
                stack[-1][1] = True
            entry = [ev, False]
            stack.append(entry)
            marked.append(entry)
        return [ev for ev, encloses in marked if not encloses]

    # -- spans -----------------------------------------------------------

    def span_ns(self) -> Tuple[int, int]:
        """The traced span: first start to last end of any program
        execution on any chip (operations where a trace has no
        ``XLA Modules`` line)."""
        if self.bounds is not None:
            return self.bounds
        starts, ends = [], []
        for plane in self.devices:
            evs = self._line(plane, MODULES_LINE) or self._line(plane, OPS_LINE)
            for _, s, d in evs:
                starts.append(s)
                ends.append(s + d)
        if not starts:
            return (0, 0)
        return (min(starts), max(ends))

    def span_s(self) -> float:
        s, e = self.span_ns()
        return (e - s) / 1e9

    def busy_s(self) -> float:
        """Seconds in which an operation ran on the device, mean over
        the chips."""
        if not self.devices:
            return 0.0
        total = 0
        for plane in self.devices:
            evs = self._line(plane, OPS_LINE) or self._line(plane, MODULES_LINE)
            total += union_ns((s, s + d) for _, s, d in evs)
        return total / len(self.devices) / 1e9

    def idle_share(self) -> float:
        span = self.span_s()
        return 1.0 - self.busy_s() / span if span > 0 else 0.0

    # -- per program / per op ---------------------------------------------

    def module_seconds(self) -> Dict[str, float]:
        """Device seconds per program (event name on ``XLA Modules``),
        mean over the chips."""
        out: Dict[str, float] = {}
        for plane in self.devices:
            for name, _, d in self._line(plane, MODULES_LINE):
                out[name] = out.get(name, 0.0) + d
        n = max(1, len(self.devices))
        return {k: v / n / 1e9 for k, v in out.items()}

    def module_counts(self) -> Dict[str, int]:
        plane = self.devices[0] if self.devices else {"lines": []}
        out: Dict[str, int] = {}
        for name, _, _ in self._line(plane, MODULES_LINE):
            out[name] = out.get(name, 0) + 1
        return out

    def op_seconds(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for plane in self.devices:
            for name, _, d in self._ops(plane):
                name = short_op_name(name)
                out[name] = out.get(name, 0.0) + d
        n = max(1, len(self.devices))
        return {k: v / n / 1e9 for k, v in out.items()}

    def op_seconds_matching(self, parts: Iterable[str]) -> float:
        parts = tuple(parts)
        return sum(
            v for k, v in self.op_seconds().items()
            if any(p in k for p in parts)
        )

    def exposed_collective_s(self) -> float:
        """Seconds of collective operations during which no other
        operation ran on that chip, mean over the chips."""
        if not self.devices:
            return 0.0
        total = 0
        for plane in self.devices:
            coll, compute = [], []
            for name, s, d in self._ops(plane):
                (coll if any(p in name for p in COLLECTIVE_PARTS)
                 else compute).append((s, s + d))
            total += subtract_ns(coll, compute)
        return total / len(self.devices) / 1e9

    # -- idle gaps ---------------------------------------------------------

    def idle_gaps(self, top: int = 10) -> List[List]:
        """The longest intervals in which the first chip ran nothing,
        each named by the host event that overlapped it longest."""
        if not self.devices:
            return []
        plane = self.devices[0]
        evs = sorted(
            (s, s + d)
            for _, s, d in (self._line(plane, OPS_LINE)
                            or self._line(plane, MODULES_LINE))
        )
        gaps, cur_e = [], None
        if self.bounds is not None and evs:
            # the host's span starts before the first operation and
            # ends after the last: those edges are idle time too
            lo, hi = self.bounds
            last = max(e for _, e in evs)
            gaps += [(evs[0][0] - lo, lo, evs[0][0]), (hi - last, last, hi)]
            gaps = [g for g in gaps if g[0] > 0]
        for s, e in evs:
            if cur_e is not None and s > cur_e:
                gaps.append((s - cur_e, cur_e, s))
            cur_e = e if cur_e is None else max(cur_e, e)
        gaps.sort(reverse=True)
        host_events = [
            ev for p in self.hosts for line in p["lines"]
            for ev in line["events"]
        ]
        out = []
        for length, gs, ge in gaps[:top]:
            # the shortest host event that covers at least half the
            # gap says most exactly what the host was at; failing
            # that, the one that overlaps it longest
            best, best_key = "host_untraced", None
            for name, s, d in host_events:
                overlap = min(ge, s + d) - max(gs, s)
                if overlap <= 0:
                    continue
                key = (0, d) if 2 * overlap >= length else (1, -overlap)
                if best_key is None or key < best_key:
                    best, best_key = name, key
            out.append([best, length / 1e9])
        return out

    def breakdown(self, top: int = 10) -> Dict[str, List[List]]:
        ops = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])[:top]
        return {
            "device_ops": [[k, v] for k, v in ops],
            "idle_gaps": self.idle_gaps(top),
        }


def _clip(plain: Dict, lo: int, hi: int) -> Dict:
    planes = []
    for plane in plain["planes"]:
        if not plane["name"].startswith(DEVICE_PLANE_PREFIX):
            planes.append(plane)
            continue
        lines = []
        for line in plane["lines"]:
            events = [
                [n, max(s, lo), min(s + d, hi) - max(s, lo)]
                for n, s, d in line["events"]
                if s < hi and s + d > lo
            ]
            lines.append({"name": line["name"], "events": events})
        planes.append({"name": plane["name"], "lines": lines})
    return {"planes": planes}


def annotation_bounds(plain: Dict, name: str) -> Optional[Tuple[int, int]]:
    """First start to last end of the host events called ``name``."""
    spans = [
        (s, s + d)
        for p in plain["planes"] if p["name"].startswith(HOST_PLANE_PREFIX)
        for line in p["lines"] for n, s, d in line["events"] if n == name
    ]
    if not spans:
        return None
    return (min(s for s, _ in spans), max(e for _, e in spans))


def newest_xplane(log_dir: str) -> str:
    files = glob.glob(
        os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def traced_span(algo, iterations: int, measure, expect, log_dir: str,
                chips: Optional[int] = None):
    """Trace ``iterations`` whole ``Algorithm.train()`` calls with the
    JAX profiler (Python tracer off; the benchmark's own
    ``perf:train`` annotation marks each call on the host's clock).
    The span is the host's: from the start of the first annotated call
    to the end of the last. Returns ``(Trace, Window over the span)``."""
    import jax

    shutil.rmtree(log_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        # the profiler's own start-up stalls the first traced call for
        # seconds: let one iteration absorb it, outside the span
        measure(algo, float("inf"), expect, max_iterations=1)
        window = measure(
            algo, float("inf"), expect,
            annotate=jax.profiler.TraceAnnotation, max_iterations=iterations,
        )
    finally:
        jax.profiler.stop_trace()
    plain = load_xplane(newest_xplane(log_dir))
    trace = Trace(plain, chips, annotation_bounds(plain, TRAIN_ANNOTATION))
    return trace, window


def describe(plain: Dict, top: int = 12) -> Dict:
    """What a trace holds, for a look by hand: every plane and line
    with its event count and its longest-running event names."""
    out = []
    for plane in plain["planes"]:
        lines = []
        for line in plane["lines"]:
            by_name: Dict[str, List[float]] = {}
            for name, _, d in line["events"]:
                rec = by_name.setdefault(name, [0, 0.0])
                rec[0] += 1
                rec[1] += d / 1e9
            names = sorted(by_name.items(), key=lambda kv: -kv[1][1])[:top]
            lines.append({
                "line": line["name"],
                "events": len(line["events"]),
                "top": [[k, v[0], v[1]] for k, v in names],
            })
        out.append({"plane": plane["name"], "lines": lines})
    return {"planes": out}


def cut(plain: Dict, max_ops: int = 600) -> Dict:
    """A small piece of a trace, for keeping beside the tests: the
    first chip's first ``max_ops`` operations, the program executions
    and the host events that overlap them."""
    device = next(
        p for p in plain["planes"] if p["name"].startswith(DEVICE_PLANE_PREFIX)
    )
    ops = sorted(Trace._line(device, OPS_LINE), key=lambda e: e[1])[:max_ops]
    lo, hi = ops[0][1], max(s + d for _, s, d in ops)

    def inside(events):
        return [
            [n, s, d] for n, s, d in events if s >= lo and s + d <= hi
        ]

    planes = [{"name": device["name"], "lines": [
        {"name": OPS_LINE, "events": ops},
        {"name": MODULES_LINE,
         "events": inside(Trace._line(device, MODULES_LINE))},
    ]}]
    for p in plain["planes"]:
        if p["name"].startswith(HOST_PLANE_PREFIX):
            lines = [
                {"name": line["name"], "events": inside(line["events"])[:200]}
                for line in p["lines"]
            ]
            planes.append(
                {"name": p["name"], "lines": [ln for ln in lines if ln["events"]]}
            )
    return {"planes": planes}


def main(argv=None) -> int:
    """``python3 -m perf.trace_reduce <log_dir or .xplane.pb>``: print
    what the trace holds and what the reductions make of it."""
    import argparse
    import json

    parser = argparse.ArgumentParser(prog="python3 -m perf.trace_reduce")
    parser.add_argument("path")
    parser.add_argument("--save-cut", default=None,
                        help="also write a small cut of the plain form "
                        "with what the reductions make of it (JSON) here")
    args = parser.parse_args(argv)
    path = args.path if args.path.endswith(".pb") else newest_xplane(args.path)
    plain = load_xplane(path)
    trace = Trace(plain)
    print(json.dumps(describe(plain), indent=1))
    print(json.dumps({
        "span_s": trace.span_s(), "busy_s": trace.busy_s(),
        "idle_share": trace.idle_share(),
        "modules": trace.module_seconds(),
        "module_counts": trace.module_counts(),
        "exposed_collective_s": trace.exposed_collective_s(),
        "breakdown": trace.breakdown(),
    }, indent=1))
    if args.save_cut:
        small = cut(plain)
        t = Trace(small)
        modules = t.module_seconds()
        top = max(modules.items(), key=lambda kv: kv[1]) if modules else ("", 0.0)
        with open(args.save_cut, "w") as f:
            json.dump({
                "plain": small,
                "expected": {"span_s": t.span_s(), "busy_s": t.busy_s(),
                             "top_module": top[0], "top_module_s": top[1]},
            }, f)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
