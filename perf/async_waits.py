"""Where the chip waits for the compiler's asynchronous copies.

The compiler puts ``copy-start``/``copy-done``, ``slice-start``/
``slice-done`` and ``async-start``/``async-done`` pairs into a
program; the device time of a ``*-done`` is time the chip WAITS. Those
operations carry none of the program's scopes, so until PR 52 they
lay in ``device.unscoped_device_ms_per_iter`` beside true layout
copies, and a prefetch that the compiler moved between "no scope" and
``rollout/act`` moved two metrics with nothing to say it was one thing.

Two sources, joined by the operation's name inside its program family:

- the trace (``perf/program_trace.load_op_scopes``' rows): which
  ``while`` event encloses each leaf operation in time
  (``trace_reduce.Trace._ops`` finds that to drop the containers and
  throws it away; ``nest`` keeps it), when each start ended and each
  done began;
- the program's own table (``ray_tpu.sharding.compile.async_pairs``,
  made from its compiled text when asked): the done's start, bytes,
  consumer, source and room. A program from before PR 52 has no such
  function: the placement by enclosing loop alone serves there.

Every leaf operation gets a LAYER (``rollout``, ``learn``, ``replay``,
``gae``) in this order: the program's scope on its own ``tf_op`` path;
the nearest enclosing loop that has a layer; for a ``*-done``, the
scope of the instruction that consumes it, by the table. What none of
the three places is UNPLACED. A loop's layer is the scope on its own
path, or the one layer that holds nine tenths of the scoped device time
directly inside it: the rollout lane's ``while`` is
``jit(rollout_superstep)/while/body/closed_call/while`` and names no
scope itself. The trace gives a ``while`` event a name (``%while.859``)
and no ``tf_op``; its path is the table's (the ``under`` chain of any
row inside it names the same instruction), so a loop has one name in
both places.

For a done whose start the table names: exposed = the done's device
time; hidden = from the start's end to the done's begin; rate = bytes
/ (hidden + exposed). ``main`` prints them by loop and consumer with a
reading of the rate (a copy's own bandwidth, a start issued too late,
a transfer queued behind others); that reading is a table for a
person, not a metric.
"""

from __future__ import annotations

import bisect
import functools
import json
import os
import time
from typing import Any, Dict, Iterable, List, Optional, Tuple

from perf import program_trace as pt
from perf import trace_reduce as tr

TABLE_FILE = "async_pairs.json"  # beside the trace, for ``main``
# a loop belongs to the layer that holds this share of the scoped
# device time directly inside it
_OWNS = 0.9
LAYERS = {"rollout": "rollout", "learn": "learn", "sgd_nest": "learn",
          "replay": "replay", "gae": "gae"}


@functools.lru_cache(maxsize=65536)  # a trace repeats a few thousand paths
def layer_of(path: str) -> str:
    """``rollout`` / ``learn`` / ``replay`` / ``gae`` of the innermost
    program scope on a ``tf_op`` or ``op_name`` path (the learn nest's
    own frames count as ``learn``); ``""`` where it holds none."""
    scope = pt.scope_of(path)
    return LAYERS.get(scope.split("/", 1)[0], "") if scope else ""


def instruction_of(op_name: str) -> str:
    """``%copy-done.294 copy-done f32[2560]`` -> ``copy-done.294``."""
    return op_name.split(" ", 1)[0].lstrip("%")


def is_done(op_name: str) -> bool:
    parts = op_name.split(" ")
    return len(parts) > 1 and parts[1].endswith("-done")


# -- the trace's side: which loop encloses which operation -------------------


class Leaf:
    """One leaf operation of the traced span."""

    __slots__ = ("op", "start", "ns", "loops", "family", "layer", "by",
                 "row")

    def __init__(self, op, start, ns, loops):
        self.op = op  # [tf_op, start_ns, duration_ns, name]
        self.start = start
        self.ns = ns  # inside the bounds
        self.loops: Tuple[int, ...] = loops  # enclosing, outermost first
        self.family = ""
        self.layer = ""
        self.by = ""  # "scope" | "loop" | "consumer" | ""
        self.row: Optional[Dict[str, Any]] = None

    @property
    def name(self) -> str:
        return self.op[3] if len(self.op) > 3 else ""


def nest(op_scopes: List[List], bounds: Optional[Tuple[int, int]]):
    """``(leaves, containers)``: every leaf operation with the chain
    of operations that enclose it in time, and those enclosing
    operations themselves (``{index: op}``). The rule is
    ``trace_reduce.Trace._ops``': an operation that another lies
    inside is a container, not work."""
    events = []
    for i, op in enumerate(op_scopes):
        s, e = op[1], op[1] + op[2]
        if bounds is not None:
            if s >= bounds[1] or e <= bounds[0]:
                continue
            s, e = max(s, bounds[0]), min(e, bounds[1])
        events.append((s, -(e - s), i))
    events.sort()
    stack: List[List] = []  # [index, end, encloses]
    marked: List[Tuple[int, float, float, Tuple[int, ...], List]] = []
    for s, neg, i in events:
        end = s - neg
        while stack and end > stack[-1][1]:
            stack.pop()
        if stack:
            stack[-1][2] = True
        entry = [i, end, False]
        marked.append((i, s, -neg, tuple(x[0] for x in stack), entry))
        stack.append(entry)
    leaves = [
        Leaf(op_scopes[i], s, d, chain)
        for i, s, d, chain, entry in marked if not entry[2]
    ]
    containers = {
        i: op_scopes[i] for i, _, _, _, entry in marked if entry[2]
    }
    return leaves, containers


def loop_layers(leaves: Iterable[Leaf], containers: Dict[int, List],
                paths: Optional[Dict[int, str]] = None) -> Dict[int, str]:
    """``{container index: layer}``: the scope on the loop's own path
    (``paths``, else its ``tf_op``), else the one layer that holds
    ``_OWNS`` of the scoped device time directly inside it; ``""``
    where neither names it."""
    inside: Dict[int, Dict[str, float]] = {}
    for leaf in leaves:
        own = layer_of(leaf.op[0])
        if own and leaf.loops:
            by = inside.setdefault(leaf.loops[-1], {})
            by[own] = by.get(own, 0.0) + leaf.ns
    out: Dict[int, str] = {}
    for index, op in containers.items():
        layer = layer_of((paths or {}).get(index) or op[0])
        if not layer:
            by = inside.get(index) or {}
            total = sum(by.values())
            best = max(by, key=by.get) if by else ""
            if best and by[best] >= _OWNS * total:
                layer = best
        out[index] = layer
    return out


# -- the join ---------------------------------------------------------------


class Waits:
    """Every leaf operation of a traced span placed, and the waits."""

    def __init__(self, trace: tr.Trace, op_scopes: List[List],
                 tables: Optional[Dict[str, Dict[str, Dict]]] = None):
        self.leaves, self.containers = nest(op_scopes, trace.bounds)
        self._runs = sorted(
            (s, s + d, pt.family_of(name))
            for name, s, d in (
                trace._line(trace.devices[0], tr.MODULES_LINE)
                if trace.devices else []
            )
        )
        self._begins = [r[0] for r in self._runs]
        starts: Dict[Tuple[str, str], List[float]] = {}
        for leaf in self.leaves:
            leaf.family = self._family_at(leaf.start)
            parts = leaf.name.split(" ")
            if len(parts) > 1 and parts[1].endswith("-start"):
                starts.setdefault(
                    (leaf.family, instruction_of(leaf.name)), []
                ).append(leaf.op[1] + leaf.op[2])
        self.start_ends = {k: sorted(v) for k, v in starts.items()}
        self.place(tables or {})

    def place(self, tables: Dict[str, Dict[str, Dict]]) -> None:
        """Give every leaf its layer, with the programs' ``tables``
        (``{family: {instruction name: row}}``, ``{}`` for none)."""
        self.tables = tables
        self.paths = self._loop_paths()
        self.layers = loop_layers(self.leaves, self.containers, self.paths)
        for leaf in self.leaves:
            self._place(leaf)

    def _family_at(self, start: float) -> str:
        """The family of the program execution an operation began in."""
        i = bisect.bisect_right(self._begins, start + 1) - 1
        if i >= 0 and start < self._runs[i][1]:
            return self._runs[i][2]
        return ""

    def _loop_paths(self) -> Dict[int, str]:
        """``{container index: its path}``: the event's own ``tf_op``
        where the trace gives one, else the ``op_name`` the table's
        ``under`` chains give the instruction of that name."""
        named: Dict[Tuple[str, str], str] = {}
        for family, rows in self.tables.items():
            for row in rows.values():
                for level in row.get("under") or ():
                    if level.get("op_name"):
                        named[(family, level["name"])] = level["op_name"]
        out: Dict[int, str] = {}
        for index, op in self.containers.items():
            path = op[0].rstrip(":")
            if not path and len(op) > 3:
                path = named.get(
                    (self._family_at(op[1]), instruction_of(op[3])), ""
                )
            out[index] = path
        return out

    def _place(self, leaf: Leaf) -> None:
        leaf.layer, leaf.by, leaf.row = "", "", None
        if is_done(leaf.name):
            leaf.row = (self.tables.get(leaf.family) or {}).get(
                instruction_of(leaf.name)
            )
        layer = layer_of(leaf.op[0])
        if layer:
            leaf.layer, leaf.by = layer, "scope"
            return
        for index in reversed(leaf.loops):
            if self.layers.get(index):
                leaf.layer, leaf.by = self.layers[index], "loop"
                return
        if leaf.row is not None:
            for path in leaf.row.get("consumers") or ():
                layer = layer_of(path)
                if layer:
                    leaf.layer, leaf.by = layer, "consumer"
                    return

    # -- the four metrics' sums, in ns ------------------------------------

    def dones(self) -> List[Leaf]:
        return [leaf for leaf in self.leaves if is_done(leaf.name)]

    def exposed_ns(self, layer: Optional[str] = None) -> float:
        return sum(
            leaf.ns for leaf in self.dones()
            if layer is None or leaf.layer == layer
        )

    def unscoped_ns(self) -> float:
        return sum(leaf.ns for leaf in self.leaves if leaf.by != "scope")

    def unplaced(self) -> List[Leaf]:
        return [leaf for leaf in self.leaves if not leaf.by]

    def unplaced_ns(self) -> float:
        return sum(leaf.ns for leaf in self.unplaced())

    # -- a pair's exposed, hidden and rate ------------------------------------

    def loop_path(self, leaf: Leaf) -> str:
        """The path of the innermost ``while`` around a leaf; its name
        as the trace prints it (``%while.859``) where no table gives
        the path; ``""`` for a leaf under none."""
        for index in reversed(leaf.loops):
            op = self.containers[index]
            name = op[3] if len(op) > 3 else ""
            if " while " in name or name.startswith("%while"):
                return self.paths.get(index) or name.split(" ", 1)[0]
        return ""

    def pairs(self) -> List[Dict[str, Any]]:
        """One row a done operation (its executions added up):
        ``exposed_ns``, ``hidden_ns`` (``None`` where the table names
        no start or the trace holds none), ``n``, and the table's
        row."""
        out: Dict[Tuple[str, str], Dict[str, Any]] = {}
        for leaf in self.dones():
            key = (leaf.family, instruction_of(leaf.name))
            rec = out.get(key)
            if rec is None:
                rec = out[key] = {
                    "family": leaf.family, "name": key[1],
                    "op": leaf.name, "layer": leaf.layer, "by": leaf.by,
                    "loop": self.loop_path(leaf), "n": 0,
                    "exposed_ns": 0.0, "hidden_ns": None, "row": leaf.row,
                }
            rec["n"] += 1
            rec["exposed_ns"] += leaf.ns
            start = (leaf.row or {}).get("start")
            ends = self.start_ends.get((leaf.family, start)) if start else None
            if ends:
                i = bisect.bisect_right(ends, leaf.op[1]) - 1
                if i >= 0:
                    rec["hidden_ns"] = (
                        (rec["hidden_ns"] or 0.0) + leaf.op[1] - ends[i]
                    )
        return sorted(out.values(), key=lambda r: -r["exposed_ns"])


def reading(rec: Dict[str, Any], peak_bytes_per_s: float) -> str:
    """What a pair's rate says: ``bandwidth`` where it moves its bytes
    at a quarter of the memory's peak or more (a whole-buffer copy
    reaches 320-650 GB/s of the v5e's 819: PERF.md section 5); under
    that, ``late start`` where the scheduler left it a room of at most
    eight instructions, else ``queued`` (issued early and still waited
    for: behind other transfers)."""
    row = rec.get("row") or {}
    rate = rate_of(rec)
    if rate is None:
        return "-"
    if rate >= peak_bytes_per_s / 4.0:
        return "bandwidth"
    room = row.get("room")
    return "late start" if room is not None and room <= 8 else "queued"


def rate_of(rec: Dict[str, Any]) -> Optional[float]:
    """Bytes a second over hidden + exposed, all executions."""
    row = rec.get("row") or {}
    if rec.get("hidden_ns") is None or not row.get("bytes"):
        return None
    ns = rec["hidden_ns"] + rec["exposed_ns"]
    return row["bytes"] * rec["n"] / (ns / 1e9) if ns > 0 else None


# -- what the readers of perf/layer_metrics share -------------------------------


def tables_for(families: Iterable[str]) -> Dict[str, Dict[str, Dict]]:
    """``{family: {instruction name: row}}`` from the program's own
    table; ``{}`` for a program that has no such function."""
    from ray_tpu.sharding import compile as compile_lib

    ask = getattr(compile_lib, "async_pairs", None)
    if ask is None:
        return {}
    out: Dict[str, Dict[str, Dict]] = {}
    for family in families:
        rows: Dict[str, Dict] = {}
        try:
            made = ask(family)
        except Exception as e:  # a reader must not fail the run
            print(f"[async-pairs] no table of {family}: {e!r}", flush=True)
            continue
        for label_rows in made.values():
            for row in label_rows:
                rows.setdefault(row["name"], row)
        if rows:
            out[family] = rows
    return out


def waits(ctx) -> Optional[Waits]:
    """The ``Waits`` of a benchmark run's traced span, made on first
    use and kept on the trace; ``None`` where the run was not traced
    or the trace names none of the program's scopes. The table is
    asked for here, after the window and the trace, for the program
    families that ran a ``*-done`` inside the span, and left beside
    the trace for ``main``."""
    rep = pt.report(ctx)
    if rep is None or rep.scopes is None or not rep.op_scopes:
        return None
    made = getattr(ctx.trace, "_async_waits", None)
    if made is None:
        began = time.perf_counter()
        made = Waits(ctx.trace, rep.op_scopes)
        families = sorted({leaf.family for leaf in made.dones() if leaf.family})
        clock = time.perf_counter()
        tables = tables_for(families)
        seconds = time.perf_counter() - clock
        if tables:
            made.place(tables)
        print(
            f"[async-pairs] table of {families} in {seconds:.2f} s: "
            f"{sum(len(t) for t in tables.values())} pairs; placed in "
            f"{time.perf_counter() - began - seconds:.2f} s", flush=True,
        )
        try:
            with open(os.path.join(ctx.cell.root, ".perf_trace", TABLE_FILE),
                      "w") as f:
                json.dump(tables, f, separators=(",", ":"))
        except OSError:
            pass
        ctx.trace._async_waits = made
    # what the readers divide by
    made.iterations, made.updates = rep.iterations, rep.updates
    return made


def lane_steps(ctx) -> int:
    """Steps of the rollout lane's loop a fragment: its env steps, or
    its blocks where the model commits a block a step."""
    steps = int(ctx.cell.traffic["algo_config"]["rollout_fragment_length"])
    lm = (ctx.cell.config.get("algo_config", {}).get("model") or {}).get(
        "sequence_lm") or {}
    return max(1, steps // int(lm.get("block_length", 1)))


# -- the printed account ----------------------------------------------------------


def summary(w: Waits, iterations: int, peak_bytes_per_s: float,
            top: int = 3) -> Dict[str, Any]:
    """What ``main`` prints: the four sums a traced iteration, the
    waits by loop and consumer (``top`` each) and the largest unplaced
    operations."""
    n = max(1, iterations)
    groups: Dict[Tuple[str, str], List[Dict]] = {}
    for rec in w.pairs():
        consumers = (rec["row"] or {}).get("consumers") or []
        consumer = consumer_scope(consumers[0]) if consumers else "-"
        groups.setdefault((rec["loop"] or "(entry)", consumer), []).append(rec)
    by_loop = []
    for (loop, consumer), recs in sorted(
        groups.items(), key=lambda kv: -sum(r["exposed_ns"] for r in kv[1])
    ):
        by_loop.append({
            "loop": loop, "consumer": consumer,
            "layer": recs[0]["layer"] or "(unplaced)",
            "exposed_ms_per_iter": sum(r["exposed_ns"] for r in recs) / 1e6 / n,
            "pairs": [_pair_line(r, n, peak_bytes_per_s) for r in recs[:top]],
        })
    unplaced: Dict[str, float] = {}
    for leaf in w.unplaced():
        key = " ".join(leaf.name.split(" ")[1:]) or leaf.name
        unplaced[key] = unplaced.get(key, 0.0) + leaf.ns
    return {
        "iterations": iterations,
        "exposed_wait_ms_per_iter": w.exposed_ns() / 1e6 / n,
        "exposed_wait_ms_per_iter_by_layer": {
            layer or "(unplaced)": w.exposed_ns(layer) / 1e6 / n
            for layer in sorted({leaf.layer for leaf in w.dones()})
        },
        "unscoped_ms_per_iter": w.unscoped_ns() / 1e6 / n,
        "unplaced_ms_per_iter": w.unplaced_ns() / 1e6 / n,
        "waits_by_loop_and_consumer": by_loop,
        "unplaced_ops_ms_per_iter": {
            k: v / 1e6 / n for k, v in
            sorted(unplaced.items(), key=lambda kv: -kv[1])[:12]
        },
    }


def consumer_scope(path: str) -> str:
    """A consumer's path from the program's scope on, its primitive
    left off: ``.../rollout/act/head/add`` -> ``rollout/act/head``;
    the last two frames of a path under no scope."""
    scope = pt.scope_of(path)
    parts = path.rstrip(":").split("/")
    if not scope:
        return "/".join(parts[-2:])
    tail = path[path.rfind(scope):].rstrip(":").split("/")
    kept = tail[:-1] if len(tail) > scope.count("/") + 1 else tail
    for i, frame in enumerate(kept):  # jit(...), an einsum's "td,edf->tef"
        if i > scope.count("/") and ("(" in frame or ">" in frame):
            kept = kept[:i]
            break
    return "/".join(kept).strip("()")


def _pair_line(rec: Dict, n: int, peak_bytes_per_s: float) -> Dict[str, Any]:
    row = rec["row"] or {}
    rate = rate_of(rec)
    return {
        "op": rec["op"], "by": rec["by"] or "-", "n_per_iter": rec["n"] / n,
        "exposed_ms_per_iter": rec["exposed_ns"] / 1e6 / n,
        "hidden_ms_per_iter": (
            None if rec["hidden_ns"] is None else rec["hidden_ns"] / 1e6 / n
        ),
        "bytes": row.get("bytes"), "room": row.get("room"),
        "hoisted": row.get("hoisted"),
        "rate_gb_per_s": None if rate is None else rate / 1e9,
        "reading": reading(rec, peak_bytes_per_s),
        "source": row.get("source"),
    }


def main(argv=None) -> int:
    """``python3 -m perf.async_waits <log_dir or cut>``: the waits of
    a trace ``perf.run --trace 1`` left (its table is
    ``<log_dir>/async_pairs.json``), or of a cut ``--save-cut`` wrote,
    by loop and consumer."""
    import argparse

    from perf import flops

    parser = argparse.ArgumentParser(prog="python3 -m perf.async_waits")
    parser.add_argument("path")
    parser.add_argument("--top", type=int, default=3)
    parser.add_argument("--save-cut", default=None,
                        help="write one whole iteration with its table "
                        "and this account, for perf/tests")
    args = parser.parse_args(argv)
    if args.path.endswith(".json"):
        if args.save_cut:
            parser.error("--save-cut cuts a trace, not a cut")
        with open(args.path) as f:
            cut = json.load(f)
        trace, op_scopes = pt.load_cut(cut)
        iterations = int(cut["iterations"])
        tables = cut.get("tables") or {}
    else:
        plain = tr.load_xplane(tr.newest_xplane(args.path))
        op_scopes = pt.load_op_scopes(tr.newest_xplane(args.path)) or []
        trace = tr.Trace(
            plain, 1, tr.annotation_bounds(plain, tr.TRAIN_ANNOTATION)
        )
        iterations = sum(1 for ev in pt.main_thread_events(plain)
                         if ev[0] == tr.TRAIN_ANNOTATION)
        tables = {}
        beside = os.path.join(args.path, TABLE_FILE)
        if os.path.isfile(beside):
            with open(beside) as f:
                tables = json.load(f)
    peak = flops.load_peaks("TPU v5 lite")["hbm_bytes_per_s"]
    w = Waits(trace, op_scopes, tables)
    out = summary(w, iterations, peak, args.top)
    print(json.dumps(out, indent=1))
    if args.save_cut:
        cut = pt.cut_iterations(plain, op_scopes, 0, 1)
        small, ops = pt.load_cut(cut)
        held = {instruction_of(op[3]) for op in ops if len(op) > 3}
        cut["tables"] = {
            fam: {k: v for k, v in rows.items()
                  if k in held or v.get("start") in held}
            for fam, rows in tables.items()
        }
        cut["expected"] = summary(
            Waits(small, ops, cut["tables"]), 1, peak, args.top
        )
        with open(args.save_cut, "w") as f:
            json.dump(cut, f, separators=(",", ":"))
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
