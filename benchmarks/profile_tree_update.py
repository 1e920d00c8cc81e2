"""The replay tree's update alone on the chip, by operation and level width.

    chiprun -- env PYTHONPATH=. python benchmarks/profile_tree_update.py [capacity]
    PYTHONPATH=. python benchmarks/profile_tree_update.py --trace <.perf_trace | .xplane.pb | cut.json>

A 131,072-leaf ``DeviceSumTree`` filled with seeded priorities, then the
two updates the DQN cell dispatches every iteration, ``(1, 512)`` (the
insert's new leaves) and ``(8, 512)`` (the superstep's refreshed
leaves), each traced alone over 5 calls. Prints one JSON line a shape:
device microseconds a call, every device operation of one call by name
(``[us a call, count a call, name | scope]``: a name carries its output
shape, so a level of the rebuild reads as its width), the same summed
by operation kind and width (``by_width``: a rebuild that pays for its
levels halves down each row; PR 37 found one that paid 90 us at EVERY
width, 64 relayouts of the whole array), and all ``2 x capacity`` nodes
of both trees afterwards: their largest relative distance from the
host's ``SumSegmentTree`` / ``MinSegmentTree`` (the chip carries an f64
as two 32-bit halves, 48 bits of mantissa: 1e-15 there, exactly 0 on
the CPU, where the tests hold it) and a digest of their bytes, so that
two bodies run on the same seeded stream can be compared bit for bit.
TPU only: a time from another backend is not a device time.

``--trace`` reads a trace ``perf.run --trace 1`` left (or a cut that
``perf.program_trace --save-cut`` kept) and prints the same table for
the execution of median length of each ``jit_tree_update`` program in it.
"""

from __future__ import annotations

import collections
import glob
import hashlib
import json
import re
import sys
import tempfile

import numpy as np

from perf import program_trace
from perf import trace_reduce as tr

SHAPES = ((1, 512), (8, 512))
CALLS = 5
FAMILY = "jit_tree_update"


def by_operation(ops, lo, hi, calls=1):
    """``(rows, by_width)`` of the leaf operations inside ``[lo, hi)``:
    rows ``[us a call, count a call, "kind shape | scope"]``, largest
    first, and ``{kind: {width: us a call}}`` for every operation whose
    output's first dimension is its width."""
    total, count = collections.Counter(), collections.Counter()
    widths = collections.defaultdict(collections.Counter)
    for (tf_op, _, _, name), ns in program_trace._leaf_ops(ops, (lo, hi)):
        kind = name.split(" ", 1)[-1]  # "%reshape.9 reshape f32[..]" -> "reshape f32[..]"
        key = kind + " | " + tf_op.split("/", 1)[-1]
        total[key] += ns / 1e3 / calls
        count[key] += 1
        shape = re.search(r"^(\S+) \w+\[(\d+)", kind)
        if shape:
            widths[shape.group(1)][int(shape.group(2))] += ns / 1e3 / calls
    rows = [[round(v, 1), count[k] / calls, k] for k, v in total.most_common()]
    return rows, {
        kind: {str(w): round(us, 1) for w, us in sorted(by.items(), reverse=True)}
        for kind, by in widths.items()
    }


def executions(path):
    """``(modules, ops)`` of a trace: the first chip's program
    executions ``[name, start, duration]`` and its operations as
    ``program_trace.load_op_scopes`` gives them."""
    if path.endswith(".json"):
        with open(path) as f:
            trace, ops = program_trace.load_cut(json.load(f))
    else:
        if not path.endswith(".pb"):
            path = tr.newest_xplane(path)
        trace, ops = tr.Trace(tr.load_xplane(path), 1), program_trace.load_op_scopes(path)
    return tr.Trace._line(trace.devices[0], tr.MODULES_LINE), ops


def from_trace(path):
    """One line for each tree-update program in a cell's trace: its
    execution of median length among the whole ones (a cut clips its
    first and last)."""
    modules, ops = executions(path)
    programs = collections.defaultdict(list)
    for name, start, ns in modules:
        if name.startswith(FAMILY):
            programs[name].append((ns, start))
    for name, runs in programs.items():
        whole = sorted(r for r in runs if r[0] > 0.9 * max(runs)[0])
        ns, start = whole[len(whole) // 2]
        rows, widths = by_operation(ops, start, start + ns)
        print(json.dumps({
            "program": name, "executions": len(runs), "us_a_call": round(ns / 1e3, 1),
            "operations": sum(r[1] for r in rows), "by_width": widths, "ops": rows[:24],
        }), flush=True)


def on_the_chip(capacity):
    import jax

    from ray_tpu.ops.segment_tree import DeviceSumTree, MinSegmentTree, SumSegmentTree

    if jax.default_backend() != "tpu":
        sys.exit("profile_tree_update: needs a TPU, found " + jax.default_backend())
    rng = np.random.default_rng(37)
    tree = DeviceSumTree(capacity)
    hosts = SumSegmentTree(capacity), MinSegmentTree(capacity)
    leaves = rng.random(capacity) ** 0.6 + 1e-3
    tree.set_leaf_values(leaves)
    for host in hosts:
        host.set_items(np.arange(capacity), leaves)
    for u, b in SHAPES:
        def update():
            # distinct inside a row, as a scatter needs; rows repeat each other's
            idx = np.stack([rng.choice(capacity, b, replace=False) for _ in range(u)])
            vals = rng.random((u, b)) ** 0.6 + 1e-3
            tree.set_powered(idx, vals)
            for i in range(u):  # the stacked update's order: the later write wins
                for host in hosts:
                    host.set_items(idx[i], vals[i])

        update()
        jax.block_until_ready(tree.sum_value)
        directory = tempfile.mkdtemp()
        with jax.profiler.trace(directory):
            for _ in range(CALLS):
                update()
            jax.block_until_ready((tree.sum_value, tree.min_value))
        path = glob.glob(directory + "/plugins/profile/*/*.xplane.pb")[0]
        modules, ops = executions(path)
        runs = [m for m in modules if m[0].startswith(FAMILY)]
        rows, widths = by_operation(
            ops, min(m[1] for m in runs), max(m[1] + m[2] for m in runs), len(runs))
        nodes = [np.asarray(jax.device_get(dev), np.float64)
                 for dev in (tree.sum_value, tree.min_value)]
        rel = max(  # slot 0, the one nothing reads, holds 0 and inf
            float(np.max(np.abs(got[1:] / host.value[1:] - 1.0)))
            for got, host in zip(nodes, hosts))
        print(json.dumps({
            "shape": [u, b], "capacity": capacity,
            "device": jax.devices()[0].device_kind, "calls": len(runs),
            "us_a_call": round(sum(m[2] for m in runs) / 1e3 / len(runs), 1),
            "operations": round(sum(r[1] for r in rows)),
            "nodes_rel_max_to_host": rel,
            "nodes_sha1": hashlib.sha1(b"".join(n.tobytes() for n in nodes)).hexdigest(),
            "by_width": widths, "ops": rows[:24],
        }), flush=True)


if __name__ == "__main__":
    if sys.argv[1:2] == ["--trace"]:
        from_trace(sys.argv[2])
    else:
        on_the_chip(int(sys.argv[1]) if sys.argv[1:] else 131072)
