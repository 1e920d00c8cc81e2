"""The device operations of a sequence cell's decode step, by name.

    python3 -m perf.run --workload <cell> --seed <n> --seconds 30 --trace 1
    PYTHONPATH=. python benchmarks/decode_step_ops.py .perf_trace <steps> [top [scope]]

Reads the ``.xplane.pb`` a traced run leaves and prints one line a leaf
operation under the lane's ``rollout/act`` scope inside the annotated
span (the iterations the run's own reductions read): microseconds a
step (its device time / ``steps``, the decode steps the span holds: 256
for the granite cell's one traced iteration), executions a step, the
operation's name and the tail of its ``tf_op`` path (the
scope says what a fusion named by its output shape computes: PR 34 read
``multiply_reduce_fusion f32[16]`` as the state's read; its path ends
``mlp/dot_general``). The traced result line's ``breakdown`` holds the
ten largest operations of the whole program only. With ``scope`` the
same table of another scope's operations (``learn/mla`` with ``steps``
20: a latent layer's update of one group of streams, by operation).
With ``scope`` ``all`` (and ``steps`` 1) every operation of the span,
filed as the run's own reduction files it (``perf.program_trace.scope_of``: the innermost
of the program's scopes on its path, so an ``add_any`` of the backward
pass with no model scope lands under ``learn/loss_grad`` and a
prefetch's ``async-done`` under none): the scopes' totals first, parent
and change side by side say WHICH operations a scope's number is made
of (PR 51: what left ``learn/loss_grad`` and what came into
``learn/moe``).
"""

from __future__ import annotations

import collections
import os
import sys

from perf import program_trace
from perf import trace_reduce as tr


def main(argv) -> int:
    if len(argv) < 3:
        sys.exit(__doc__)
    path, steps = argv[1], int(argv[2])
    top = int(argv[3]) if len(argv) > 3 else 40
    scope = argv[4] if len(argv) > 4 else "rollout/act"
    if os.path.isdir(path):
        path = tr.newest_xplane(path)
    bounds = tr.annotation_bounds(tr.load_xplane(path), tr.TRAIN_ANNOTATION)
    total, count = collections.Counter(), collections.Counter()
    by_scope = collections.Counter()
    for op, duration_ns in program_trace._leaf_ops(
            program_trace.load_op_scopes(path), bounds):
        tf_op, _, _, name = op
        if scope == "all":
            filed = program_trace.scope_of(tf_op) or "(unscoped)"
            by_scope[filed] += duration_ns / 1e3
            key = f"{filed}: {name.split(' ', 1)[-1]} | {tf_op[-60:]}"
        elif scope in tf_op:
            key = name.split(" ", 1)[-1] + " | " + tf_op.split(scope + "/", 1)[-1][-60:]
        else:
            continue
        total[key] += duration_ns / 1e3
        count[key] += 1
    for filed, us in by_scope.most_common():
        print(f"{us / steps:9.2f} us  {filed}")
    print(f"{scope}: {sum(total.values()) / steps:.1f} us a step in "
          f"{sum(count.values()) / steps:.0f} operations")
    for key, us in total.most_common(top):
        print(f"{us / steps:9.2f} us  x{count[key] / steps:5.1f}  {key}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
