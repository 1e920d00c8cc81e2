"""What the selective scan's kernels are bound by: their time with a part taken out.

    chiprun -- env PYTHONPATH=. python benchmarks/ablate_selective_scan.py

The two kernels of ``ops/selective_scan.py`` alone on the chip at the
Phi-4-mini-flash cell's size (``profile_selective_scan.operands``), as
they are and with one part of the per-token arithmetic replaced by
something that costs nothing: ``1 + x`` for the ``exp``, no select for
the reset, one row in place of the sum down the sublanes, and all three
(the results are wrong, the operands' traffic and the loops are the
same). Prints a line a case and one JSON line: microseconds a layer of
the forward and the backward custom call, from a profiler trace of one
``value_and_grad``. PR 58 read: whole 719.5 / 2,034.7, no ``exp`` 658.2
/ 1,971.9, no select 654.8 / 1,908.3, no sum 544.2 / 1,758.3, none of
the three 430.1 / 1,570.7 (PERF.md section 6). TPU only.
"""

from __future__ import annotations

import json
import sys

import jax
import jax.numpy as jnp

from benchmarks.profile_moe_product import largest_ops
from benchmarks.profile_selective_scan import both, operands
from ray_tpu.ops import selective_scan as ss


def no_exp(s, fresh, dt, u, a, bx):
    s = jnp.where(fresh, 0.0, s)
    return (1.0 + dt * a) * s + (dt * u) * bx


def no_select(s, fresh, dt, u, a, bx):
    return jnp.exp(dt * a) * s + (dt * u) * bx


def neither(s, fresh, dt, u, a, bx):
    return (1.0 + dt * a) * s + (dt * u) * bx


def one_row(x):
    return x[0:1] + x[8:9]


def run():
    whole = (ss._advance, ss._over_states)
    cases = {
        "whole": whole,
        "no_exp": (no_exp, whole[1]),
        "no_select": (no_select, whole[1]),
        "no_sublane_sum": (whole[0], one_row),
        "none_of_the_three": (neither, one_row),
    }
    ops = operands(5120, 16)
    out = {}
    for name, (advance, over_states) in cases.items():
        ss._advance, ss._over_states = advance, over_states
        ss._kernel_fwd.clear_cache()
        ss._kernel_bwd.clear_cache()
        _, grad = both(ss.selective_scan_kernel)
        out[name] = {
            call: us for us, _, op in largest_ops(grad, *ops, top=4)
            for call in ("selective_scan_fwd", "selective_scan_bwd") if call in op}
        print(name, out[name], flush=True)
    ss._advance, ss._over_states = whole
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    if jax.default_backend() != "tpu":
        sys.exit("ablate_selective_scan: needs a TPU, found " + jax.default_backend())
    run()
