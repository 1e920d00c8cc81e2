#!/bin/bash
# The latent layer's one-token form in the Xing4 cell (or any cell, for a
# pair that must not move): runs from the checkouts under .chip_check/
# (benchmarks/chip/step_cells.sh: one compile cache, each run's lowering
# counters, among them ray_tpu_mla_decode_lowerings_total, and learn
# statistics beside its result, the decode step by operation of each
# side's newest traced run), then of each side's trace the waits by loop
# and consumer (python3 -m perf.async_waits, with a cut for a later look)
# and the operations under learn/commit (ROADMAP A8e):
#   mkdir -p .chip_check/change && git archive $(git write-tree) | tar -x -C .chip_check/change
#   chiprun --timeout 3400 -- bash benchmarks/chip/latent_step.sh <cell> <decode steps> <side>:<seed>[:1] ...
# Lands in chiprun_out/: what step_cells.sh leaves, waits_<cell>_<side>.json,
# waits_cut_<cell>_<side>.json and <cell>_commit_ops_<side>.txt.
set -u
cell=$1; steps=$2
out=$PWD/chiprun_out; mkdir -p "$out"
bash benchmarks/chip/step_cells.sh "$@"
for side in parent change; do
  [ -d ".chip_check/$side/.perf_trace" ] || continue
  ( cd ".chip_check/$side" \
    && python3 -m perf.async_waits .perf_trace --top 8 --save-cut "$out/waits_cut_${cell}_$side.json" \
         > "$out/waits_${cell}_$side.json" 2> "$out/waits_${cell}_$side.err" \
    ; PYTHONPATH=. python3 "$OLDPWD/benchmarks/decode_step_ops.py" .perf_trace 1 12 learn/commit \
         > "$out/${cell}_commit_ops_$side.txt" 2> /dev/null )
  echo "$side learn/commit: $(head -n 4 "$out/${cell}_commit_ops_$side.txt" | cut -c1-200 | tr '\n' ';')"
done
