#!/bin/bash
# What the program's tracing costs the untraced window, and whether the
# change moved it: per seed, one run of the change as the driver runs it
# (`off`), one with `tracing.enable()` on through RAY_TPU_TRACE=1 (`on`:
# every span site builds a Span and appends it to the bounded list; no
# profiler session), and one of the parent commit (`parent`), in that
# order so that the three share the machine's drift. Prepare
# .chip_check/{change,parent} as perf/chip/traced_pair.sh says, then:
#   chiprun --timeout 3400 -- bash perf/chip/tracing_cost.sh <cell> <seconds> <seed> [<seed> ...]
# Result lines land in chiprun_out/<cell>_cost_{off,on,parent}.jsonl.
set -u
cell=$1; seconds=$2; shift 2
out=$PWD/chiprun_out
mkdir -p "$out"
for seed in "$@"; do
  for side in off on parent; do
    dir=.chip_check/change; trace=0
    [ "$side" = parent ] && dir=.chip_check/parent
    [ "$side" = on ] && trace=1
    log="$out/${cell}_cost_${side}_$seed.log"
    ( cd "$dir" && RAY_TPU_TRACE=$trace python3 -m perf.run --workload "$cell" \
        --seed "$seed" --seconds "$seconds" --trace 0 > "$log" 2>&1 )
    rc=$?
    tail -n 1 "$log" >> "$out/${cell}_cost_$side.jsonl"
    echo "$side seed $seed rc=$rc $(grep -E '^\[window\] seconds' "$log" | cut -c1-200)"
    tail -n 1 "$log" | cut -c1-330
  done
done
