#!/bin/bash
# Parent and change on the same seeds, alternating which side goes first,
# all in one call, each from a checkout of what git would commit. Prepare
# .chip_check/{change,parent} as perf/chip/traced_pair.sh says (WITHOUT
# laying the change's benchmark over the parent, where the change is to
# the benchmark itself), then:
#   chiprun --timeout 3000 -- bash perf/chip/pairs.sh <cell> <seconds> <seed> [<seed> ...]
# Result lines land in chiprun_out/<cell>_pairs_{parent,change}.jsonl, in seed order.
set -u
cell=$1; seconds=$2; shift 2
out=$PWD/chiprun_out
mkdir -p "$out"
n=0
for seed in "$@"; do
  order="parent change"; [ $((n % 2)) = 1 ] && order="change parent"
  n=$((n + 1))
  for side in $order; do
    log="$out/${cell}_pairs_${side}_$seed.log"
    ( cd ".chip_check/$side" && python3 -m perf.run --workload "$cell" \
        --seed "$seed" --seconds "$seconds" --trace 0 > "$log" 2>&1 )
    rc=$?
    tail -n 1 "$log" >> "$out/${cell}_pairs_$side.jsonl"
    echo "$side seed $seed rc=$rc $(grep -E '^\[window\] seconds' "$log" | cut -c1-160)"
    tail -n 1 "$log" | cut -c1-330
  done
done
