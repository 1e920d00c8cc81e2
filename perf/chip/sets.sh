#!/bin/bash
# Two sets of runs of one cell with the same seeds in both, all in one
# call, FROM A CHECKOUT OF WHAT GIT WOULD COMMIT. Before the call, here:
#   git add -A && rm -rf .chip_check && mkdir .chip_check &&
#     git archive $(git write-tree) | tar -x -C .chip_check
#   chiprun --timeout 3000 -- bash perf/chip/sets.sh <cell> <seconds> <gate seed> <seed> [<seed> ...]
# A 5-second run on <gate seed> goes first; the sets run only if it is correct.
# Result lines land in chiprun_out/<cell>_set{1,2}.jsonl, logs beside them.
set -u
cell=$1; seconds=$2; gate=$3; shift 3
out=$PWD/chiprun_out
mkdir -p "$out"
cd .chip_check || exit 9
python3 -m perf.run --workload "$cell" --seed "$gate" --seconds 5 --trace 0 \
  > "$out/${cell}_gate_$gate.log" 2>&1
echo "gate rc=$? $(tail -n 1 "$out/${cell}_gate_$gate.log" | cut -c1-300)"
tail -n 1 "$out/${cell}_gate_$gate.log" | grep -q '"correct": true' || {
  grep -E "'ok': False|Error" "$out/${cell}_gate_$gate.log" | tail -n 20; exit 8; }
for set in 1 2; do
  for seed in "$@"; do
    log="$out/${cell}_set${set}_$seed.log"
    python3 -m perf.run --workload "$cell" --seed "$seed" --seconds "$seconds" \
      --trace 0 > "$log" 2>&1
    rc=$?
    tail -n 1 "$log" >> "$out/${cell}_set$set.jsonl"
    echo "set $set seed $seed rc=$rc $(grep -E '^\[window\] seconds' "$log")"
    tail -n 1 "$log" | cut -c1-420
  done
done
