#!/bin/bash
# The chip calls of a model_config PR, FROM CHECKOUTS OF WHAT GIT WOULD
# COMMIT. Before the call, here:
#   git add -A && rm -rf .chip_check && mkdir -p .chip_check/change .chip_check/parent &&
#     git archive $(git write-tree) | tar -x -C .chip_check/change &&
#     git archive <parent commit> | tar -x -C .chip_check/parent
#   chiprun --timeout 3400 -- bash perf/chip/model_config.sh new <cell> <seconds> <traced seed> <seed> ...
#   chiprun --timeout 3000 -- bash perf/chip/model_config.sh pairs <old cell> <seconds> <seed> ...
# "new": the parent on the new cell's name first (it must exit non-zero at
# once), then the change on every seed, then one traced run.
# "pairs": an old cell, both sides on every seed, the side that goes first
# alternating (parent, change, change, parent, ...).
# Result lines land in chiprun_out/<cell>_<side>.jsonl, logs beside them.
set -u
mode=$1; cell=$2; seconds=$3; shift 3
out=$PWD/chiprun_out
mkdir -p "$out"
one() {  # side seed trace
  log="$out/${cell}_$1_$2_t$3.log"
  ( cd ".chip_check/$1" && python3 -m perf.run --workload "$cell" --seed "$2" \
      --seconds "$seconds" --trace "$3" ) > "$log" 2>&1
  rc=$?
  tail -n 1 "$log" >> "$out/${cell}_$1_t$3.jsonl"
  echo "$1 seed $2 trace $3 rc=$rc $(grep -E '^\[window\] seconds' "$log")"
  tail -n 1 "$log" | cut -c1-330
}
if [ "$mode" = new ]; then
  traced=$1; shift
  t0=$(date +%s)
  ( cd .chip_check/parent && python3 -m perf.run --workload "$cell" --seed "$1" \
      --seconds "$seconds" --trace 0 ) > "$out/${cell}_parent.log" 2>&1
  echo "parent on $cell: rc=$? in $(( $(date +%s) - t0 )) s: $(tail -n 1 "$out/${cell}_parent.log" | cut -c1-200)"
  for seed in "$@"; do one change "$seed" 0; done
  one change "$traced" 1
else
  first=parent; second=change
  for seed in "$@"; do
    one $first "$seed" 0; one $second "$seed" 0
    swap=$first; first=$second; second=$swap
  done
fi
