#!/bin/bash
# "What the chip waits for" (PERF.md section 5): one traced run of a cell
# from each checkout under .chip_check/ that is named (a `git archive`
# of the tree, as the driver's is), then the waits of its trace by loop
# and consumer, exposed / hidden / rate / room each
# (python3 -m perf.async_waits), and a cut of one whole iteration with
# its table for a later look without the chip:
#   mkdir -p .chip_check/change && git archive $(git write-tree) | tar -x -C .chip_check/change
#   chiprun --timeout 1500 -- bash benchmarks/chip/waits_account.sh <cell> <side>:<seed> [<side>:<seed> ...]
# A side from before PR 52 takes this PR's BENCHMARK.json and perf/ laid
# over its archive (as the driver lays them over the parent): its program
# has no table, so its waits are placed by their enclosing loop alone.
# Files land in chiprun_out/: waits_<cell>_<side>_<seed>.json (the account),
# waits_cut_<cell>_<side>.json (the cut), waits_<cell>_<side>_<seed>.log.
set -u
cell=$1; shift
out=$PWD/chiprun_out; mkdir -p "$out"
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-$(mktemp -d)}
for run in "$@"; do
  IFS=: read -r side seed <<< "$run"
  log="$out/waits_${cell}_${side}_$seed.log"
  t0=$(date +%s)
  ( cd ".chip_check/$side" && python3 -m perf.run --workload "$cell" --seed "$seed" \
      --seconds 30 --trace 1 > "$log" 2>&1 )
  echo "$side seed $seed rc=$? in $(( $(date +%s) - t0 )) s"
  grep -E "^\[(setup|async-pairs)\]" "$log" | cut -c1-330
  tail -n 1 "$log" | python3 -c '
import json, sys
r = json.loads(sys.stdin.readline())
m = {k: v["value"] for k, v in r["metrics"].items()}
print(r["workload"], "correct", r["correct"], "failed", r["failed"])
for k in sorted(m):
    if "wait" in k or "unplaced" in k or "unscoped" in k or "decode_device" in k or k.startswith("learner.scope") or k.startswith("compile."):
        print("  ", k, m[k])'
  ( cd ".chip_check/$side" && python3 -m perf.async_waits .perf_trace \
      --save-cut "$out/waits_cut_${cell}_$side.json" \
      > "$out/waits_${cell}_${side}_$seed.json" 2>> "$log" ) \
    || echo "perf.async_waits failed: see $log"
done
