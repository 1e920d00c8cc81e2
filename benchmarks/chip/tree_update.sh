#!/bin/bash
# PR 37's chip calls for the replay tree's update. Prepare .chip_check/{change,parent}
# as perf/chip/traced_pair.sh says, and copy benchmarks/profile_tree_update.py into a
# parent that lacks it (it drives DeviceSumTree's public calls only). Then:
#   chiprun --timeout 900 -- bash benchmarks/chip/tree_update.sh alone
#     the (1, 512) and (8, 512) updates of a 131,072-leaf tree alone on each side, by
#     operation and level width: chiprun_out/profile_tree_update_<side>.jsonl
#   chiprun --timeout 1500 -- bash benchmarks/chip/tree_update.sh traced_pair <seed>
#     perf/chip/traced_pair.sh on the DQN cell, then each side's jit_tree_update
#     executions by operation: chiprun_out/tree_update_ops_<side>_<seed>.jsonl
# Untraced pairs of parent and change go through perf/chip/pairs.sh.
set -u
mode=${1:-alone}
out=$PWD/chiprun_out; mkdir -p "$out"
tool=benchmarks/profile_tree_update.py
show() { python3 -c '
import json, sys
for line in open(sys.argv[1]):
    d = json.loads(line)
    print(d.get("shape", d.get("program")), "us", d["us_a_call"], "operations", d["operations"],
          {k: d[k] for k in ("nodes_rel_max_to_host", "nodes_sha1") if k in d})
    for row in d["ops"][:8]: print("    ", row)
' "$1"; }
if [ "$mode" = alone ]; then
  for side in change parent; do
    log="$out/profile_tree_update_$side.jsonl"
    ( cd ".chip_check/$side" && PYTHONPATH=. python3 $tool ) > "$log" 2> "${log%.jsonl}.err"
    echo "$side alone rc=$?"; tail -n 2 "${log%.jsonl}.err" | cut -c1-300; show "$log"
  done
else
  seed=$2
  bash perf/chip/traced_pair.sh dqn_per.fused.1chip 30 "$seed"
  for side in change parent; do
    log="$out/tree_update_ops_${side}_$seed.jsonl"
    ( cd ".chip_check/$side" && PYTHONPATH=. python3 "../../$tool" --trace .perf_trace ) \
      > "$log" 2> "${log%.jsonl}.err"
    echo "$side ops rc=$?"; show "$log"
  done
fi
