#!/bin/bash
# PR 35's chip calls for the one-token state-space kernel, from the working tree:
#   chiprun --timeout 900 -- bash benchmarks/chip/ssd_step.sh alone [heads ...]   (OUT=<dir> for its files)
#     the step alone (benchmarks/profile_ssd_step.py: body against kernel at
#     each block of heads), one JSON line a run of layers.
#   chiprun --timeout 1500 -- bash benchmarks/chip/ssd_step.sh traced_pair <seed>
#     perf/chip/traced_pair.sh on the granite cell (prepare .chip_check/{change,parent}
#     as it says), then each side's decode step by operation
#     (benchmarks/decode_step_ops.py) in chiprun_out/decode_step_ops_<side>_<seed>.txt.
# Untraced pairs of parent and change go through perf/chip/pairs.sh.
set -u
mode=${1:-alone}; shift
out=${OUT:-$PWD/chiprun_out}; mkdir -p "$out"
if [ "$mode" = alone ]; then
  PYTHONPATH=. python benchmarks/profile_ssd_step.py "$@" \
    > "$out/profile_ssd_step.jsonl" 2> "$out/profile_ssd_step.err"
  echo "profile rc=$?"; tail -n 3 "$out/profile_ssd_step.err" | cut -c1-400
  cut -c1-6000 "$out/profile_ssd_step.jsonl"
else
  seed=$1; tool=$PWD/benchmarks/decode_step_ops.py
  bash perf/chip/traced_pair.sh granite4h_ppo.fused_tokens.1chip 30 "$seed"
  for side in change parent; do
    ( cd ".chip_check/$side" && PYTHONPATH=. python3 "$tool" .perf_trace 256 60 ) \
      > "$out/decode_step_ops_${side}_$seed.txt" 2>&1
    echo "$side:"; head -n 30 "$out/decode_step_ops_${side}_$seed.txt" | cut -c1-200
  done
fi
