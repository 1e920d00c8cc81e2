#!/bin/bash
# The chip calls of the learned-index cell (PR 65), from the tree as it
# stands or from .chip_check/<side> checkouts of what git would commit:
#   chiprun --timeout 1200 -- bash perf/chip/sparse_attention_cell.sh cold [seed]
#       what the driver's first traced run sees: an EMPTY compile cache of
#       this call's own, every compile logged, the whole process on the
#       clock (the driver stops a run at 360 s; ISSUE 65 allows 300 s)
#   chiprun --timeout 3400 -- bash perf/chip/sparse_attention_cell.sh runs <seconds> <seed> ...
#       one run a seed from the tree, the last one traced
#   chiprun --timeout 3400 -- bash perf/chip/sparse_attention_cell.sh control <seeds> <first seed>
#       the system's and the int8 / fp8 controls' readings (perf.control:
#       seeds first, first + 7919, ...)
set -u
mode=$1; shift
cell=keye2_ppo.fused_tokens.1chip
out=$PWD/chiprun_out; mkdir -p "$out"
root=${SIDE:+.chip_check/$SIDE}
if [ "$mode" = cold ]; then
  seed=${1:-3650000017}
  export JAX_COMPILATION_CACHE_DIR=$(mktemp -d)
  t0=$(date +%s)
  ( cd "${root:-.}" && JAX_LOG_COMPILES=1 python3 -m perf.run --workload $cell --seed "$seed" \
      --seconds 30 --trace 1 ) > "$out/cold_traced.log" 2> "$out/cold_traced.err"
  echo "cold traced run rc=$? in $(( $(date +%s) - t0 )) s (the driver's limit is 360 s)"
  grep -E "^\[setup|^\[window\]" "$out/cold_traced.log" | cut -c1-600
  grep -E "^\[correct\]" "$out/cold_traced.log" | cut -c1-220
  grep -oE "Finished XLA compilation of jit\([^)]*\) in [0-9.]+" "$out/cold_traced.err" \
    | awk '$NF > 1.0 {print "compile", $5, $NF}'
  tail -n 1 "$out/cold_traced.log" | cut -c1-3000
  tail -n 8 "$out/cold_traced.err" | cut -c1-400
elif [ "$mode" = runs ]; then
  seconds=$1; shift
  n=$#; i=0
  for seed in "$@"; do
    i=$((i + 1)); trace=0; [ $i = $n ] && trace=1
    log="$out/${cell}_${seed}_t$trace.log"
    t0=$(date +%s)
    ( cd "${root:-.}" && python3 -m perf.run --workload $cell --seed "$seed" \
        --seconds "$seconds" --trace $trace ) > "$log" 2>&1
    echo "seed $seed trace $trace rc=$? in $(( $(date +%s) - t0 )) s $(grep -E '^\[window\] seconds' "$log")"
    grep -E "^\[correct\]" "$log" | grep -E "'ok': False" | cut -c1-300
    tail -n 1 "$log" >> "$out/${cell}_t$trace.jsonl"
    tail -n 1 "$log" | cut -c1-$([ $trace = 1 ] && echo 4000 || echo 700)
  done
else
  ( cd "${root:-.}" && python3 -m perf.control --workload $cell --seeds "$1" --first-seed "$2" ) \
    > "$out/control_$cell.log" 2>&1
  echo "control rc=$?"
  grep -E "^\[control\]" "$out/control_$cell.log" | cut -c1-2600
  tail -n 1 "$out/control_$cell.log" | cut -c1-4000
  grep -E "Error|error|Traceback" -A 12 "$out/control_$cell.log" | tail -n 40 | cut -c1-400
fi
