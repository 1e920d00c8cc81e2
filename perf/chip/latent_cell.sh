#!/bin/bash
# The latent-attention cell's own chip calls (any sequence cell: the cell
# is the second argument), from the working tree:
#   chiprun --timeout 3500 -- bash perf/chip/latent_cell.sh first  [cell] [control seeds] [first seed]
#     ONE run of the cell, then (only if it ran) the readings every limit
#     is set from on that many seeds (each control is a whole reference
#     iteration at 8,192 tokens).
#   chiprun --timeout 900 -- bash perf/chip/latent_cell.sh cold [cell] [seed]
#     what the driver's first traced run sees: an EMPTY compile cache,
#     every compile logged, the whole process on the clock (its limit is 360 s).
#   chiprun --timeout 3500 -- bash perf/chip/latent_cell.sh runs [cell] <seed> ...
#     one untraced 30 s run a seed; result lines in chiprun_out/<cell>_runs.jsonl
set -u
mode=${1:-first}; cell=${2:-xing4_ppo.fused_tokens.1chip}
out=$PWD/chiprun_out; mkdir -p "$out"
if [ "$mode" = first ]; then
  seeds=${3:-4}; first=${4:-2147490001}
  t0=$(date +%s)
  python3 -m perf.run --workload "$cell" --seed 2899999927 --seconds 30 --trace 0 > "$out/${cell}_first.log" 2>&1
  rc=$?
  echo "first run rc=$rc in $(( $(date +%s) - t0 )) s"
  grep -E "^\[correct\]|^\[setup|^\[window\]|Error|error|RESOURCE" "$out/${cell}_first.log" | cut -c1-500
  tail -n 1 "$out/${cell}_first.log" | cut -c1-900
  if [ $rc -ne 0 ]; then tail -n 40 "$out/${cell}_first.log"; exit $rc; fi
  t0=$(date +%s)
  python3 -m perf.control --workload "$cell" --seeds "$seeds" --first-seed "$first" > "$out/${cell}_control.log" 2>&1
  echo "control rc=$? in $(( $(date +%s) - t0 )) s"
  grep -E "^\[control\]" "$out/${cell}_control.log" | cut -c1-3000
  tail -n 1 "$out/${cell}_control.log" | cut -c1-3000
  grep -E "Error|RESOURCE" "$out/${cell}_control.log" | head -5 | cut -c1-500
elif [ "$mode" = cold ]; then
  seed=${3:-3100000019}
  export JAX_COMPILATION_CACHE_DIR=$(mktemp -d)
  t0=$(date +%s)
  JAX_LOG_COMPILES=1 python3 -m perf.run --workload "$cell" --seed "$seed" --seconds 30 --trace 1 \
    > "$out/${cell}_cold_traced.log" 2> "$out/${cell}_cold_traced.err"
  echo "cold traced run rc=$? in $(( $(date +%s) - t0 )) s (the driver's limit is 360 s)"
  grep -E "^\[setup|^\[window\]" "$out/${cell}_cold_traced.log" | cut -c1-600
  grep -E "^\[correct\]" "$out/${cell}_cold_traced.log" | cut -c1-160
  grep -oE "Finished XLA compilation of jit\([^)]*\) in [0-9.]+" "$out/${cell}_cold_traced.err" \
    | awk '$NF > 1.0 {print "compile", $5, $NF}'
  python3 -m perf.program_trace .perf_trace > "$out/${cell}_program_trace.txt" 2>&1
  tail -n 1 "$out/${cell}_cold_traced.log" | cut -c1-3000
  tail -n 5 "$out/${cell}_cold_traced.err" | cut -c1-300
else
  shift 2
  for seed in "$@"; do
    log="$out/${cell}_run_$seed.log"
    t0=$(date +%s)
    python3 -m perf.run --workload "$cell" --seed "$seed" --seconds 30 --trace 0 > "$log" 2>&1
    echo "seed $seed rc=$? in $(( $(date +%s) - t0 )) s $(grep -E '^\[window\] seconds' "$log")"
    grep -E "^\[correct\]" "$log" | grep -v "'ok': True" | cut -c1-300
    tail -n 1 "$log" >> "$out/${cell}_runs.jsonl"
    tail -n 1 "$log" | cut -c1-400
  done
fi
