#!/bin/bash
# What the driver's first traced run of the sequence cell sees: an EMPTY
# compile cache (a directory of this call's own), every compile logged,
# the whole process on the clock (the driver stops a run at 360 s):
#   chiprun --timeout 900 -- bash perf/chip/cold_traced.sh [seed]
set -u
out=$PWD/chiprun_out; mkdir -p "$out"
cell=qwen3next_ppo.fused_tokens.1chip
seed=${1:-3100000019}
export JAX_COMPILATION_CACHE_DIR=$(mktemp -d)
t0=$(date +%s)
JAX_LOG_COMPILES=1 python3 -m perf.run --workload $cell --seed "$seed" --seconds 30 --trace 1 \
  > "$out/cold_traced.log" 2> "$out/cold_traced.err"
echo "cold traced run rc=$? in $(( $(date +%s) - t0 )) s (the driver's limit is 360 s)"
grep -E "^\[setup|^\[window\]" "$out/cold_traced.log" | cut -c1-600
grep -E "^\[correct\]" "$out/cold_traced.log" | cut -c1-160
grep -oE "Finished XLA compilation of jit\([^)]*\) in [0-9.]+" "$out/cold_traced.err" \
  | awk '$NF > 1.0 {print "compile", $5, $NF}'
tail -n 1 "$out/cold_traced.log" | cut -c1-1500
tail -n 5 "$out/cold_traced.err" | cut -c1-300
