#!/bin/bash
# "Where set-up goes" (PERF.md section 5): for each cell named, one run
# on an EMPTY compile cache directory of the cell's own and two warm
# runs on it (the second traced), as the driver runs them, with the
# program's own account of set-up written out beside each result:
#   chiprun --timeout 3500 -- bash benchmarks/chip/setup_account.sh <seed0> <cell> [<cell> ...]
# Lines land in chiprun_out/setup_account_<cell>.jsonl; print them with
#   python3 benchmarks/setup_account.py --table chiprun_out/setup_account_*.jsonl
set -u
out=$PWD/chiprun_out; mkdir -p "$out"
seed=$1; shift
for cell in "$@"; do
  export JAX_COMPILATION_CACHE_DIR=$(mktemp -d)
  for run in cold warm traced; do
    trace=0; [ "$run" = traced ] && trace=1
    seed=$((seed + 18))
    log="$out/setup_account_${cell}_$run.log"
    t0=$(date +%s)
    PYTHONPATH=. python3 benchmarks/setup_account.py "$out/setup_account_$cell.jsonl" \
      --workload "$cell" --seed "$seed" --seconds 30 --trace $trace > "$log" 2>&1
    echo "$cell $run seed $seed rc=$? in $(( $(date +%s) - t0 )) s; cache $(du -sm "$JAX_COMPILATION_CACHE_DIR" | cut -f1) MiB"
    grep -E "^\[setup\]" "$log" | cut -c1-400
    tail -n 1 "$log" | cut -c1-300
  done
  rm -rf "$JAX_COMPILATION_CACHE_DIR"
done
PYTHONPATH=. python3 benchmarks/setup_account.py --table "$out"/setup_account_*.jsonl
