#!/bin/bash
# The chip call after the driver's check stopped the sequence cell at its
# limit of 360 s a run, FROM CHECKOUTS OF WHAT GIT WOULD COMMIT (made as
# perf/chip/model_config.sh says, plus .chip_check/overlay = the parent
# with this PR's BENCHMARK.json and perf/ laid over it, as the driver does):
#   chiprun --timeout 1500 -- bash perf/chip/refused_fix.sh <cold seed> <traced seed> <seed> ...
# 1. the overlay on the new cell's name: must exit non-zero in seconds;
# 2. the change on the new cell, traced, on an EMPTY compile cache (what
#    the driver's first run may see), the whole process on the clock;
# 3. the same with the machine's cache, then every seed untraced.
set -u
out=$PWD/chiprun_out; mkdir -p "$out"
new=qwen3next_ppo.fused_tokens.1chip
run() {  # tree seed trace tag
  t0=$(date +%s)
  ( cd ".chip_check/$1" && python3 -m perf.run --workload $new --seed "$2" \
      --seconds 30 --trace "$3" ) > "$out/fix_$4.log" 2>&1
  echo "$4: rc=$? in $(( $(date +%s) - t0 )) s (the driver's limit is 360 s)"
  grep -E "^\[setup|^\[window\] seconds" "$out/fix_$4.log" | cut -c1-420
  grep -E "^\[correct\].*'ok': False" "$out/fix_$4.log" | cut -c1-300
  tail -n 1 "$out/fix_$4.log" | cut -c1-${5:-420}
}
cold=$1; traced=$2; shift 2
run overlay "$cold" 0 overlay_new
( export JAX_COMPILATION_CACHE_DIR=$(mktemp -d); run change "$cold" 1 new_cold_traced 1500 )
run change "$traced" 1 new_traced 1500
for seed in "$@"; do run change "$seed" 0 "new_$seed"; done
