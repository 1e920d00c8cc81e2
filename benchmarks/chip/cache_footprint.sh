#!/bin/bash
# What a cell's programs take in the compile cache, by entry, from
# .chip_check/<side> checkouts: one run on an EMPTY cache directory with
# NO size limit (the chip tool's machine comes with
# JAX_COMPILATION_CACHE_MAX_SIZE set: a cell whose entries pass it evicts
# its own programs and no run is warm), the entries by size, then <warm>
# further runs on the same directory.
#   chiprun --timeout 1800 -- bash benchmarks/chip/cache_footprint.sh <cell> <seed> <warm> <side> [<side> ...]
set -u
cell=$1; seed=$2; warm=$3; shift 3
out=$PWD/chiprun_out; mkdir -p "$out"
here=$PWD
echo "machine: $(env | grep -E '^JAX_' | tr '\n' ' ')"
for side in "$@"; do
  export JAX_COMPILATION_CACHE_DIR=$(mktemp -d)
  export JAX_COMPILATION_CACHE_MAX_SIZE=-1
  for i in $(seq 0 "$warm"); do
    log="$out/footprint_${side}_$i.log"; t0=$(date +%s)
    ( cd ".chip_check/$side" && PYTHONPATH=. python3 "$here/benchmarks/setup_account.py" \
        "$out/footprint_$side.jsonl" --workload "$cell" --seed $((seed + 18 * i)) --seconds 30 --trace 0 > "$log" 2>&1 )
    echo "$side run $i rc=$? in $(( $(date +%s) - t0 )) s; cache $(du -sm "$JAX_COMPILATION_CACHE_DIR" | cut -f1) MiB in $(ls "$JAX_COMPILATION_CACHE_DIR" | wc -l) files"
    grep -E "^\[setup\]" "$log" | cut -c1-330
    tail -n 1 "$log" | cut -c1-200
  done
  ( cd "$JAX_COMPILATION_CACHE_DIR" && ls -lS | awk 'NR > 1 {print $5, $9}' ) > "$out/footprint_$side.txt"
  awk '{s += $1} END {print "entries", NR, "bytes", s}' "$out/footprint_$side.txt"
  head -n 25 "$out/footprint_$side.txt" | cut -c1-120
  rm -rf "$JAX_COMPILATION_CACHE_DIR"
done
