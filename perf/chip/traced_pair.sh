#!/bin/bash
# One `--trace 1` run of a cell on the change and one on the parent, same
# seed, one call; the parent carries this tree's benchmark files laid over
# its own, as the driver lays them, so the run also shows that every new
# reader finds nothing there and says so. Before the call, here:
#   git add -A && rm -rf .chip_check && mkdir -p .chip_check/change .chip_check/parent &&
#     git archive $(git write-tree) | tar -x -C .chip_check/change &&
#     git archive <parent commit> | tar -x -C .chip_check/parent &&
#     cp .chip_check/change/BENCHMARK.json .chip_check/parent/ &&
#     cp -r .chip_check/change/perf/. .chip_check/parent/perf/
#   chiprun --timeout 1800 -- bash perf/chip/traced_pair.sh <cell> <seconds> <seed>
# Lands in chiprun_out/: traced_{change,parent}_<seed>.log (last line: the
# result), program_trace_{change,parent}_<seed>.json (perf.program_trace's
# summary of the run's trace) and, from the change, recorded_trace_spans.json
# (the cut kept in perf/tests/data).
set -u
cell=$1; seconds=$2; seed=$3
out=$PWD/chiprun_out
mkdir -p "$out"
for side in change parent; do
  cd ".chip_check/$side" || exit 9
  log="$out/traced_${side}_$seed.log"
  python3 -m perf.run --workload "$cell" --seed "$seed" --seconds "$seconds" \
    --trace 1 > "$log" 2>&1
  echo "$side rc=$? $(grep -E '^\[window\] seconds' "$log")"
  tail -n 1 "$log" | cut -c1-2600
  cut=()
  [ "$side" = change ] &&
    cut=(--save-cut "$out/recorded_trace_spans.json" --cut-first 10)
  python3 -m perf.program_trace .perf_trace "${cut[@]}" \
    > "$out/program_trace_${side}_$seed.json" 2> "$out/program_trace_${side}_$seed.err"
  echo "$side program_trace rc=$?"
  cd ../..
done
