#!/bin/bash
# PR 58's chip calls for the fragment-form selective scan kernel:
#   chiprun --timeout 900 -- bash benchmarks/chip/selective_scan.sh alone [tile ...]   (OUT=<dir> for its files)
#     the recurrence alone at the Phi-4-mini-flash cell's size
#     (benchmarks/profile_selective_scan.py: text against kernel at each tile
#     of channels, forward and forward-plus-backward), one JSON line.
#   chiprun --timeout 900 -- bash benchmarks/chip/selective_scan.sh ablate
#     the kernels alone with a part of the arithmetic taken out
#     (benchmarks/ablate_selective_scan.py): what bounds them.
#   chiprun --timeout 1800 -- bash benchmarks/chip/selective_scan.sh traced <seed> [side ...]
#     one `--trace 1` run of the Phi-4 cell from each .chip_check/<side> (default
#     change parent; prepare them as perf/chip/traced_pair.sh says), then each
#     side's reduction (perf.program_trace), its waits by loop and consumer
#     (perf.async_waits), its decode step and its learn/scan by operation
#     (benchmarks/decode_step_ops.py), all in chiprun_out/.
# Untraced pairs of parent and change go through perf/chip/pairs.sh.
set -u
mode=${1:-alone}; shift
out=${OUT:-$PWD/chiprun_out}; mkdir -p "$out"
if [ "$mode" = alone ]; then
  PYTHONPATH=. python benchmarks/profile_selective_scan.py "$@" \
    > "$out/profile_selective_scan.jsonl" 2> "$out/profile_selective_scan.err"
  echo "profile rc=$?"; tail -n 3 "$out/profile_selective_scan.err" | cut -c1-400
  cut -c1-8000 "$out/profile_selective_scan.jsonl"
elif [ "$mode" = ablate ]; then
  PYTHONPATH=. python benchmarks/ablate_selective_scan.py > "$out/ablate_selective_scan.log" 2>&1
  echo "ablate rc=$?"; grep -v -i warn "$out/ablate_selective_scan.log" | tail -n 7 | cut -c1-600
else
  # .chip_check/<side> for every side named after the seed (default: change parent)
  seed=$1; shift; sides=${*:-change parent}
  cell=phi4flash_ppo.fused_tokens.1chip; tool=$PWD/benchmarks/decode_step_ops.py
  for side in $sides; do
    cd ".chip_check/$side" || exit 9
    log="$out/traced_${side}_$seed.log"
    python3 -m perf.run --workload "$cell" --seed "$seed" --seconds 30 --trace 1 > "$log" 2>&1
    echo "$side rc=$? $(grep -E '^\[window\] seconds' "$log")"
    tail -n 1 "$log" | cut -c1-2600
    python3 -m perf.program_trace .perf_trace > "$out/program_trace_${side}_$seed.json" \
      2> "$out/program_trace_${side}_$seed.err"
    python3 -m perf.async_waits .perf_trace --top 8 > "$out/waits_${cell}_${side}_$seed.json" \
      2> "$out/waits_${cell}_${side}_$seed.err"
    PYTHONPATH=. python3 "$tool" .perf_trace 256 60 > "$out/decode_step_ops_${side}_$seed.txt" 2>&1
    PYTHONPATH=. python3 "$tool" .perf_trace 1 40 learn/scan > "$out/learn_scan_ops_${side}_$seed.txt" 2>&1
    echo "$side learn/scan:"; head -n 16 "$out/learn_scan_ops_${side}_$seed.txt" | grep -v Warn | cut -c1-160
    cd ../..
  done
fi
