#!/bin/bash
# PR 64's chip calls for the fragment-form gated delta rule's kernel pair
# (ops/deltanet.gated_delta_chunked_kernel, a decay a head):
#   chiprun --timeout 900 -- bash benchmarks/chip/delta_rule.sh alone [chunk ...]   (OUT=<dir> for its files)
#     one layer's rule alone at the Qwen3-Next cell's size (64 x 128 x 32 x
#     128 x 128; benchmarks/profile_delta_rule.py: text against kernel,
#     forward and forward-forward-backward, their distance and largest
#     operations), one JSON line. No cell runs it.
#   chiprun --timeout 1800 -- bash benchmarks/chip/delta_rule.sh traced <seed> [side ...]
#     one `--trace 1` run of the Qwen3-Next cell from each .chip_check/<side>
#     (default change parent; prepare them as perf/chip/traced_pair.sh says),
#     then each side's reduction (perf.program_trace), its waits by loop and
#     consumer (perf.async_waits), and learn/linear_attn by operation
#     (benchmarks/decode_step_ops.py), all in chiprun_out/.
# Untraced pairs of parent and change go through perf/chip/pairs.sh.
set -u
mode=${1:-alone}; shift
out=${OUT:-$PWD/chiprun_out}; mkdir -p "$out"
if [ "$mode" = alone ]; then
  PYTHONPATH=. python benchmarks/profile_delta_rule.py "$@" \
    > "$out/profile_delta_rule.jsonl" 2> "$out/profile_delta_rule.err"
  echo "profile rc=$?"; tail -n 3 "$out/profile_delta_rule.err" | cut -c1-400
  cut -c1-8000 "$out/profile_delta_rule.jsonl"
else
  # .chip_check/<side> for every side named after the seed (default: change parent)
  seed=$1; shift; sides=${*:-change parent}
  cell=qwen3next_ppo.fused_tokens.1chip; tool=$PWD/benchmarks/decode_step_ops.py
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
    PYTHONPATH=. python3 "$tool" .perf_trace 1 40 learn/linear_attn \
      > "$out/learn_linear_attn_ops_${side}_$seed.txt" 2>&1
    echo "$side learn/linear_attn:"
    head -n 24 "$out/learn_linear_attn_ops_${side}_$seed.txt" | grep -v Warn | cut -c1-160
    cd ../..
  done
fi
