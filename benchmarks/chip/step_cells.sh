#!/bin/bash
# A sequence cell from the checkouts under .chip_check/ (benchmarks/chip/sides.sh:
# one compile cache of the call's own, each run's lowering counters, among them
# ray_tpu_attention_step_lowerings_total, and learn statistics, among them
# attn_decode_key_blocks_skipped_share, beside its result), then the decode
# step by operation from each side's newest traced run:
#   chiprun --timeout 3400 -- bash benchmarks/chip/step_cells.sh <cell> <decode steps> <side>:<seed>[:1] ...
# Lands in chiprun_out/: what sides.sh leaves and <cell>_decode_ops_<side>.txt.
set -u
cell=$1; steps=$2; shift 2
out=$PWD/chiprun_out; mkdir -p "$out"
bash benchmarks/chip/sides.sh "$cell" "$@"
for side in parent change; do
  [ -d ".chip_check/$side/.perf_trace" ] || continue
  ( cd ".chip_check/$side" && PYTHONPATH=. python3 "$OLDPWD/benchmarks/decode_step_ops.py" \
      .perf_trace "$steps" 30 > "$out/${cell}_decode_ops_$side.txt" 2> /dev/null )
  echo "$side: $(head -n 1 "$out/${cell}_decode_ops_$side.txt")"
done
