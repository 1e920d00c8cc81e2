#!/bin/bash
# First chip call after a change to the sequence cell's comparisons: ONE
# run of the cell from the working tree, then (only if it ran) the
# readings every limit is set from, on 4 seeds (about 8 chip-minutes a
# seed: each control is a whole reference iteration at 8,192 tokens):
#   chiprun --timeout 3300 -- bash perf/chip/first_and_control.sh
set -u
out=$PWD/chiprun_out; mkdir -p "$out"
cell=qwen3next_ppo.fused_tokens.1chip
t0=$(date +%s)
python3 -m perf.run --workload $cell --seed 2899999927 --seconds 30 --trace 0 > "$out/seq_first.log" 2>&1
rc=$?
echo "first run rc=$rc in $(( $(date +%s) - t0 )) s"
grep -E "^\[correct\]|^\[setup\]|^\[window\]|Error|error" "$out/seq_first.log" | cut -c1-500
tail -n 1 "$out/seq_first.log" | cut -c1-700
if [ $rc -ne 0 ]; then tail -n 40 "$out/seq_first.log"; exit $rc; fi
t0=$(date +%s)
python3 -m perf.control --workload $cell --seeds 4 --first-seed 2147490001 > "$out/seq_control.log" 2>&1
echo "control rc=$? in $(( $(date +%s) - t0 )) s"
grep -E "^\[control\]" "$out/seq_control.log" | cut -c1-3000
tail -n 1 "$out/seq_control.log" | cut -c1-3000
