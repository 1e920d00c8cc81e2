#!/bin/bash
# A new cell's first call, from the working tree: ONE traced run (does it
# fit, what does a step cost, which lowerings did it take), its trace
# reduced twice (by program and scope; the decode step by operation),
# then the controls' readings on a few seeds for the limits file:
#   chiprun --timeout 3500 -- bash benchmarks/chip/new_cell_first.sh <cell> <traced seed> <control seeds> <first control seed> <fragment steps>
# Everything lands in chiprun_out/ (traced_*.log, program_trace_*.txt,
# decode_ops_*.txt, readings_*.log). PR 50's took 13.7 chip-minutes.
set -u
cell=$1; traced=$2; seeds=$3; first=$4; steps=$5
mkdir -p chiprun_out
python3 -m perf.run --workload "$cell" --seed "$traced" --seconds 10 --trace 1 \
  > "chiprun_out/traced_${cell}_$traced.log" 2>&1
echo "traced rc=$?"
grep -E '^\[(setup|window|setup-part)\]' "chiprun_out/traced_${cell}_$traced.log" | cut -c1-600
tail -n 1 "chiprun_out/traced_${cell}_$traced.log" | cut -c1-7000
python3 -m perf.program_trace .perf_trace > "chiprun_out/program_trace_$cell.txt" 2>&1
PYTHONPATH=. python benchmarks/decode_step_ops.py .perf_trace "$steps" \
  > "chiprun_out/decode_ops_$cell.txt" 2>&1
python3 -m perf.control --workload "$cell" --seeds "$seeds" --first-seed "$first" \
  > "chiprun_out/readings_$cell.log" 2>&1
echo "control rc=$?"
grep '^\[control\]' "chiprun_out/readings_$cell.log" | cut -c1-3000
tail -n 1 "chiprun_out/readings_$cell.log" | cut -c1-4000
