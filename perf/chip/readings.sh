#!/bin/bash
# The readings every `correct` limit is set from, then two short trial
# runs of the cell. One chip:
#   chiprun --timeout 1500 -- bash perf/chip/readings.sh <cell> <seeds> <trial seed> <traced seed>
# Everything lands in chiprun_out/ (readings_<cell>.log, trial_*.log).
set -u
cell=$1; seeds=$2; trial=$3; traced=$4
mkdir -p chiprun_out
python3 -m perf.control --workload "$cell" --seeds "$seeds" \
  > "chiprun_out/readings_$cell.log" 2>&1
echo "control rc=$?"
grep -c '^\[control\]' "chiprun_out/readings_$cell.log"
tail -n 1 "chiprun_out/readings_$cell.log"
python3 -m perf.run --workload "$cell" --seed "$trial" --seconds 10 --trace 0 \
  > "chiprun_out/trial_${cell}_$trial.log" 2>&1
echo "trial rc=$?"
grep -E '^\[(setup|window)\]' "chiprun_out/trial_${cell}_$trial.log"
tail -n 1 "chiprun_out/trial_${cell}_$trial.log" | cut -c1-1500
python3 -m perf.run --workload "$cell" --seed "$traced" --seconds 10 --trace 1 \
  > "chiprun_out/traced_${cell}_$traced.log" 2>&1
echo "traced rc=$?"
grep -E '^\[(setup|window)\]' "chiprun_out/traced_${cell}_$traced.log"
tail -n 1 "chiprun_out/traced_${cell}_$traced.log" | cut -c1-6000
