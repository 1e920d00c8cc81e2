#!/bin/bash
# The DQN cell on one compile cache directory of the call's own: a run
# that fills it, then parent / change pairs (perf/chip/pairs.sh), what
# tracing costs when it is on (benchmarks/chip/tracing_cost.sh) and one
# traced pair (perf/chip/traced_pair.sh: the parent carries this tree's
# benchmark files, so it also shows the new readers saying nothing there).
#   chiprun --timeout 3550 -- bash benchmarks/chip/dqn_sides_and_cost.sh <seed0>
set -u
cell=dqn_per.fused.1chip
seed=$1
export JAX_COMPILATION_CACHE_DIR=$(mktemp -d)
( cd .chip_check/parent && python3 -m perf.run --workload $cell --seed "$seed" \
    --seconds 5 --trace 0 2>&1 | grep -E "^\[setup\]" | cut -c1-300 )
bash perf/chip/pairs.sh $cell 30 $((seed + 12)) $((seed + 30))
bash benchmarks/chip/tracing_cost.sh $cell 30 $((seed + 48)) $((seed + 66)) $((seed + 84))
bash perf/chip/traced_pair.sh $cell 30 $((seed + 102)) 2>&1 | cut -c1-1500
rm -rf "$JAX_COMPILATION_CACHE_DIR"
