#!/bin/bash
# What tracing costs the untraced window when it is ON, and whether the
# cost grows through the window (perf/chip/tracing_cost.sh's three sides
# and the parent with tracing on, each through benchmarks/setup_account.py,
# which also writes the median wall of the window's first and last 100
# iterations). Prepare .chip_check/{change,parent} (git archive), then:
#   chiprun --timeout 3400 -- bash benchmarks/chip/tracing_cost.sh <cell> <seconds> <seed> [<seed> ...]
# Lines land in chiprun_out/<cell>_cost_{off,on,parent,parent_on}.jsonl.
set -u
cell=$1; seconds=$2; shift 2
out=$PWD/chiprun_out; mkdir -p "$out"
for seed in "$@"; do
  for side in off on parent parent_on; do
    dir=.chip_check/change; trace=0
    case $side in parent*) dir=.chip_check/parent;; esac
    case $side in on|parent_on) trace=1;; esac
    log="$out/${cell}_cost_${side}_$seed.log"
    ( cd "$dir" && RAY_TPU_TRACE=$trace PYTHONPATH=. python3 "$OLDPWD/benchmarks/setup_account.py" \
        "$out/${cell}_cost_$side.jsonl" --workload "$cell" --seed "$seed" \
        --seconds "$seconds" --trace 0 > "$log" 2>&1 )
    echo "$side seed $seed rc=$? $(grep -E '^\[window\] seconds' "$log" | cut -c1-200)"
    tail -n 1 "$log" | cut -c1-330
  done
done
python3 - "$out" "$cell" <<'PY'
import json, sys
out, cell = sys.argv[1:]
for side in ("off", "on", "parent", "parent_on"):
    for raw in open(f"{out}/{cell}_cost_{side}.jsonl"):
        r = json.loads(raw)
        m = r["result"]["metrics"]
        print(side, r["result"]["seed"], {k: round(v["value"], 3) for k, v in m.items()}, r.get("walls_ms"))
PY
