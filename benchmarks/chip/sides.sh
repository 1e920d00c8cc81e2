#!/bin/bash
# Runs of one cell from several checkouts under .chip_check/ in the
# order given, all on ONE compile cache directory of the call's own, each
# through benchmarks/setup_account.py so that every run of a tree that
# keeps the account says which program families missed the cache:
#   chiprun --timeout 3400 -- bash benchmarks/chip/sides.sh <cell> <side>:<seed>[:1] [<side>:<seed>[:1] ...]
# (":1" traces the run and leaves perf.program_trace's summary of it in
# chiprun_out/program_trace_<cell>_<side>_<seed>.json.)
# <side> is a directory under .chip_check/ (change, parent, moved: the
# change with one line inserted at the top of models/sequence_lm/model.py).
# "parent:S change:S" is a pair on one seed; the first run fills the cache.
# Lines land in chiprun_out/sides_<cell>_<side>.jsonl.
set -u
cell=$1; shift
out=$PWD/chiprun_out; mkdir -p "$out"
export JAX_COMPILATION_CACHE_DIR=$(mktemp -d)
n=0
for run in "$@"; do
  IFS=: read -r side seed trace <<< "$run"; trace=${trace:-0}; n=$((n + 1))
  log="$out/sides_${cell}_${n}_${side}_$seed.log"
  t0=$(date +%s)
  ( cd ".chip_check/$side" && PYTHONPATH=. python3 "$OLDPWD/benchmarks/setup_account.py" \
      "$out/sides_${cell}_$side.jsonl" --workload "$cell" --seed "$seed" \
      --seconds 30 --trace "$trace" > "$log" 2>&1 )
  echo "$n $side seed $seed rc=$? in $(( $(date +%s) - t0 )) s; cache $(find "$JAX_COMPILATION_CACHE_DIR" -type f -printf '%s\n' | awk '{s += $1} END {print s, "B in", NR, "files"}')"
  [ "$trace" = 1 ] && ( cd ".chip_check/$side" && python3 -m perf.program_trace .perf_trace \
      > "$out/program_trace_${cell}_${side}_$seed.json" 2> /dev/null )
  grep -E "^\[setup\]" "$log" | cut -c1-330
  tail -n 1 "$log" | cut -c1-300
done
rm -rf "$JAX_COMPILATION_CACHE_DIR"
python3 - "$out" "$cell" <<'PY'
import glob, json, sys
out, cell = sys.argv[1:]
for path in sorted(glob.glob(f"{out}/sides_{cell}_*.jsonl")):
    for raw in open(path):
        r = json.loads(raw)
        fam = r.get("families") or {}
        missed = {k: v["cache_misses"] for k, v in fam.items() if v["cache_misses"]}
        m = {k: round(v["value"], 3) for k, v in r["result"]["metrics"].items()}
        print(path.rsplit("_", 1)[-1], r["result"]["seed"], r["result"].get("correct"), m,
              "missed:", missed, (r.get("lowerings") or {}).get("attention_fragment_lowerings"),
              "step:", (r.get("lowerings") or {}).get("attention_step_lowerings"),
              r.get("learn_stats"), r.get("host_reads"), "iterations:", r["result"].get("attempted"))
PY
