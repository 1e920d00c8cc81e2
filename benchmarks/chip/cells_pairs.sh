#!/bin/bash
# One pair a cell, parent beside change (checkouts under .chip_check/),
# each cell on a compile cache of its own (benchmarks/chip/sides.sh):
#   chiprun --timeout 3500 -- bash benchmarks/chip/cells_pairs.sh <first seed> <cell> [<cell> ...]
# A cell named "<cell>@change" runs the change alone on three seeds (a
# cell the parent cannot run).
s=$1; shift
for cell in "$@"; do
  if [ "${cell%@change}" != "$cell" ]; then
    runs="change:$s change:$((s + 11)) change:$((s + 23))"
  else
    runs="parent:$s change:$s"
  fi
  bash benchmarks/chip/sides.sh "${cell%@change}" $runs 2>&1 | grep -v "^\[setup\]" | cut -c1-600
  s=$((s + 102))
done
