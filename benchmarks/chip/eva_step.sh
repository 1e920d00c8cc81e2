#!/bin/bash
# The two-store one-token kernel alone on the chip at the EvaByte cell's
# sizes (benchmarks/profile_eva_step.py: us a call, GB/s over the bytes
# fetched and over perf/eva_model.eva_step_bytes, trips a call), the
# parent's walk first where .chip_check/parent holds a `git archive` of
# it, then the tree's, then flash_attention.step_attention at the Laguna
# cell's f32[16,8,8,128] tile as the yardstick (a line each, appended to
# chiprun_out/eva_step_alone.jsonl), then the on-chip test of the kernel.
# An argument is a form of the tree's kernel to try: spans in flight, or
# <blocks a span>:<spans in flight>.
#   chiprun --timeout 900 -- bash benchmarks/chip/eva_step.sh [[<blocks>:]<ahead> ...]
set -u
out=$PWD/chiprun_out; mkdir -p "$out"
[ -d .chip_check/parent/ray_tpu ] &&
  PYTHONPATH=.chip_check/parent python3 benchmarks/profile_eva_step.py no_sibling \
    | tee -a "$out/eva_step_alone.jsonl"
PYTHONPATH=. python3 benchmarks/profile_eva_step.py "$@" | tee -a "$out/eva_step_alone.jsonl"
RAY_TPU_HW_TEST=1 PYTHONPATH=. python3 -m pytest tests/test_tpu_hardware.py -q -m "" \
  -k "eva_step" -p no:cacheprovider 2>&1 | tail -n 30
