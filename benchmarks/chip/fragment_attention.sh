#!/bin/bash
# The learn form's attention alone on the chip: the kernel against the
# XLA text at the three sequence cells' sizes (a line a case, appended to
# chiprun_out/fragment_attention_alone.jsonl), then the on-chip tests of it.
# Cases by name: smallthinker_full, smallthinker_ring, qwen3next, granite4h,
# xing4_latent, and keye2_selected (the learned-index cell's layer: the text
# under a choice against the kernel with the choice as its operand, and
# the kernel without one beside them).
#   chiprun --timeout 1500 -- bash benchmarks/chip/fragment_attention.sh [<block_k> ...]
# A cell's runs with the counter and the statistic: benchmarks/chip/sides.sh.
set -u
out=$PWD/chiprun_out; mkdir -p "$out"
export PYTHONPATH=.
python3 benchmarks/profile_fragment_attention.py "$@" | tee -a "$out/fragment_attention_alone.jsonl"
RAY_TPU_HW_TEST=1 python3 -m pytest tests/test_tpu_hardware.py -q -m "" -k fragment \
  -p no:cacheprovider 2>&1 | tail -n 30
