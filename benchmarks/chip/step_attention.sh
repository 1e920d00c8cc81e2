#!/bin/bash
# The one-token form of a full-depth softmax layer alone on the chip: the
# step kernel against the XLA text at the four sequence cells' sizes and
# the latent layer's (xing4_latent) at the Xing4 cell's (a line a case,
# appended to chiprun_out/step_attention_alone.jsonl), then the on-chip
# tests of both.
#   chiprun --timeout 900 -- bash benchmarks/chip/step_attention.sh [<case> ...] [<block_k> ...]
set -u
out=$PWD/chiprun_out; mkdir -p "$out"
export PYTHONPATH=.
python3 benchmarks/profile_fragment_attention.py step "$@" | tee -a "$out/step_attention_alone.jsonl"
RAY_TPU_HW_TEST=1 python3 -m pytest tests/test_tpu_hardware.py -q -m "" -k "step_attention or latent_step" \
  -p no:cacheprovider 2>&1 | tail -n 30
