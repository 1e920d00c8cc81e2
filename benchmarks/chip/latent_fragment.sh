#!/bin/bash
# The latent layer's fragment form on the chip: the layer alone (the
# expanded text against the absorbed product on the kernel, a line
# appended to chiprun_out/fragment_attention_alone.jsonl), the on-chip
# tests of the fragment kernel, then runs of the Xing4 cell from the
# checkouts under .chip_check/ (benchmarks/chip/sides.sh's arguments).
#   chiprun --timeout 3400 -- bash benchmarks/chip/latent_fragment.sh parent:<seed>:1 change:<seed>:1 ...
set -u
out=$PWD/chiprun_out; mkdir -p "$out"
export PYTHONPATH=.
python3 benchmarks/profile_fragment_attention.py xing4_latent 2> "$out/latent_alone.err" \
  | tee -a "$out/fragment_attention_alone.jsonl"
tail -n 3 "$out/latent_alone.err" | cut -c1-300
RAY_TPU_HW_TEST=1 python3 -m pytest tests/test_tpu_hardware.py -q -m "" -k "fragment or latent" \
  -p no:cacheprovider 2>&1 | tail -n 15
[ $# -gt 0 ] && bash benchmarks/chip/sides.sh xing4_ppo.fused_tokens.1chip "$@"
