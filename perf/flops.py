"""Operations and bytes the algorithms need, from shapes alone: the
arithmetic the FLOP rules share and the table of peaks. A model
family's own rule is ``perf/flop_rules/<flops_family>.py``
(``Cell.flop_rule()``).

Copy of the sound arithmetic of ``bench.py:nature_cnn_train_flops_per_sample``
(listed in PERF.md for deletion there), generalized to the filter
list and input shape a configuration file states. A multiply-add
counts as two operations; the backward pass as twice the forward;
recomputed operations are not counted.
"""

from __future__ import annotations

import json
import os
from typing import Dict, Sequence, Tuple

_HERE = os.path.dirname(os.path.abspath(__file__))


def conv_out_hw(hw: int, kernel: int, stride: int) -> int:
    """VALID padding."""
    return (hw - kernel) // stride + 1


def nature_cnn_layers(
    input_shape: Sequence[int], conv_filters, dense: Sequence[int], heads: int
) -> Tuple[Dict, ...]:
    """One dict per layer: ``macs`` per sample, and the bytes of its
    input activation, weights and output activation per sample in the
    compute dtype (2 bytes, bf16) — what a roofline share divides by."""
    h, w, c = (int(x) for x in input_shape)
    layers = []
    for i, (out_c, kernel, stride) in enumerate(conv_filters):
        k = int(kernel[0] if isinstance(kernel, (list, tuple)) else kernel)
        s = int(stride[0] if isinstance(stride, (list, tuple)) else stride)
        oh, ow = conv_out_hw(h, k, s), conv_out_hw(w, k, s)
        layers.append(
            {
                "name": f"conv{i}",
                "macs": oh * ow * int(out_c) * k * k * c,
                "in_elems": h * w * c,
                "weight_elems": k * k * c * int(out_c),
                "out_elems": oh * ow * int(out_c),
            }
        )
        h, w, c = oh, ow, int(out_c)
    feat = h * w * c
    for j, width in enumerate(dense):
        layers.append(
            {
                "name": f"dense{j}",
                "macs": feat * int(width),
                "in_elems": feat,
                "weight_elems": feat * int(width),
                "out_elems": int(width),
            }
        )
        feat = int(width)
    layers.append(
        {
            "name": "heads",
            "macs": feat * int(heads),
            "in_elems": feat,
            "weight_elems": feat * int(heads),
            "out_elems": int(heads),
        }
    )
    return tuple(layers)


def forward_flops_per_sample(model: Dict, heads: int) -> float:
    return 2.0 * sum(
        layer["macs"]
        for layer in nature_cnn_layers(
            model["input_shape"],
            model["conv_filters"],
            model["dense"],
            heads,
        )
    )


def load_peaks(device_kind: str) -> Dict:
    """The row of ``peaks.json`` for this device kind. A kind that is
    not in the table is an error, never a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(
            f"perf/peaks.json has no row for device kind {device_kind!r}"
        )
    return table[device_kind]
