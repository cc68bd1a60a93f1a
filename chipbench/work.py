"""Operations and bytes each cell's work needs, counted from its shapes.

Kept with the benchmark so that no PR that claims a gain can change the
yardstick. Nothing here reads the program or a trace.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The table of peaks, keyed by ``device_kind``; unknown = an error."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in chipbench/peaks.json")
    return table[device_kind]


def resnet_flops_per_image(config: dict) -> float:
    """Multiply-adds x 2 of every convolution of the featurizer, stem to
    the last block (the pooled features need no head)."""
    from chipbench.reference.resnet import conv_table

    total = 0.0
    for _name, k, stride, c_in, c_out, h_in in conv_table(config):
        h_out = -(-h_in // stride)
        total += 2.0 * h_out * h_out * k * k * c_in * c_out
    return total


def histogram_call(rows: int, features: int, bins: int = 256, stats: int = 3) -> dict:
    """One histogram pass over ``rows`` binned rows, whatever implements it:
    read the uint8 bin matrix and the f32 stats once, write one plane."""
    return {
        "bytes": rows * features * 1.0 + rows * stats * 4.0 + features * bins * stats * 4.0,
        "ops": 2.0 * rows * features * stats,
    }


def gbdt_tree_floor_s(rows: int, features: int, peak: dict, stats: int = 3) -> float:
    """The least time any histogram GBDT needs for one tree on one device:
    one pass over the uint8 binned matrix and the f32 stats at the memory
    peak."""
    return (rows * features * 1.0 + rows * stats * 4.0) / peak["hbm_bytes_per_s"]
