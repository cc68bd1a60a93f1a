"""Featurizer program, whole step: FLOPs per image counted from the layer
shapes (chipbench/work.py) x rows of the traced window, over the window's
wall time x the chips' bf16 peak (chipbench/peaks.json)."""

from chipbench import work


def read(trace: dict, cell: dict) -> "float | None":
    rows, peaks = cell["shapes"].get("rows"), cell["peaks"]
    if not rows or not peaks or trace["window_s"] <= 0:
        return None
    flops = work.resnet_flops_per_image(cell["config"]) * rows
    return 100.0 * flops / (trace["window_s"] * peaks["bf16_flops_per_s"] * cell["chips"])
