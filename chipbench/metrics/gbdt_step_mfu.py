"""GBDT trainer, whole step: the least time any histogram GBDT needs per
tree on this many chips — one pass over a device's uint8 binned rows and
f32 stats at the memory peak (chipbench/work.py, chipbench/peaks.json) —
times the trees of the traced window, over the window's wall time."""

from chipbench import work


def read(trace: dict, cell: dict) -> "float | None":
    shapes, peaks = cell["shapes"], cell["peaks"]
    if not shapes.get("trees") or not peaks or trace["window_s"] <= 0:
        return None
    floor_s = work.gbdt_tree_floor_s(shapes["rows_per_device"], shapes["features"], peaks)
    return 100.0 * floor_s * shapes["trees"] / trace["window_s"]
