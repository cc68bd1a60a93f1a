"""GBDT trainer: union of device-operation time inside the traced window,
per tree grown in it (xplane)."""


def read(trace: dict, cell: dict) -> "float | None":
    trees = cell["shapes"].get("trees")
    if not trees or not trace.get("busy_s_each") or trace["busy_s_each"][0] <= 0:
        return None
    return 1e3 * trace["busy_s_each"][0] / trees
