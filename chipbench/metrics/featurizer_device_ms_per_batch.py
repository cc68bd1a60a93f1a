"""Featurizer program: union of device-operation time inside the traced
window, per batch of the window (xplane)."""


def read(trace: dict, cell: dict) -> "float | None":
    batches = cell["shapes"].get("batches")
    if not batches or not trace.get("busy_s_each") or trace["busy_s_each"][0] <= 0:
        return None
    return 1e3 * trace["busy_s_each"][0] / batches
