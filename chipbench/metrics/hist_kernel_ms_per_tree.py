"""Histogram kernels: summed duration of the Pallas (Mosaic) custom calls
on one device inside the traced window, per tree (xplane)."""


def read(trace: dict, cell: dict) -> "float | None":
    trees = cell["shapes"].get("trees")
    if not trees or not trace.get("kernel_s"):
        return None
    return 1e3 * trace["kernel_s"] / trees
