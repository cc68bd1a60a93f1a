"""Collectives: summed duration of the all-reduce operations on one device
inside the traced window, per tree (xplane). Silent on one chip."""


def read(trace: dict, cell: dict) -> "float | None":
    trees = cell["shapes"].get("trees")
    if not trees or not trace.get("all_reduce_s"):
        return None
    return 1e3 * trace["all_reduce_s"] / trees
