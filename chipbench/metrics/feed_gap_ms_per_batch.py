"""DataFrame -> device feed: device idle time inside the traced window,
per batch of the window (xplane)."""


def read(trace: dict, cell: dict) -> "float | None":
    batches = cell["shapes"].get("batches")
    if not batches or not trace.get("busy_s_each"):
        return None
    idle_s = trace["window_s"] - trace["busy_s_each"][0]
    return 1e3 * idle_s / batches
