"""GBDT trainer: per fit, wall time minus the span from the first to the
last device operation of that fit (binning, upload, tree unpack and
whatever else the host does outside the device program), mean over the
fits of the traced window (xplane + the driver's ``chipbench.fit`` spans)."""


def read(trace: dict, cell: dict) -> "float | None":
    fits = [f for f in trace.get("fits", []) if f["device_span_s"] > 0]
    if not fits:
        return None
    return 1e3 * sum(f["wall_s"] - f["device_span_s"] for f in fits) / len(fits)
