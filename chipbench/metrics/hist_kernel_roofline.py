"""Histogram kernels: per call the larger of bytes over the memory peak and
operations over the compute peak, counted from the cell's shapes whatever
implements the call (chipbench/work.py: n rows per device), times the
histogram calls of the traced window, over their summed time. Memory-bound
at these shapes (bytes/819 GB/s is ~1,000x ops/197 TFLOP/s).

A custom call that on average ends sooner than that least time made no
pass over the device's rows: it is
no histogram call and counts on neither side of the share."""

from chipbench import work, xplane


def read(trace: dict, cell: dict) -> "float | None":
    shapes, peaks = cell["shapes"], cell["peaks"]
    if not peaks:
        return None
    call = work.histogram_call(shapes["rows_per_device"], shapes["features"])
    least = max(call["bytes"] / peaks["hbm_bytes_per_s"], call["ops"] / peaks["bf16_flops_per_s"])
    calls, kernel_s = 0, 0.0
    for name, n in trace.get("op_counts", {}).items():
        seconds = trace["op_seconds"][name]
        if xplane.is_kernel_call(name) and seconds >= least * n:
            calls += n
            kernel_s += seconds
    if not calls:
        return None
    return 100.0 * least * calls / kernel_s
