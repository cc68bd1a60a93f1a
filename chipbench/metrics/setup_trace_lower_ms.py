"""Entry points, device bootstrap: union of the ``xla.trace`` and
``xla.lower`` spans that ended before the window began — every program's
first call traced to a jaxpr and lowered to an MLIR module (Pallas kernels'
Mosaic lowering included), which a cache hit does not spare. A ``jit``
inside a ``jit`` fires a nested ``xla.trace``: the union, not the sum. The
set-up's compile requests, one row each (``setup_first_calls``), go to
standard error beside it (program spans, chipbench/setup_trace.py)."""

from chipbench import program_trace, setup_trace


def read(trace: dict, cell: dict) -> "float | None":
    spans = setup_trace.before_window(trace)
    ms = setup_trace.named_ms(spans, (setup_trace.TRACE, setup_trace.LOWER))
    if ms is not None:
        program_trace.say("setup_first_calls", setup_trace.first_calls(spans))
    return ms
