"""GBDT trainer: host time a fit spends binning — the program's
``gbdt.bin_fit`` (the bin edges, from a sample) and ``gbdt.bin_transform``
(every cell to its uint8 bin) spans under each ``gbdt.fit`` root of the
traced window, mean per fit (program spans, chipbench/program_trace.py)."""

from chipbench import program_trace


def read(trace: dict, cell: dict) -> "float | None":
    return program_trace.per_root_ms(trace, "gbdt.fit", ("gbdt.bin_fit", "gbdt.bin_transform"))
