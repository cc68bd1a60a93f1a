"""GBDT trainer: host time a fit spends after its trees reached the host —
``gbdt.chunk.unpack`` (the packed record back into trees) and
``gbdt.model_string`` — mean per fit of the traced window (program spans,
chipbench/program_trace.py)."""

from chipbench import program_trace


def read(trace: dict, cell: dict) -> "float | None":
    return program_trace.per_root_ms(
        trace, "gbdt.fit", ("gbdt.chunk.unpack", "gbdt.model_string"))
