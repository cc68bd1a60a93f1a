"""GBDT trainer: host time a fit spends inside its ``gbdt.upload`` spans —
padding plus ``shard_batch`` / ``jnp.asarray`` of bins, weights, labels and
scores — mean per fit of the traced window. The host's time in the calls:
a transfer still in flight when they return shows under
``gbdt.chunk.wait`` (program spans, chipbench/program_trace.py)."""

from chipbench import program_trace


def read(trace: dict, cell: dict) -> "float | None":
    return program_trace.per_root_ms(trace, "gbdt.fit", ("gbdt.upload",))
