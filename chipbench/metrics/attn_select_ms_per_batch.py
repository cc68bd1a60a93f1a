"""Sparse attention: device time under ``lm.attn.select`` — the scores'
order-preserving integers, the passes that build each query's k-th largest,
the comparison that is the selection and the count of the keys kept — per
batch, all layers together: comparisons and counts, no products (device
trace)."""

from chipbench.metrics import attn_index_ms_per_batch as attn


def read(trace: dict, cell: dict) -> "float | None":
    return attn.per_batch_ms(trace, cell, ("lm.attn.select",))
