"""Sparse attention: device time under ``lm.attn.sparse`` — the attention
scores of a block of queries, their softmax over the selected keys and the
sum over the values — per batch, all layers together (device trace)."""

from chipbench.metrics import attn_index_ms_per_batch as attn


def read(trace: dict, cell: dict) -> "float | None":
    return attn.per_batch_ms(trace, cell, ("lm.attn.sparse",))
