"""Sparse expert layer: device time under ``lm.ffn.shared`` — the shared
experts, one gated FFN every token passes through — per batch, all expert
layers together (device trace)."""

from chipbench.metrics import attn_latent_ms_per_batch as latent


def read(trace: dict, cell: dict) -> "float | None":
    return latent.per_batch_ms(trace, cell, "lm.ffn.shared")
