"""Latent attention: device time under ``lm.attn.pairs`` — scores, softmax
and values over the causal pairs (the Pallas kernel ``latent_attend`` on a
TPU) — per batch, all layers together (device trace)."""

from chipbench.metrics import attn_latent_ms_per_batch as latent


def read(trace: dict, cell: dict) -> "float | None":
    return latent.per_batch_ms(trace, cell, "lm.attn.pairs")
