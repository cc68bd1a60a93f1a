"""Sparse expert layer: device time around the experts' products — router
scores, top-k and the per-expert counts (``lm.moe.route``), the sort of the
(token, expert) pairs and the gather of their rows (``lm.moe.dispatch``),
the gather back and the weighted sum (``lm.moe.combine``) — per batch, all
expert layers together: the memory-bound part of the layer (device trace)."""

from chipbench.metrics import moe_experts_ms_per_batch as experts


def read(trace: dict, cell: dict) -> "float | None":
    return experts.per_batch_ms(trace, cell, ("lm.moe.route", "lm.moe.dispatch", "lm.moe.combine"))
