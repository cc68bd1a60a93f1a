"""Language-model program: device time under ``lm.mixer.attn`` — the
attention layers' projections, QK norm, RoPE and the blockwise causal
attention — per batch, all attention layers together; grows with the
bucket's length where everything else is per token (device trace)."""

from chipbench.metrics import moe_experts_ms_per_batch as experts


def read(trace: dict, cell: dict) -> "float | None":
    return experts.per_batch_ms(trace, cell, ("lm.mixer.attn",))
