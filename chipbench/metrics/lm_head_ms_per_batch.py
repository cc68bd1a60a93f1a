"""Language-model program: device time under ``lm.head`` — the last norm,
the vocabulary's logits a block of tokens at a time, their log-sum-exp and
the targets' logits — per batch (device trace)."""

from chipbench.metrics import moe_experts_ms_per_batch as experts


def read(trace: dict, cell: dict) -> "float | None":
    return experts.per_batch_ms(trace, cell, ("lm.head",))
