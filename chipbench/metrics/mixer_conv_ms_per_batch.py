"""Language-model program: device time under ``lm.mixer.conv`` — the gated
short convolutions' two projections, gates and taps — per batch, all
convolution layers together (device trace)."""

from chipbench.metrics import moe_experts_ms_per_batch as experts


def read(trace: dict, cell: dict) -> "float | None":
    return experts.per_batch_ms(trace, cell, ("lm.mixer.conv",))
