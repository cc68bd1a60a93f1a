"""Sparse expert layer: the least time the chip could take for the experts'
work — per expert layer and batch the larger of operations over the compute
peak and bytes over the memory peak, counted from the shapes alone
(chipbench/work_lm.py: every token's routed rows through three products,
every expert's weights read once; compute-bound at these shapes) — times
the expert layers and the batches of the traced window, over the device
time the experts took (``moe_experts_ms_per_batch``)."""

from chipbench import work_lm
from chipbench.metrics import moe_experts_ms_per_batch as experts


def read(trace: dict, cell: dict) -> "float | None":
    peaks, shapes = cell["peaks"], cell["shapes"]
    ms = experts.per_batch_ms(trace, cell, (experts.EXPERTS,))
    if not peaks or not ms:
        return None
    call = work_lm.experts_call(cell["config"], shapes["batch_tokens"])
    least = max(call["flops"] / peaks["bf16_flops_per_s"], call["bytes"] / peaks["hbm_bytes_per_s"])
    layers = sum(1 for _mixer, ffn in work_lm.layer_kinds(cell["config"]) if ffn == "moe")
    return 100.0 * least * layers / (ms / 1e3)
