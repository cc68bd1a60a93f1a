"""Sparse expert layer, as one chip's share: the least time the chip could
take for the held experts' work — per expert layer and batch the larger of
operations over the compute peak and bytes over the memory peak, counted
from the shapes alone (chipbench/work_lm_mla.py: the ``batch_tokens x
experts per token x held / published`` rows a balanced router sends here
through three products, the held experts' matrices read once, the rows read
and written once) — times the expert layers and the batches of the traced
window, over the device time the experts took
(``moe_experts_ms_per_batch``). ``moe_held_pair_share`` says how far the run
was from the balance assumed."""

from chipbench import work_lm_mla
from chipbench.metrics import moe_experts_ms_per_batch as experts


def read(trace: dict, cell: dict) -> "float | None":
    peaks, shapes, config = cell["peaks"], cell["shapes"], cell["config"]
    ms = experts.per_batch_ms(trace, cell, (experts.EXPERTS,))
    if not peaks or not ms:
        return None
    call = work_lm_mla.held_experts_call(config, shapes["batch_tokens"])
    least = max(call["flops"] / peaks["bf16_flops_per_s"], call["bytes"] / peaks["hbm_bytes_per_s"])
    layers = config["num_hidden_layers"] - config["first_k_dense_replace"]
    return 100.0 * least * layers / (ms / 1e3)
