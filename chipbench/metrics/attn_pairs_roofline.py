"""Latent attention: the least time the chip could take for the causal
pairs — per layer the larger of operations over the compute peak and bytes
over the memory peak, counted from the shapes alone
(chipbench/work_lm_mla.py: a score of 192 and a value of 128 for each of
128 heads a causal pair of the real rows, 81,920 operations; q, k_n, the
shared k_r and v read once, the result written once; padding and the
masked half of a diagonal tile count for nothing; compute-bound at these
lengths) for the real rows the traced window scored — times the layers,
over the device time under ``lm.attn.pairs``."""

from chipbench import work_lm_mla
from chipbench.metrics import attn_index_ms_per_batch as attn
from chipbench.metrics import attn_latent_ms_per_batch as latent


def least_s(cell: dict) -> "float | None":
    """The window's pairs at the peaks, all layers; None without lengths."""
    peaks, lengths = cell["peaks"], attn.window_lengths(cell)
    if not peaks or not lengths:
        return None
    call = work_lm_mla.pairs_call(cell["config"], lengths)
    return cell["config"]["num_hidden_layers"] * max(
        call["flops"] / peaks["bf16_flops_per_s"], call["bytes"] / peaks["hbm_bytes_per_s"])


def read(trace: dict, cell: dict) -> "float | None":
    found = latent.by_scope(trace)
    least = least_s(cell)
    if not found or not least or found.get("lm.attn.pairs", 0.0) <= 0:
        return None
    return 100.0 * least / found["lm.attn.pairs"]
