"""Language-model program, whole step, for one chip's share of a
latent-attention model: the operations the traced window's real tokens need
(chipbench/work_lm_mla.py: per layer the five attention projections, the
dense FFN or the shared experts, the router and the held share of a token's
routed experts; every causal pair's score and value; the head over the
slice of the vocabulary; padding counts for nothing) over the window's wall
time x the chips' bf16 peak (chipbench/peaks.json)."""

from chipbench import work_lm_mla
from chipbench.metrics import attn_index_ms_per_batch as attn


def read(trace: dict, cell: dict) -> "float | None":
    peaks = cell["peaks"]
    lengths = attn.window_lengths(cell)
    if not lengths or not peaks or trace["window_s"] <= 0:
        return None
    flops = work_lm_mla.step_flops(cell["config"], lengths)
    return 100.0 * flops / (trace["window_s"] * peaks["bf16_flops_per_s"] * cell["chips"])
