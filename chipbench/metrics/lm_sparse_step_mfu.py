"""Language-model program, whole step, where attention runs over an
indexer's selection: the operations the traced window's real tokens need
(chipbench/work_lm_sparse.py: every layer's products at the published head
width, the indexer's projections, every causal pair's index score, the
selected pairs' attention, the untied head; padding and keys that were not
selected count for nothing) over the window's wall time x the chips' bf16
peak (chipbench/peaks.json)."""

from chipbench import work_lm_sparse
from chipbench.metrics import attn_index_ms_per_batch as attn


def read(trace: dict, cell: dict) -> "float | None":
    peaks = cell["peaks"]
    lengths = attn.window_lengths(cell)
    if not lengths or not peaks or trace["window_s"] <= 0:
        return None
    flops = work_lm_sparse.step_flops(cell["config"], lengths)
    return 100.0 * flops / (trace["window_s"] * peaks["bf16_flops_per_s"] * cell["chips"])
