"""Sparse attention: the least time the chip could take for the mechanism
— per layer the larger of operations over the compute peak and bytes over
the memory peak, counted from the shapes alone (chipbench/work_lm_sparse.py:
every causal pair's index score, two products of the heads' whole width for
every selected pair, q / k / v and the indexer's projections read once;
compute-bound at these lengths) for the real tokens the traced window
scored — over the device time under ``lm.attn.index``, ``lm.attn.select``
and ``lm.attn.sparse``. The selection itself counts no operation, so its
time is all loss here; a masked pass over keys that were not selected
counts for nothing either."""

from chipbench import work_lm_sparse
from chipbench.metrics import attn_index_ms_per_batch as attn


def read(trace: dict, cell: dict) -> "float | None":
    peaks = cell["peaks"]
    found = attn.by_scope(trace)
    lengths = attn.window_lengths(cell)
    if not peaks or not found or not lengths:
        return None
    seconds = sum(found.get(s, 0.0) for s in attn.SCOPES)
    if seconds <= 0:
        return None
    config = cell["config"]
    least = max(work_lm_sparse.attention_flops(config, lengths) / peaks["bf16_flops_per_s"],
                work_lm_sparse.attention_bytes(config, sum(lengths)) / peaks["hbm_bytes_per_s"])
    return 100.0 * least * config["num_hidden_layers"] / seconds
