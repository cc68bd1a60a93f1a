"""Language-model program, whole step: the operations the traced window's
real tokens need (chipbench/work_lm.py: every layer's products, the causal
attention pairs of every row, the head; padding counts for nothing) over
the window's wall time x the chips' bf16 peak (chipbench/peaks.json)."""

from chipbench import work_lm


def read(trace: dict, cell: dict) -> "float | None":
    shapes, peaks = cell["shapes"], cell["peaks"]
    if not shapes.get("tokens_real") or not peaks or trace["window_s"] <= 0:
        return None
    flops = work_lm.step_flops(cell["config"], shapes["rows"], shapes["tokens_real"],
                               shapes["tokens_real_sq"])
    return 100.0 * flops / (trace["window_s"] * peaks["bf16_flops_per_s"] * cell["chips"])
