"""Sparse attention: of the causal keys the real positions of the traced
window had, the percent they attended —
``mmlspark_lm_attn_keys_total{kind="selected"}`` over ``{kind="causal"}``,
both counted on the device over real positions and summed over the layers.
A counter has no history, so the window's part of it is what the
``lm.score`` spans inside the window say they added (attrs
``attn_keys_selected`` / ``attn_keys_causal``); the counter itself must hold
at least that much. What the lengths alone give (position ``t`` keeps
``min(t + 1, topk)`` of ``t + 1``) is said beside it on stderr: an exact
selection reads the same (program counter, chipbench/program_trace.py)."""

from chipbench import program_trace, work_lm_sparse
from chipbench.metrics import attn_index_ms_per_batch as attn
from chipbench.metrics import lm_pad_token_share as tokens


def read(trace: dict, cell: dict) -> "float | None":
    run = program_trace.of_run(trace)
    if run is None:
        return None
    spans = run.in_window("lm.score")
    selected = sum(s["attrs"].get("attn_keys_selected", 0) for s in spans)
    causal = sum(s["attrs"].get("attn_keys_causal", 0) for s in spans)
    if causal <= 0:
        return None
    total = tokens.counter("mmlspark_lm_attn_keys_total", "kind")
    if total.get("selected", 0) < selected or total.get("causal", 0) < causal:
        raise ValueError(f"the window's spans add {selected}/{causal} keys, more than "
                         f"the counter holds ({total})")
    lengths = attn.window_lengths(cell)
    topk = cell["config"]["sa_config"]["topk"]
    program_trace.say("attn_keys", {
        "selected": selected, "causal": causal, "counter": total,
        "share_by_lengths": 100.0 * work_lm_sparse.attended_pairs(lengths, topk)
        / max(1.0, work_lm_sparse.index_pairs(lengths))})
    return 100.0 * selected / causal
