"""Sparse expert layer, as one chip's share: of the (token, expert) pairs
the router chose for the real tokens of the traced window, the percent that
fell on the experts this chip holds — from
``mmlspark_moe_tokens_routed_total{expert}``, summed over the expert layers.
Symmetric groups give the held share of the experts (12.5% for one group of
eight); the expert layer's work follows this number, not ``tokens x
experts per token``. A counter has no history, so the window's part of it is
what the ``lm.score`` spans inside the window say they added (attrs
``moe_pairs_held`` / ``moe_pairs_routed``); the counter itself must hold at
least that much. A program whose spans carry no such attribute (one that
holds every expert) reads nothing (program counter,
chipbench/program_trace.py)."""

from chipbench import program_trace
from chipbench.metrics import lm_pad_token_share as tokens


def read(trace: dict, cell: dict) -> "float | None":
    run = program_trace.of_run(trace)
    if run is None:
        return None
    spans = run.in_window("lm.score")
    held = sum(s["attrs"].get("moe_pairs_held", 0) for s in spans)
    routed = sum(s["attrs"].get("moe_pairs_routed", 0) for s in spans)
    if routed <= 0:
        return None
    total = sum(tokens.counter("mmlspark_moe_tokens_routed_total", "expert").values())
    if total < routed:
        raise ValueError(f"the window's spans add {routed} routed pairs, more than "
                         f"the counter holds ({total})")
    program_trace.say("moe_pairs", {"held": held, "routed": routed, "counter": total})
    return 100.0 * held / routed
