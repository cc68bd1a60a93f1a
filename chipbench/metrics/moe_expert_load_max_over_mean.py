"""Sparse expert layer: the busiest expert's routed tokens over the mean
expert's, from ``mmlspark_moe_tokens_routed_total{expert}`` — real tokens
per expert summed over the expert layers, carried out of the program with
every batch's output: 1 is a balanced router; the grouped product's longest
group, and an expert range's chip in a sharded deployment, wait on the
busiest. The counter runs from the start of the process (the warm-up chunk
is of the same traffic); it is read only for a run whose window holds
``lm.score`` spans, and a program without it reads nothing (program
counter)."""

from chipbench import program_trace
from chipbench.metrics import lm_pad_token_share as tokens


def read(trace: dict, cell: dict) -> "float | None":
    run = program_trace.of_run(trace)
    if run is None or not run.in_window("lm.score"):
        return None
    load = [v for _e, v in sorted(tokens.counter(
        "mmlspark_moe_tokens_routed_total", "expert").items())]
    if not load or sum(load) <= 0:
        return None
    program_trace.say("moe_tokens_routed", {"per_expert": load})
    return max(load) / (sum(load) / len(load))
