"""Sparse expert layer: what the grouped-matmul kernel's tiling costs — the
(group, row tile) visits it made in the traced window over the row tiles that
held any routed row, the visits there would be if every expert's rows ended
on a tile's edge: ``mmlspark_moe_gmm_tiles_total{kind="visited"}`` over
``{kind="aligned"}``, both counted on the device from the layer's group
metadata and summed over the expert layers. 1.0 is a tiling no group edge
cuts; a tile two experts share is multiplied once for each. A counter has no
history, so the window's part of it is what the ``lm.score`` spans inside the
window say they added (attrs ``gmm_tiles_visited`` / ``gmm_tiles_aligned``);
the counter itself must hold at least that much. A program that runs no such
kernel (the parent; any program off a TPU) says nothing here (program
counter, chipbench/program_trace.py)."""

from chipbench import program_trace
from chipbench.metrics import lm_pad_token_share as tokens


def read(trace: dict, cell: dict) -> "float | None":
    run = program_trace.of_run(trace)
    if run is None:
        return None
    spans = run.in_window("lm.score")
    visited = sum(s["attrs"].get("gmm_tiles_visited", 0) for s in spans)
    aligned = sum(s["attrs"].get("gmm_tiles_aligned", 0) for s in spans)
    if aligned <= 0:
        return None
    total = tokens.counter("mmlspark_moe_gmm_tiles_total", "kind")
    if total.get("visited", 0) < visited or total.get("aligned", 0) < aligned:
        raise ValueError(f"the window's spans add {visited}/{aligned} tiles, more than "
                         f"the counter holds ({total})")
    program_trace.say("moe_gmm_tiles", {"visited": visited, "aligned": aligned,
                                        "counter": total})
    return visited / aligned
