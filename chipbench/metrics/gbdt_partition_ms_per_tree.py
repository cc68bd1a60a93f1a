"""GBDT trainer: device time of the partitioned grower's row movement — the
operations whose HLO ``op_name`` lies under its ``gbdt.partition`` scope
(the parent's bucket sliced out, split stably into a left and a right
block, and written back) — on one device inside the traced window, per
tree. The scope does not hold the histogram call of the smaller child, so
an operation under ``gbdt.hist.*`` or a kernel inside it counts there and
not here: the innermost of the grower's scopes decides, as for
``gbdt_hist_prep_ms_per_tree``. A program with no such scope (the masked
grower) reads nothing (device trace, chipbench/program_trace.py)."""

from chipbench import program_trace
from chipbench.metrics import gbdt_hist_prep_ms_per_tree as prep

PARTITION = "gbdt.partition"


def read(trace: dict, cell: dict) -> "float | None":
    trees = cell["shapes"].get("trees")
    run = program_trace.of_run(trace)
    if run is None or not trees:
        return None
    seconds = run.seconds_by_scope(prep.GROWER + (PARTITION,)).get(PARTITION, 0.0)
    if seconds <= 0:
        return None
    program_trace.say("partition_ms_per_tree", {PARTITION: 1e3 * seconds / trees})
    return 1e3 * seconds / trees
