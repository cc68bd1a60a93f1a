"""GBDT trainer: device time of the passes that prepare a histogram call —
the operations whose HLO ``op_name`` lies under the grower's
``gbdt.hist.mask`` (mask times stats), ``gbdt.hist.pad`` (padding to the
kernel's blocks) and ``gbdt.hist.widen`` (uint8 bins to int32) scopes — on
one device inside the traced window, per tree. A fusion counts under the
scope of its root. All scopes of the grower go to standard error beside it
(device trace, chipbench/program_trace.py)."""

from chipbench import program_trace

PREP = ("gbdt.hist.mask", "gbdt.hist.pad", "gbdt.hist.widen")
GROWER = PREP + ("plane_histogram", "multi_plane_histogram", "gbdt.best_split",
                 "gbdt.apply_split")


def read(trace: dict, cell: dict) -> "float | None":
    trees = cell["shapes"].get("trees")
    run = program_trace.of_run(trace)
    if run is None or not trees:
        return None
    by_scope = run.seconds_by_scope(GROWER)
    prep = sum(by_scope.get(name, 0.0) for name in PREP)
    if prep <= 0:
        return None
    program_trace.say("device_ms_per_tree_by_scope",
                      {k: 1e3 * v / trees for k, v in sorted(by_scope.items())})
    return 1e3 * prep / trees
