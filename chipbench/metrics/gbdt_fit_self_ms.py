"""GBDT trainer: what a ``gbdt.fit`` root span does not hand to a child —
its duration less what its direct children cover (``gbdt.gather`` is one of
them), mean per fit of the traced window. Small by design: large means a
span is missing. Every child's per-fit time goes to standard error beside
it (program spans, chipbench/program_trace.py)."""

from chipbench import program_trace


def read(trace: dict, cell: dict) -> "float | None":
    run = program_trace.of_run(trace)
    if run is None:
        return None
    times = run.child_times("gbdt.fit")
    if not times:
        return None
    chunk = run.child_times("gbdt.chunk")
    program_trace.say("gbdt.fit_ms", {k: v / 1e6 for k, v in sorted(times.items())})
    program_trace.say("gbdt.chunk_ms", {k: v / 1e6 for k, v in sorted(chunk.items())})
    return times["self"] / 1e6
