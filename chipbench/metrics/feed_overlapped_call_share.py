"""DataFrame to device feed: percent of the traced window's
``xla_model.apply_batch`` calls that were *overlapped* — at the call's first
dispatch a batch of an earlier call on the same model was still in flight,
so the device's queue did not run empty between the two calls (a stream's
chunks). The program says so itself, in the span's ``overlapped`` attribute
(and counts it in ``mmlspark_xla_model_calls_total{start}``); a program
whose spans carry no such attribute gives nothing to read (program spans,
chipbench/program_trace.py)."""

from chipbench import program_trace


def read(trace: dict, cell: dict) -> "float | None":
    run = program_trace.of_run(trace)
    if run is None:
        return None
    calls = [s["attrs"]["overlapped"] for s in run.in_window("xla_model.apply_batch")
             if "overlapped" in s["attrs"]]
    if not calls:
        return None
    return 100.0 * sum(bool(c) for c in calls) / len(calls)
