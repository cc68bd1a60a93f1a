"""DataFrame to device feed: host time in ``xla_model.stage`` — one batch
through ``shard_batch`` (the host's re-tiling and the hand-over to the
transfer) — mean over the batches of the traced window. Overlapped with the
device's work on the batches before it, except for a chunk's first
(program spans, chipbench/program_trace.py)."""

from chipbench import program_trace


def read(trace: dict, cell: dict) -> "float | None":
    run = program_trace.of_run(trace)
    if run is None:
        return None
    stages = run.in_window("xla_model.stage")
    if not stages:
        return None
    return sum(s["end"] - s["start"] for s in stages) / len(stages) / 1e6
