"""DataFrame to device feed: from the end of ``xla_model.drain`` (the last
batch's features are on the host) to the end of the ``featurize.partition``
span — ``xla_model.concat`` and what follows it — mean over the partitions
(chunks) of the traced window (program spans, chipbench/program_trace.py)."""

from chipbench import program_trace


def read(trace: dict, cell: dict) -> "float | None":
    run = program_trace.of_run(trace)
    if run is None:
        return None
    tails = []
    for root in run.in_window("featurize.partition"):
        drains = [s for s in run.descendants(root) if s["name"] == "xla_model.drain"]
        if drains:
            tails.append(root["end"] - max(s["end"] for s in drains))
    if not tails:
        return None
    return sum(tails) / len(tails) / 1e6
