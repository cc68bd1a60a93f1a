"""DataFrame to device feed: from the start of a ``featurize.partition``
span to the start of its first ``xla_model.dispatch`` — coercion, padding,
the weights and the compiled program looked up, and the staging of the
first batch, before anything is dispatched — mean over the partitions
(chunks) of the traced window. The parts go to standard error beside it
(program spans, chipbench/program_trace.py)."""

from chipbench import program_trace


def read(trace: dict, cell: dict) -> "float | None":
    run = program_trace.of_run(trace)
    if run is None:
        return None
    waits, parts = [], {}
    for root in run.in_window("featurize.partition"):
        inside = sorted(run.descendants(root), key=lambda s: s["start"])
        first = [s for s in inside if s["name"] == "xla_model.dispatch"]
        if not first:
            continue
        until = first[0]["start"]
        waits.append(until - root["start"])
        for s in inside:
            # leaves only: apply_batch covers its own children
            if s["end"] <= until and not run.children(s):
                parts[s["name"]] = parts.get(s["name"], 0.0) + (s["end"] - s["start"])
    if not waits:
        return None
    program_trace.say("feed_prepare_ms_per_chunk_by_span",
                      {k: v / len(waits) / 1e6 for k, v in sorted(parts.items())})
    return sum(waits) / len(waits) / 1e6
