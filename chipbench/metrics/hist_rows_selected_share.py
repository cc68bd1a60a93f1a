"""Histogram kernels: of the rows the masked grower's histogram calls were
handed in the traced window, the percent their masks selected —
``mmlspark_gbdt_hist_rows_total{kind="selected"}`` over ``{kind="streamed"}``.
A counter has no history, so the window's part of it is what the
``gbdt.chunk.unpack`` spans inside the window say they added (attrs
``hist_rows_streamed`` / ``hist_rows_selected``); the counter itself must
hold at least that much (program counter, chipbench/program_trace.py)."""

from chipbench import program_trace


def _counter() -> dict:
    try:
        from mmlspark_tpu import obs
    except ImportError:
        return {}
    fam = obs.REGISTRY.snapshot().get("mmlspark_gbdt_hist_rows_total") or {}
    return {labels.get("kind"): value for labels, value in fam.get("samples", [])}


def read(trace: dict, cell: dict) -> "float | None":
    run = program_trace.of_run(trace)
    if run is None:
        return None
    unpacks = [s for fit in run.in_window("gbdt.fit") for s in run.descendants(fit)
               if s["name"] == "gbdt.chunk.unpack"]
    streamed = sum(s["attrs"].get("hist_rows_streamed", 0) for s in unpacks)
    selected = sum(s["attrs"].get("hist_rows_selected", 0) for s in unpacks)
    if streamed <= 0:
        return None
    total = _counter()
    if total.get("streamed", 0) < streamed or total.get("selected", 0) < selected:
        raise ValueError(f"the window's spans add {streamed}/{selected} rows, more than "
                         f"the counter holds ({total})")
    program_trace.say("hist_rows", {"streamed": streamed, "selected": selected,
                                    "counter": total})
    return 100.0 * selected / streamed
