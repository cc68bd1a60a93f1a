"""Entry points, device bootstrap: the time of set-up spent inside the
program or inside a compile request — the union of every ``obs`` span that
ended before the window began, root spans (the package imports, the
warm-up's ``featurize.partition`` / ``lm.score`` / ``gbdt.fit``) and orphan
``xla.*`` spans (the harness's own weight and input programs) alike.
``setup_s`` less this is the interpreter's and JAX's start and the
harness's inputs. Milliseconds by root span name and the span count of the run
go to standard error beside it (program spans, chipbench/setup_trace.py)."""

from chipbench import program_trace, setup_trace


def read(trace: dict, cell: dict) -> "float | None":
    spans = setup_trace.before_window(trace)
    ms = setup_trace.program_ms(spans)
    if ms is not None:
        program_trace.say("setup_program_ms_by_root", setup_trace.by_root(spans))
        program_trace.say("setup_spans", {
            "before_window": len(spans), "whole_run": len(program_trace.program_spans(None))})
    return ms
