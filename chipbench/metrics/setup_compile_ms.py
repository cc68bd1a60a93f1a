"""Entry points, device bootstrap: summed duration of the program's
``xla.compile`` spans — each compile request of the process, answered by
the backend compiler or by a retrieval from the persistent cache — that
ended before the window began: the part of ``setup_s`` spent compiling or
loading programs. The harness already fails a run that compiles inside the
window (program spans, chipbench/program_trace.py)."""

from chipbench import program_trace


def read(trace: dict, cell: dict) -> "float | None":
    ns = program_trace.setup_compile_ns(trace)
    return None if ns is None else ns / 1e6
